"""The closed-loop and jobs loads through a real GraphServer on a tiny
graph: the drain, the rate and the 95th percentile."""

import numpy as np
import pytest

from bench import harness
from bench.loads import closed_loop, jobs

SECONDS = 1.0


def _window(reg, cell):
    compiles = harness.Compiles()
    system = harness.setup(reg, reg.cell(cell), 77, compiles,
                           log=lambda m: None)
    try:
        win = harness.run_window(system, SECONDS, compiles)
    finally:
        system.server.close()
    return system, win


def test_p95_is_nearest_rank():
    assert closed_loop.p95(range(1, 21)) == 19
    assert closed_loop.p95(range(1, 101)) == 95
    assert closed_loop.p95([4.0]) == 4.0


@pytest.mark.parametrize("cell", ["tiny.sssp", "tiny.bfs"])
def test_closed_loop_drains_and_counts(tiny, cell):
    system, win = _window(tiny, cell)
    recs = win.records
    assert recs and all(r.ok for r in recs)
    end = win.t_open + SECONDS
    # nobody sends after the deadline; what was in flight is drained
    assert all(r.t_submit < end for r in recs)
    assert win.t_close == max(r.t_done for r in recs)
    # each caller's queries follow its own seeded sequence
    sent = sorted(r.query for r in recs)
    assert len(recs) == win.sched_after["wave_queries"] - \
        win.sched_before["wave_queries"]
    e2e = closed_loop.end_to_end(recs, win.t_open)
    assert e2e["queries_per_s"] == pytest.approx(
        len(recs) / (win.t_close - win.t_open))
    lat = sorted(r.t_done - r.t_submit for r in recs)
    k = int(np.ceil(0.95 * len(lat))) - 1
    assert e2e["query_p95_s"] == lat[k]
    expect = closed_loop.queries(system.graph, system.mix, 77, 200)
    assert set(sent) <= set(expect)


def test_every_seed_sends_the_same_waves():
    from bench.data import road_network
    g = road_network.generate(dict(side=20, keep_frac=0.68,
                                   extra_frac=0.05, topology_seed=0,
                                   weight_low=1.0, weight_high=10.0), 1)
    mix = dict(clients=4, sources="uniform", pool=40, pool_seed=0)
    a = closed_loop.sequences(g, mix, 1)
    b = closed_loop.sequences(g, mix, 2 ** 33 + 9)
    assert a.shape == b.shape == (4, 10)
    np.testing.assert_array_equal(np.sort(a, axis=0), np.sort(b, axis=0))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, closed_loop.sequences(g, mix, 1))
    # queries() follows the callers in turn, wave after wave, cycling
    q = closed_loop.queries(g, mix, 1, 48)
    assert q[:8] == [int(x) for x in a[:, :2].T.ravel()]
    assert q[40:] == q[:8]
    with pytest.raises(ValueError):
        closed_loop.sequences(g, dict(mix, pool=42), 1)


def test_jobs_drain_and_rate(tiny):
    system, win = _window(tiny, "tiny.pagerank")
    recs = win.records
    assert recs and all(r.ok for r in recs)
    assert all(r.t_submit < win.t_open + SECONDS for r in recs)
    # one caller: jobs run back to back, in the seeded order
    assert [r.query for r in recs] == jobs.queries(
        system.graph, system.mix, 77, len(recs))
    e2e = jobs.end_to_end(recs, win.t_open)
    assert e2e["job_s"] == pytest.approx(
        (win.t_close - win.t_open) / len(recs))


def test_dampings_are_stratified():
    mix = dict(damping_low=0.8, damping_high=0.9, strata=4)
    d = jobs.queries(None, mix, 3, 8)
    assert all(0.8 <= x < 0.9 for x in d)
    for block in (d[:4], d[4:]):
        assert sorted(int((x - 0.8) / 0.025) for x in block) == [0, 1, 2, 3]
