"""repro.obs: spans at the layer boundaries of the served path and the
plan build, and the named scopes in the jitted engine loops."""

import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.core import engine as eng
from repro.core import graph as G
from repro.kernels.spec import KernelSpec


@pytest.fixture(scope="module")
def road():
    return G.road_network(8, seed=1)


def sssp(s):
    return api.QuerySpec(algo="sssp", sources=(s,))


def by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_nested_spans_record_parents_and_inherit_the_wave():
    obs.clear()
    with obs.span("outer", wave=7) as outer:
        with obs.span("inner") as inner:
            pass
        late = obs.record("cross", 1, 2)
    with obs.span("alone"):
        pass
    s = {x.name: x for x in obs.spans()}
    assert s["outer"].parent_id is None
    assert s["inner"].parent_id == outer.span_id == s["outer"].span_id
    assert s["inner"].span_id == inner.span_id
    assert s["inner"].attrs["wave"] == 7 and late.attrs["wave"] == 7
    assert late.parent_id == outer.span_id
    assert s["alone"].parent_id is None and "wave" not in s["alone"].attrs
    assert s["outer"].start_ns <= s["inner"].start_ns \
        <= s["inner"].end_ns <= s["outer"].end_ns
    assert outer.last_child_end_ns == s["inner"].end_ns
    assert obs.spans("inner") == [s["inner"]]


def test_parents_are_per_thread():
    obs.clear()
    seen = []

    def other():
        with obs.span("other") as o:
            seen.append(o.parent_id)

    with obs.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(10)
    assert not t.is_alive() and seen == [None]


def test_the_ring_is_bounded_and_counts_what_it_drops():
    rec = obs.Recorder(maxlen=4)
    for i in range(6):
        rec.record("s", i, i + 1, i=i)
    assert [s.attrs["i"] for s in rec.spans()] == [2, 3, 4, 5]
    assert rec.dropped == 2
    rec.clear()
    assert rec.spans() == [] and rec.dropped == 0


def test_a_fresh_jit_records_a_compile_span():
    obs.clear()

    def fresh_kernel_for_obs(x):
        return x * 3 + 1

    t0 = time.perf_counter_ns()
    jax.jit(fresh_kernel_for_obs)(jnp.arange(5.0)).block_until_ready()
    comp = [s for s in obs.spans("jax.compile")
            if "fresh_kernel_for_obs" in s.attrs["fun_name"]]
    assert len(comp) == 1
    assert t0 <= comp[0].start_ns <= comp[0].end_ns \
        <= time.perf_counter_ns()


def test_plan_build_phases_are_spans(road):
    obs.clear()
    proc = api.GraphProcessor(road, b=16, num_clusters=4)
    proc.prepare("min_plus")
    phases = [s.name for s in obs.spans() if s.name.startswith("plan.")]
    assert phases == ["plan.cluster", "plan.tile", "plan.upload"]


def test_two_waves_give_one_span_of_each_kind_per_wave(road):
    svc = api.GraphService()
    svc.register("roads", road, b=16, num_clusters=4)
    svc.run("roads", sssp(0))           # plan built, programs warm
    server = api.GraphServer(
        service=svc, wave=api.WavePolicy(max_wave=2, max_wait_s=60.0),
        autostart=False)
    obs.clear()
    futs = [server.submit("roads", sssp(s)) for s in (1, 2, 3)]
    server.close()                      # forced flush: waves 2 and 1
    for f in futs:
        f.result(60)
    st = server.stats()["scheduler"]
    assert (st["waves"], st["wave_queries"]) == (2, 3)
    assert (st["closed_full"], st["closed_wait"], st["closed_forced"]) \
        == (1, 0, 1)

    s = by_name(obs.spans())
    waves = {w.attrs["wave"]: w for w in s["wave"]}
    assert len(waves) == 2
    assert sorted((w.attrs["size"], w.attrs["closed"])
                  for w in waves.values()) == [(1, "forced"),
                                               (2, "full")]
    for name in ("wave.launch", "wave.resolve"):
        assert sorted(x.attrs["wave"] for x in s[name]) == sorted(waves)
    # one queue span per request, naming its ticket and its wave
    q = s["request.queue"]
    assert len(q) == 3 and len({x.attrs["ticket"] for x in q}) == 3
    for w, span in waves.items():
        mine = [x for x in q if x.attrs["wave"] == w]
        assert len(mine) == span.attrs["size"]
        # the run's phases are children of the wave, and carry its id
        for name in ("run.prep", "run.device", "run.fetch"):
            kids = [x for x in s[name] if x.attrs.get("wave") == w]
            assert len(kids) == 1 and kids[0].parent_id == span.span_id
        res = [x for x in s["wave.resolve"] if x.attrs["wave"] == w][0]
        fetch = [x for x in s["run.fetch"] if x.attrs["wave"] == w][0]
        assert res.parent_id == span.span_id
        assert res.start_ns == fetch.end_ns <= res.end_ns <= span.end_ns
        launch = [x for x in s["wave.launch"] if x.attrs["wave"] == w][0]
        assert max(x.end_ns for x in mine) == launch.start_ns \
            <= launch.end_ns <= span.start_ns


def test_a_wave_that_waited_out_counts_as_closed_wait(road):
    svc = api.GraphService()
    svc.register("roads", road, b=16, num_clusters=4)
    with api.GraphServer(service=svc, wave=api.WavePolicy(
            max_wave=8, max_wait_s=0.01)) as server:
        server.submit("roads", sssp(4)).result(60)
        st = server.stats()["scheduler"]
    assert (st["closed_full"], st["closed_wait"], st["closed_forced"]) \
        == (0, 1, 0)


def _loops(p, x0, ch0):
    f32 = jnp.float32
    ref = KernelSpec(impl="ref")
    yield "engine.async_loop", eng._async_loop.lower(
        p.vals, p.cols, p.nnz, p.valid, p.dangling, p.group_tiles,
        p.group_edges, p.group_ext_tiles, p.row_edges, p.row_ext, x0, ch0,
        f32(0.85), f32(1e-6), f32(1.0), "min_plus", "relax", 100, p.gb,
        p.s, ref)
    yield "engine.sync_loop", eng._sync_loop.lower(
        p.vals, p.cols, p.nnz, p.valid, p.dangling, x0, f32(0.85),
        f32(1e-6), f32(1.0), "min_plus", "relax", 100, ref)


def test_engine_loops_carry_scopes_and_nothing_else(road):
    p = api.GraphProcessor(road, b=16, num_clusters=4).prepare("min_plus")
    x0 = p.to_blocks(np.full(road.n, np.inf, np.float32), np.inf)
    ch0 = jnp.ones(p.r_pad, dtype=bool)
    for loop, lowered in _loops(p, x0, ch0):
        hlo = lowered.compile().as_text()
        names = set(re.findall(r'op_name="([^"]*)"', hlo))
        assert any(re.search(loop + r"/.*sweep\.spmv", n) for n in names)
        assert any("sweep.apply" in n for n in names)
        if loop == "engine.async_loop":
            assert any("sweep.frontier" in n for n in names)
        # scopes are metadata only: no host callback, no custom call
        text = lowered.as_text()
        assert "custom_call" not in text and "callback" not in text
