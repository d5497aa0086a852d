"""Whole-graph jobs back to back: one caller runs a job through
``GraphServer.run``, waits for it and starts the next.

Each job draws its damping from U[``damping_low``, ``damping_high``), so
no two jobs are the same computation and no result cache can answer
them.  The draws are stratified: every block of ``strata`` jobs takes
each of ``strata`` equal sub-intervals once, in an order drawn from the
seed, so every seed asks for the same spread of work.  The caller stops
starting jobs at the window's end; the job in flight is drained.
"""

from __future__ import annotations

import time

from .. import harness


def _spec(system, damping: float):
    from repro.core.api import QuerySpec
    return QuerySpec(algo=system.mix["algo"],
                     params={"damping": float(damping)})


def dampings(seed: int, mix: dict):
    lo, hi = float(mix["damping_low"]), float(mix["damping_high"])
    k = int(mix["strata"])
    draw = harness.rng(seed, 2)
    while True:
        for s in draw.permutation(k):
            yield lo + (hi - lo) * (s + draw.random()) / k


def queries(graph, mix: dict, seed: int, count: int) -> list:
    """The first ``count`` dampings the caller sends."""
    gen = dampings(seed, mix)
    return [next(gen) for _ in range(count)]


def warm(system) -> None:
    """One job: the window's only program."""
    mix = system.mix
    d = (float(mix["damping_low"]) + float(mix["damping_high"])) / 2
    system.server.service.run(system.name, _spec(system, d))


def run(system, t_open: float, t_end: float) -> list:
    records = []
    for d in dampings(system.seed, system.mix):
        if time.perf_counter() >= t_end:
            break
        t0 = time.perf_counter()
        try:
            with harness.span("job"):
                res = system.server.run(system.name, _spec(system, d))
        except Exception as e:  # counted as failed, never fatal
            records.append(harness.record(d, t0, error=repr(e)))
        else:
            with harness.span("result_fetch"):
                records.append(harness.record(d, t0, res))
    return records


def end_to_end(records: list, t_open: float) -> dict:
    ok = [r for r in records if r.ok]
    if not ok:
        return {}
    t_last = max(r.t_done for r in records)
    return {"job_s": (t_last - t_open) / len(ok)}
