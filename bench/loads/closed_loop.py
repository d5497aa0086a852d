"""Closed loop: ``clients`` callers each send one single-source query,
wait for its answer and send the next, with no think time.

Every run sends the same work: a fixed sequence of ``pool`` sources,
drawn once with ``pool_seed`` (``sources``: ``uniform`` over all
vertices, or ``degree_ge_1`` over vertices with an edge, as Graph500
draws its BFS roots), taken ``clients`` at a time, one wave after
another, and cycled when it runs out.  The run's seed draws which
caller sends which of a wave's sources (and, in the data, the edge
weights).  Which sources a wave holds sets how many sweeps it takes, so
a sequence drawn anew for every seed would change the work from run to
run.  Callers stop sending at the window's end; queries in flight then
are drained and counted.  The callers start together, so the first wave
is as full as the rest.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import harness


def _candidates(graph, mix) -> np.ndarray:
    if mix["sources"] == "degree_ge_1":
        deg = graph.out_degrees() + np.bincount(graph.indices,
                                                minlength=graph.n)
        return np.flatnonzero(deg > 0)
    return np.arange(graph.n)


def sequences(graph, mix: dict, seed: int) -> np.ndarray:
    """``(clients, waves)``: caller ``i``'s ``k``-th source is
    ``seq[i, k % waves]``.  Wave ``k`` is the pool's ``k``-th group of
    ``clients`` sources, dealt to the callers in the seed's order."""
    clients, size = int(mix["clients"]), int(mix["pool"])
    if size % clients:
        raise ValueError(f"pool {size} is not a multiple of {clients} "
                         "clients")
    cands = _candidates(graph, mix)
    pool = cands[np.random.default_rng(int(mix["pool_seed"])).integers(
        len(cands), size=size)].reshape(-1, clients)
    deal = harness.rng(seed, 2)
    return np.stack([wave[deal.permutation(clients)] for wave in pool],
                    axis=1)


def queries(graph, mix: dict, seed: int, count: int) -> list:
    """The first ``count`` sources the callers send, one from each
    caller in turn."""
    seq = sequences(graph, mix, seed)
    clients, waves = seq.shape
    return [int(seq[k % clients, (k // clients) % waves])
            for k in range(count)]


def _spec(system, src: int):
    from repro.core.api import QuerySpec
    return QuerySpec(algo=system.mix["algo"], sources=(int(src),))


def wave_sizes(clients: int, max_wave: int) -> list:
    """The wave sizes a closed loop sends: the callers start together
    and are answered together, so every wave is full, and with more
    callers than ``max_wave`` the rest make one more wave."""
    full, rest = divmod(clients, max_wave)
    return ([max_wave] if full else []) + ([rest] if rest else [])


def warm(system) -> None:
    """Run each wave size the window sends once, through the service's
    own wave path, from a source with the fewest out-edges (one at
    least): the same programs as the window's waves.  Other sizes appear only if a wave
    splits (a caller more than ``max_wait_s`` late); the harness then
    reports compiles inside the window."""
    svc = system.server.service
    deg = system.graph.out_degrees()
    src = int(np.flatnonzero(deg == deg[deg > 0].min())[0])
    for q in wave_sizes(int(system.mix["clients"]),
                        int(system.cfg["wave"]["max_wave"])):
        for _ in range(q):
            svc.submit(system.name, _spec(system, src))
        for ticket, res in svc.gather().items():
            if isinstance(res, Exception):
                raise res


def run(system, t_open: float, t_end: float) -> list:
    seq = sequences(system.graph, system.mix, system.seed)
    records, lock = [], threading.Lock()
    server, span = system.server, harness.span
    clients = int(system.mix["clients"])
    start = threading.Barrier(clients)

    def client(i: int) -> None:
        start.wait()
        k = 0
        while time.perf_counter() < t_end:
            src = int(seq[i, k % seq.shape[1]])
            k += 1
            t0 = time.perf_counter()
            try:
                with span("submit"):
                    fut = server.submit(system.name, _spec(system, src))
                with span("wave_wait"):
                    res = fut.result()
            except Exception as e:  # counted as failed, never fatal
                rec = harness.record(src, t0, error=repr(e))
            else:
                with span("result_fetch"):
                    rec = harness.record(src, t0, res)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"bench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records


def p95(values) -> float:
    """Nearest-rank 95th percentile.  The callers of a wave are answered
    together, so every latency is its wave's time: over the 24 or so
    answers of a road-graph window this is the slowest wave."""
    v = sorted(values)
    return float(v[max(int(np.ceil(0.95 * len(v))) - 1, 0)])


def end_to_end(records: list, t_open: float) -> dict:
    ok = [r for r in records if r.ok]
    if not records:
        return {}
    t_last = max(r.t_done for r in records)
    return {"queries_per_s": len(ok) / (t_last - t_open),
            "query_p95_s": p95([r.t_done - r.t_submit for r in records])}
