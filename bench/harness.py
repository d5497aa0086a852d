"""The benchmark harness: finds a cell's files by name, sets the system
up, drives the measured window, checks the answers against the plain
reference and builds the result line.

Everything that belongs to one configuration, traffic mix, load kind,
check or per-layer metric lives in a file of its own that ``Registry``
finds by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json    graph generator, plan and policies
  traffic/<mix>.json       load kind and its parameters
  loads/<kind>.py          warm(system), run(window) and end_to_end()
  checks/<algo>.py         compare(graph, items, control) -> numbers
  limits/<cell>.json       the limit of each number a cell compares
  metrics/<metric>.py      read(window) -> value or None
  data/<generator>.py      generate(params, seed) -> Csr

From the system it takes only the served path (``GraphServer`` and what
lies beneath it) and the counters it exposes.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

GB = 1e9


# -- discovery ---------------------------------------------------------------


class Registry:
    """Finds a cell's files by name.  ``dirs`` are searched in order, so
    a test can put a directory of its own in front of ``bench/``."""

    def __init__(self, spec: dict, dirs=(BENCH,), root: str = ROOT):
        self.spec = spec
        self.dirs = tuple(dirs)
        self.root = root

    @classmethod
    def load(cls, root: str = ROOT, dirs=(BENCH,)) -> "Registry":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return cls(json.load(f), dirs=dirs, root=root)

    def _find(self, sub: str, name: str, ext: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, sub, name + ext)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"no {sub}/{name}{ext} under {list(self.dirs)}")

    def _json(self, sub: str, name: str) -> dict:
        with open(self._find(sub, name, ".json")) as f:
            return json.load(f)

    def _module(self, sub: str, name: str):
        path = self._find(sub, name, ".py")
        if os.path.dirname(os.path.dirname(path)) == BENCH and \
                "." not in name:
            return importlib.import_module(f"bench.{sub}.{name}")
        mod_name = f"bench_{sub}_{name.replace('.', '_')}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("limits", cell)

    def load_kind(self, kind: str):
        return self._module("loads", kind)

    def check(self, algo: str):
        return self._module("checks", algo)

    def generator(self, name: str):
        return self._module("data", name)

    def metric(self, name: str):
        return self._module("metrics", name)

    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        reported = {m["name"] for m in self.end_to_end(cell)}

        def applies(m):
            if "workloads" in m:
                return cell in m["workloads"]
            return m["moves"] in reported

        return [m for m in self.spec["per_layer"] if applies(m)]


# -- compile accounting (copied from the system's chip_smoke.Compiles) -------


class Compiles:
    """Seconds JAX spends tracing, lowering and compiling, and how many
    backend compiles ran, read from ``jax.monitoring`` events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.backend = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.BACKEND:
            self.backend += 1


# -- the system under test ---------------------------------------------------


def _seq(seed: int, *stream: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) & (2 ** 64 - 1), *stream])


def rng(seed: int, *stream: int) -> np.random.Generator:
    """The run's random stream ``stream`` (1: weights, 2: traffic,
    3: the sample that is checked)."""
    return np.random.default_rng(_seq(seed, *stream))


@dataclasses.dataclass
class System:
    """One cell's system, set up and warm."""

    name: str               # the graph's name in the server
    cfg: dict
    mix: dict
    graph: Any              # bench.data.csr.Csr
    server: Any             # repro GraphServer
    load: Any               # the load kind's module
    seed: int
    setup: Dict[str, float]


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def setup(reg: Registry, cell: dict, seed: int, compiles: Compiles,
          log=print) -> System:
    """Generate the data, hand it to the server, build the cell's plan
    and warm every program the window will use."""
    import jax
    from repro import api
    from repro.core.algorithms import get_algorithm
    from repro.core.graph import Graph
    from repro.serve.sched import WavePolicy

    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    load = reg.load_kind(mix["load"])
    t = time.perf_counter()
    csr = reg.generator(cfg["generator"]).generate(
        cfg["generator_params"], seed)
    log(f"data generator={cfg['generator']} n={csr.n} edges={csr.nnz} "
        f"seconds={time.perf_counter() - t}")
    g = Graph(n=csr.n, indptr=csr.indptr, indices=csr.indices,
              weights=csr.weights)
    stats = jax.devices()[0].memory_stats() or {}
    budget = int(stats.get("bytes_limit", 1 << 40))
    pol = cfg["policy"]
    policy = api.ExecutionPolicy(
        mode=pol["mode"], kernel=api.KernelSpec(impl=pol["kernel"]),
        degrade=bool(pol["degrade"]))
    wave = WavePolicy(**cfg["wave"])

    c0 = compiles.seconds
    t0 = time.perf_counter()
    svc = api.GraphService(max_plan_bytes=budget, policy=policy,
                           max_wave=wave.max_wave)
    server = api.GraphServer(service=svc, wave=wave, warm_limit=0)
    proc = server.register(cell["config"], g, b=int(cfg["b"]),
                           num_clusters=int(cfg["num_clusters"]),
                           warm=False)
    a = get_algorithm(mix["algo"])
    tp = time.perf_counter()
    p = proc.prepare(a.semiring, variant=a.variant, pull=a.pull,
                     normalize=a.normalize)
    p.vals.block_until_ready()
    plan_build_s = time.perf_counter() - tp
    log(f"plan build_s={plan_build_s} plan_bytes={p.nbytes} "
        f"r_pad={p.r_pad} k={p.k_max} tiles={int(p.tiles_total)}")
    del p
    system = System(name=cell["config"], cfg=cfg, mix=mix, graph=csr,
                    server=server, load=load, seed=seed, setup={})
    load.warm(system)
    system.setup = dict(setup_s=time.perf_counter() - t0,
                        plan_build_s=plan_build_s,
                        compile_s=compiles.seconds - c0)
    return system


# -- the measured window -----------------------------------------------------


@dataclasses.dataclass
class Record:
    """One query or job of the window."""

    query: Any                 # source vertex, or damping
    t_submit: float
    t_done: float
    values: Optional[np.ndarray] = None
    stats: Any = None          # the engine's RunStats (shared by a wave)
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


def record(query, t_submit: float, result=None, error=None) -> Record:
    """A Record from a finished query: a Result whose ``extra`` says it
    was degraded counts as failed."""
    t_done = time.perf_counter()
    if result is None:
        return Record(query, t_submit, t_done, error=error)
    if "degraded" in result.extra:
        return Record(query, t_submit, t_done,
                      error=f"degraded {result.extra['degraded']}")
    return Record(query, t_submit, t_done, values=np.asarray(result.values),
                  stats=result.stats)


@dataclasses.dataclass
class Window:
    """What a per-layer metric reads."""

    records: List[Record]
    t_open: float
    t_close: float              # the last completion
    sched_before: dict
    sched_after: dict
    max_wave: int
    setup: Dict[str, float]
    trace: Any = None           # bench.trace.Summary, traced runs only


def run_window(system: System, seconds: float, compiles: Compiles,
               trace_dir: Optional[str] = None) -> Window:
    import jax
    sched0 = system.server.stats()["scheduler"]
    b0 = compiles.backend
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with span("window"):
            t_open = time.perf_counter()
            records = system.load.run(system, t_open, t_open + seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    t_close = max((r.t_done for r in records), default=t_open)
    in_window = compiles.backend - b0
    if in_window:
        print(f"warning: {in_window} compiles inside the window",
              file=sys.stderr)
    return Window(records=records, t_open=t_open,
                  t_close=t_close, sched_before=sched0,
                  sched_after=system.server.stats()["scheduler"],
                  max_wave=int(system.cfg["wave"]["max_wave"]),
                  setup=system.setup)


# -- correctness -------------------------------------------------------------


def checked_items(system: System, records: List[Record]) -> list:
    """The answers compared: all of them, or ``check_sample`` drawn from
    the seed."""
    ok = [r for r in records if r.ok]
    k = int(system.mix.get("check_sample", 0)) or len(ok)
    pick = sorted(rng(system.seed, 3).choice(len(ok), min(k, len(ok)),
                                             replace=False)) if ok else []
    return [(ok[i].query, ok[i].values) for i in pick]


def judge(numbers: Dict[str, float], limits: dict) -> Dict[str, dict]:
    out = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r}")
        out[name] = {"value": value, "limit": limits[name]["limit"]}
    return out


# -- one run -----------------------------------------------------------------


def run_cell(reg: Registry, cell_name: str, seed: int, seconds: float,
             trace: bool, device_line=None, log=print) -> dict:
    """Set up, measure, check; returns the result line as a dict."""
    import jax
    from . import trace as tr
    cell = reg.cell(cell_name)
    compiles = Compiles()
    system = setup(reg, cell, seed, compiles, log=log)
    log(f"setup setup_s={system.setup['setup_s']} "
        f"plan_build_s={system.setup['plan_build_s']} "
        f"compile_s={system.setup['compile_s']}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        win = run_window(system, seconds, compiles, trace_dir)
        if trace:
            kw = {} if device_line is None else {"device_line": device_line}
            win.trace = tr.reduce(tr.find_xplane(trace_dir), **kw)
            log(f"trace inventory {win.trace.inventory}")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    devs = jax.devices()[:int(cell["chips"])]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peak = max((p for p in peaks if p is not None), default=None)
    system.server.close()
    server_stats = system.server.stats()
    system.server = None
    gc.collect()

    records = win.records
    failed = sum(not r.ok for r in records)
    for r in records:
        if not r.ok:
            log(f"failed query={r.query} error={r.error}")
    candidates = dict(system.load.end_to_end(records, win.t_open),
                      setup_s=system.setup["setup_s"])
    if peak is not None:
        candidates["hbm_peak_gb"] = peak / GB
    metrics = {}
    if trace:
        for m in reg.per_layer(cell_name):
            v = reg.metric(m["name"]).read(win)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in reg.end_to_end(cell_name):
            if candidates.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": candidates[m["name"]],
                                      "unit": m["unit"]}
    log(f"window queries={len(records)} failed={failed} "
        f"seconds={win.t_close - win.t_open} "
        f"scheduler={server_stats['scheduler']}")

    t = time.perf_counter()
    items = checked_items(system, records)
    numbers = reg.check(system.mix["algo"]).compare(system.graph, items)
    checks = judge(numbers, reg.limits(cell_name))
    log(f"reference compared={len(items)} "
        f"seconds={time.perf_counter() - t}")
    correct = bool(items) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": len(records),
            "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=win.trace.busy_s, window_s=win.trace.window_s)
        line["breakdown"] = {
            "device_ops": [list(x) for x in win.trace.device_ops],
            "idle_gaps": [list(x) for x in win.trace.idle_gaps]}
    line["checks"] = checks
    return line
