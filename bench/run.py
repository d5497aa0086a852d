"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload ca_road.sssp_c8 --seed 7 \\
        --seconds 51 --trace 0

Progress and the compared numbers go to stderr; the last line of stdout
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
each compared number beside its limit).  With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics.  The run refuses, with no result line, when JAX finds no TPU
or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The system's persistent compilation cache (``<checkout>/.jax_cache``
    or ``JAX_COMPILATION_CACHE_DIR``), holding every program however
    quick its compile, so a checkout's later runs compile nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, peaks
    reg = harness.Registry.load(ROOT)
    cell = reg.cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU found (JAX platform {devs[0].platform!r}); "
            "the benchmark runs on a TPU only")
        return 2
    if len(devs) < int(cell["chips"]):
        log(f"bench: {args.workload} needs {cell['chips']} chips, "
            f"found {len(devs)}")
        return 2
    peaks.lookup(devs[0].device_kind)
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} compile_cache={enable_compile_cache()}")

    line = harness.run_cell(reg, args.workload, args.seed, args.seconds,
                            bool(args.trace), log=log)
    for name, c in line["checks"].items():
        log(f"check {name} value={c['value']!r} limit={c['limit']!r}")
    log(f"correct={line['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
