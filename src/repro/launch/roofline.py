"""Roofline analysis from the dry-run dumps (EXPERIMENTS.md §Roofline).

Terms per (arch × shape), single-pod 16×16 mesh, from the per-device
composed cost analysis (see dryrun.py for the while-body composition):

  compute_s    = HLO_FLOPs_per_device / peak FLOP/s   (bf16 peak / chip)
  memory_s     = HLO_bytes_per_device / HBM bytes/s
  collective_s = collective_bytes_per_device / ICI bytes/s (1 link, worst
                 case serialization; v5e has 4 links → best case ÷4)

with the peaks of the pod's chip from ``PEAKS`` (the dry-run describes a
TPU v5e pod slice).

The dominant term is the bottleneck; roofline fraction for the dominant
term = useful/attained:  MODEL_FLOPS/(chips·peak·T_dom) when compute-
dominated, else term_ratio = T_dom / ΣT (how far overlap could help).

Usage: python -m repro.launch.roofline --in results/dryrun_single \
           [--md results/roofline.md]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, NamedTuple, Optional

from ..configs.base import get_config
from . import analytic
from .specs import SHAPES


class Peaks(NamedTuple):
    flops: float    # bf16 FLOP/s per chip
    hbm_bw: float   # HBM bytes/s per chip
    ici_bw: float   # ICI bytes/s per link


# Published per-chip peaks keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud TPU documentation, "TPU v5e" system architecture: 197
# TFLOP/s bf16, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect per chip over 4 links (50 GB/s per link).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
DRYRUN_DEVICE = "TPU v5 lite"   # the chip of the dry-run's pod slice
CHIPS = 256


def peaks(device_kind: str) -> Peaks:
    """Peaks of one chip; a device missing from ``PEAKS`` is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def kernel_roofline(flops: float, hbm_bytes: float, device_kind: str,
                    ici_bytes: float = 0.0) -> Dict:
    """Single-chip roofline for one kernel invocation (no dry-run dump):
    seconds per term, the dominant bottleneck, and the modeled runtime
    assuming perfect compute/memory overlap, on the peaks of
    ``device_kind``.  The kernel autotuner (kernels/autotune.py)
    validates its *measured* winner against this model — agreement
    means the measurement is believable, disagreement is recorded
    (measured always wins; the model can't see VMEM effects)."""
    pk = peaks(device_kind)
    t_compute = flops / pk.flops
    t_memory = hbm_bytes / pk.hbm_bw
    t_coll = ici_bytes / pk.ici_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    return {"t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "modeled_s": max(t_compute, t_memory) + t_coll}


def load_cells(directory: str) -> List[Dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def roofline_row(cell: Dict) -> Optional[Dict]:
    if cell.get("status") != "ok":
        return None
    comp = cell.get("composed") or {"cost": cell["full"]["cost"],
                                    "collectives":
                                        cell["full"]["collectives"]}
    cfg0 = get_config(cell["arch"])
    flops_dev = comp["cost"]["flops"] \
        + analytic.prefill_attention_correction(cfg0, cell["shape"])
    bytes_dev = comp["cost"]["bytes"]
    coll_dev = comp["collectives"].get("total_bytes", 0.0)
    pk = peaks(DRYRUN_DEVICE)
    t_compute = flops_dev / pk.flops
    t_memory = bytes_dev / pk.hbm_bw
    t_coll = coll_dev / pk.ici_bw
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)

    cfg = get_config(cell["arch"])
    an = analytic.model_flops(cfg, cell["shape"])
    hlo_total = flops_dev * CHIPS
    useful = an["model_flops"] / hlo_total if hlo_total else 0.0
    # attained fraction of the dominant roof if perfectly overlapped
    t_dom = terms[dominant]
    mfu_bound = an["model_flops"] / (CHIPS * pk.flops * t_dom) \
        if t_dom else 0.0
    return {
        "arch": cell["arch"], "shape": cell["shape"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": an["model_flops"], "hlo_flops_total": hlo_total,
        "useful_ratio": useful, "mfu_bound": mfu_bound,
        "peak_gib": cell["full"]["mem"]["peak_est_bytes"] / 2**30,
        "coll_bytes_dev": coll_dev,
        "collectives": {k: v for k, v in comp["collectives"].items()
                        if k not in ("total_bytes", "count")},
    }


def make_table(cells: List[Dict]) -> str:
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant "
        "| MODEL_FLOPS | useful (MF/HLO) | MFU bound | peak GiB |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    rows = []
    for c in cells:
        r = roofline_row(c)
        if r is None:
            lines.append(
                f"| {c['arch']} | {c['shape']} | — | — | — | "
                f"{c['status']}: {c.get('reason', c.get('error', ''))[:60]}"
                f" | | | | |")
            continue
        rows.append(r)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"**{r['dominant']}** | {r['model_flops']:.2e} | "
            f"{r['useful_ratio']:.2f} | {r['mfu_bound']:.2f} | "
            f"{r['peak_gib']:.2f} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="indir", default="results/dryrun_single")
    ap.add_argument("--md", default=None)
    ap.add_argument("--json", dest="json_out", default=None)
    args = ap.parse_args()
    cells = load_cells(args.indir)
    # order: arch registry order × shape order
    order = {s: i for i, s in enumerate(SHAPES)}
    cells.sort(key=lambda c: (c["arch"], order.get(c["shape"], 9)))
    table = make_table(cells)
    print(table)
    if args.md:
        with open(args.md, "w") as f:
            f.write("# Roofline (single-pod 16×16, per-step)\n\n")
            f.write(table + "\n")
    if args.json_out:
        rows = [r for r in (roofline_row(c) for c in cells) if r]
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
