"""SSSP answers against float64 Dijkstra.

``sssp_rel_err``: the widest gap, over the compared queries and every
vertex reachable in the reference, of |d - d_ref| / max(d_ref, 1).
``sssp_reach_mismatch``: vertices whose reachability differs (exact).
"""

from __future__ import annotations

import numpy as np

from .. import reference


def compare(g, items, control: bool = False) -> dict:
    """``items``: (source, values) pairs; ``control`` puts the
    reference's bfloat16 form in the system's place."""
    rel, mismatch = 0.0, 0
    for src, values in items:
        ref = reference.sssp(g, src)
        got = reference.sssp_control(g, src) if control else \
            np.asarray(values, np.float64)
        fin = np.isfinite(ref)
        mismatch += int(np.count_nonzero(fin != np.isfinite(got)))
        both = fin & np.isfinite(got)
        if both.any():
            gap = np.abs(got[both] - ref[both]) / np.maximum(ref[both], 1.0)
            rel = max(rel, float(gap.max()))
    return {"sssp_rel_err": rel, "sssp_reach_mismatch": float(mismatch)}
