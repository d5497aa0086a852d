"""PageRank vectors against float64 power iteration run to 1e-14.

``pagerank_l1_err``: the largest, over the compared jobs, of
sum_v |x_v - x_ref_v| (both vectors sum to 1).
"""

from __future__ import annotations

import numpy as np

from .. import reference


def compare(g, items, control: bool = False) -> dict:
    """``items``: (damping, ranks) pairs; ``control`` puts the
    reference's bfloat16 form in the system's place."""
    l1 = 0.0
    for damping, values in items:
        ref = reference.pagerank(g, damping)
        got = reference.pagerank_control(g, damping) if control else \
            np.asarray(values, np.float64)
        l1 = max(l1, float(np.abs(got - ref).sum()))
    return {"pagerank_l1_err": l1}
