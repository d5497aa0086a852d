"""BFS levels against the reference's hop levels, exactly.

``bfs_level_mismatch``: vertices, over the compared queries, whose level
(or reachability) differs from the reference.
"""

from __future__ import annotations

import numpy as np

from .. import reference


def compare(g, items, control: bool = False) -> dict:
    """``items``: (root, levels) pairs; ``control`` puts the reference's
    one-level-short form in the system's place."""
    mismatch = 0
    for src, values in items:
        ref = reference.bfs(g, src)
        got = reference.bfs_control(g, src) if control else \
            np.asarray(values, np.float64)
        mismatch += int(np.count_nonzero(got != ref))
    return {"bfs_level_mismatch": float(mismatch)}
