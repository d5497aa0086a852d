"""Plan build: the plan's tile bytes on its fullest device, in GB,
``shard_bytes`` of the ``plan.upload`` spans that end before the window
opens (the largest, where set-up built more than one plan).  ``None``
when none records ``shard_bytes``."""

from bench.metrics import _spans


def read(win):
    lo, _ = _spans.window_ns(win)
    got = [s.attrs["shard_bytes"] for s in _spans.spans("plan.upload")
           if s.end_ns <= lo and "shard_bytes" in s.attrs]
    return max(got) / 1e9 if got else None
