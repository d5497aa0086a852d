"""Production mesh builders.

Functions, not module-level constants — importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device)."""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh(shape, axes, axis_types=(auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (TPU v5e pod slice); 2 pods = 512 chips
    with a leading 'pod' axis for cross-pod data parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_factored_mesh(*, multi_pod: bool = False, factors=(8, 2)):
    """Same 256 chips/pod, but the model axis is FACTORED (model=8 ×
    model2=2): architectures whose head counts don't divide 16 (MiniCPM3:
    40 heads, Llama-4: 40) can shard heads over the 8-sub-axis while
    mlp/vocab still use all 16 — beyond-paper optimization, see
    EXPERIMENTS.md §Perf."""
    shape = (2, 16) + factors if multi_pod else (16,) + factors
    axes = ("pod", "data", "model", "model2") if multi_pod else \
        ("data", "model", "model2")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist (tests: 1 CPU → (1,1))."""
    n = len(jax.devices())
    d = 1
    for cand in (16, 8, 4, 2, 1):
        if n % cand == 0 and n >= cand:
            d = cand
            break
    return _mesh((n // d, d), ("data", "model"))
