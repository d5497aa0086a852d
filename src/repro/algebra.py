"""The NALE datapath algebra: semirings and update rules, defined once.

The paper's NALE (Node Arithmetic Logic Engine) is "optimized for fast MAC
operations with a three-state output comparator".  Algebraically that is a
semiring (⊕, ⊗): the MAC is the ⊗-then-⊕-accumulate, and the three-state
comparator (smaller / equal / larger) is realized by comparing the new
⊕-reduced value against the node's current value, producing both the update
decision and the "changed" bit that feeds the asynchronous frontier.

Semirings built in (all the paper's six algorithms reduce to these):

  plus_times : (+, ×)  — PageRank, k-core, general SpMV
  min_plus   : (min,+) — SSSP, BFS-by-level
  max_min    : (max,min) over {0,1} — boolean reachability (alias or_and)
  min_select : (min, select-right) — connected-components label propagation

User-defined semirings register through :func:`register`, update rules
through :func:`register_rule`.  Every engine, the reference and wave
kernels and the Pallas kernels read this module and nothing else for what
⊕, ⊗, the ⊕-identity, "improves" and each rule mean, so a registered ring
or rule runs through all of them without touching dispatch code.

The contract of a ring:

  * the carrier is normal float32 values, zero and ±inf: XLA flushes
    subnormals to zero on CPU and TPU, so ``add(a, zero)`` is not ``a``
    for a subnormal ``a``;
  * ``mul(zero, x) == zero`` for every ``x`` of the carrier: the
    ⊕-identity absorbs, so tiles padded with it are no-ops without masks;
  * ``add`` is associative: reductions fold it in a fixed but unspecified
    order.

How each kernel contracts a tile.  The reference and wave kernels call
:meth:`Semiring.contract`, which is ``reduce(mul(w, x))`` unless the ring
gives a ``contract_fn``; plus_times gives one, a full-f32
(``Precision.HIGHEST``) einsum, because on the TPU its products then run
on the matrix unit, whose default precision is bf16.  The Pallas kernels
reduce ``mul(w, x)`` themselves for every ring, plus_times included: their
tile product is a VPU elementwise sum of products, exact f32 already.

This module imports only jax and numpy, never ``repro.core`` or
``repro.kernels``: both read it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class Semiring:
    """An (⊕, ⊗) pair with identities, driving both engines and kernels.

    Attributes:
      name:        stable identifier used for kernel dispatch (static arg).
      add:         ⊕, the reduction (MAC accumulate / comparator side).
      mul:         ⊗, the edge combine (MAC multiply side). mul(edge_w, x_src).
      zero:        ⊕-identity; also the padding value for absent edges,
                   chosen so that padded lanes are no-ops without masks.
      one:         ⊗-identity.
      improves:    strict order test improves(new, old) -> bool array; the
                   "three-state comparator" output used for frontier bits.
      reduce_fn:   the axis-reduction realizing ⊕ over an array (e.g.
                   ``jnp.sum`` for plus_times).  None falls back to a
                   generic ⊕-fold of ``add`` — correct for any registered
                   custom semiring, at the cost of XLA seeing a chain of
                   binary ops instead of one fused reduction.
      contract_fn: ``contract_fn(w, x)`` in place of :meth:`contract`'s
                   ``reduce(mul(w, x))`` (see the module docstring).
    """

    name: str
    add: Callable[[Array, Array], Array]
    mul: Callable[[Array, Array], Array]
    zero: float
    one: float
    improves: Callable[[Array, Array], Array]
    reduce_fn: Optional[Callable[..., Array]] = None
    contract_fn: Optional[Callable[[Array, Array], Array]] = None

    def reduce(self, x: Array, axis=None) -> Array:
        if self.reduce_fn is not None:
            return self.reduce_fn(x, axis=axis)
        # generic ⊕-fold: move the reduced axes to one leading axis, then
        # fold ``add`` over its (static) extent.  Works for any custom
        # ring whose ``add`` is associative — no name-switch involved.
        if axis is None:
            axes = tuple(range(x.ndim))
        elif isinstance(axis, int):
            axes = (axis % x.ndim,)
        else:
            axes = tuple(a % x.ndim for a in axis)
        rest = tuple(a for a in range(x.ndim) if a not in axes)
        t = jnp.transpose(x, axes + rest)
        t = t.reshape((-1,) + tuple(x.shape[a] for a in rest))
        out = t[0]
        for i in range(1, t.shape[0]):
            out = self.add(out, t[i])
        return out

    def contract(self, w: Array, x: Array) -> Array:
        """Tile MACs: ``y[r, ..., i] = ⊕_j w[r, i, j] ⊗ x[r, ..., 0, j]``.

        ``w`` (R, B, J) holds the tiles' values destination-major; ``x``
        (R, ..., 1, J) the gathered sources, with any query axes between
        R and the unit axis.  Returns (R, ..., B)."""
        if self.contract_fn is not None:
            return self.contract_fn(w, x)
        if x.ndim > w.ndim:   # a wave: the tiles broadcast over its queries
            w = jnp.expand_dims(w, tuple(range(1, 1 + x.ndim - w.ndim)))
        return self.reduce(self.mul(w, x), axis=-1)


def _ne(a, b):
    return a != b


def _dot(w, x):
    # full f32 products: the TPU's default matmul precision is bf16
    return jnp.einsum("rij,r...j->r...i", w, x[..., 0, :],
                      precision=jax.lax.Precision.HIGHEST)


PLUS_TIMES = Semiring(
    name="plus_times",
    add=lambda a, b: a + b,
    mul=lambda w, x: w * x,
    zero=0.0,
    one=1.0,
    improves=_ne,
    reduce_fn=lambda x, axis=None: jnp.sum(x, axis=axis),
    contract_fn=_dot,
)

MIN_PLUS = Semiring(
    name="min_plus",
    add=jnp.minimum,
    mul=lambda w, x: w + x,
    zero=np.inf,
    one=0.0,
    improves=lambda new, old: new < old,
    reduce_fn=lambda x, axis=None: jnp.min(x, axis=axis),
)

MAX_MIN = Semiring(
    name="max_min",
    add=jnp.maximum,
    mul=jnp.minimum,
    zero=0.0,  # valid ⊕-identity for the {0,1} boolean carrier
    one=1.0,
    improves=lambda new, old: new > old,
    reduce_fn=lambda x, axis=None: jnp.max(x, axis=axis),
)

# CC label propagation: an edge selects its source's label, which is
# min-reduced; an absent edge (weight +inf, the ⊕-identity) gives +inf.
MIN_SELECT = Semiring(
    name="min_select",
    add=jnp.minimum,
    mul=lambda w, x: jnp.where(jnp.isfinite(w), x, jnp.inf),
    zero=np.inf,
    one=0.0,
    improves=lambda new, old: new < old,
    reduce_fn=lambda x, axis=None: jnp.min(x, axis=axis),
)

SEMIRINGS = {s.name: s for s in (PLUS_TIMES, MIN_PLUS, MAX_MIN, MIN_SELECT)}
# alias: boolean or_and is max_min on the {0,1} carrier
SEMIRINGS["or_and"] = MAX_MIN


def register(ring: Semiring, overwrite: bool = False) -> Semiring:
    """Register a user-defined semiring for engine/kernel dispatch.  It
    must meet the contract in the module docstring."""
    if ring.name in SEMIRINGS and not overwrite:
        raise ValueError(
            f"semiring {ring.name!r} is already registered; pass "
            "overwrite=True to replace it")
    SEMIRINGS[ring.name] = ring
    return ring


def get(name: str) -> Semiring:
    try:
        return SEMIRINGS[name]
    except KeyError:
        raise ValueError(f"unknown semiring {name!r}; have {sorted(SEMIRINGS)}")


# ---------------------------------------------------------------------------
# update rules — the engine-facing half of an algorithm's identity
# ---------------------------------------------------------------------------
#
# A rule's ``apply(ring, y, x, valid, damping, inv_n, tol)`` returns
# ``(x_new, improved)`` for a block of rows: ``y`` is the ⊕-reduced
# neighbourhood, ``x`` the rows' current values, ``valid`` the real (non-
# padding) vertices; ``damping`` also carries k-core's threshold.
# :func:`apply_rule` keeps invalid rows as they are and never reports
# them improved.
#
# PageRank uses dangling-drop semantics (no global dangling-mass
# redistribution; the caller L1-renormalizes the result).  This keeps
# the update edge-local, which the asynchronous model requires: a global
# scalar input would invalidate cluster-level data-readiness tracking.


def _relax(ring, y, x, valid, damping, inv_n, tol):
    # x' = y ⊕ x: the semiring relaxation (SSSP/BFS/CC/reachability)
    x_new = ring.add(y, x)
    return x_new, ring.improves(x_new, x)


def _pagerank(ring, y, x, valid, damping, inv_n, tol):
    # x' = (1−d)/n + d·y, unconditional: the classic damped sweep
    x_new = (1.0 - damping) * inv_n + damping * y
    x_new = jnp.where(valid, x_new, 0.0)
    return x_new, jnp.abs(x_new - x) > tol


def _pagerank_delta(ring, y, x, valid, damping, inv_n, tol):
    # x' = max(x, (1−d)/n + d·y) (GraphScale's delta form): ranks only
    # RISE (by > tol) from the (1−d)/n floor toward the fixpoint, so a
    # stale y under-estimates the rank
    cand = (1.0 - damping) * inv_n + damping * y
    imp = (cand - x) > tol
    return jnp.where(imp, cand, x), imp


def _kcore(ring, y, x, valid, damping, inv_n, tol):
    # membership peeling: y counts live neighbours (plus_times over unit
    # weights), k rides ``damping``; a vertex dies when its live degree
    # drops below k and never revives
    alive = (x > 0.0) & (y >= damping)
    x_new = jnp.where(alive, x, 0.0)
    return x_new, x_new < x


def _identity(ring, y, x, valid, damping, inv_n, tol):
    # x' = y: plain SpMV assignment (debug/diagnostic)
    x_new = jnp.where(valid, y, x)
    return x_new, ring.improves(x_new, x)


@dataclasses.dataclass(frozen=True)
class UpdateRule:
    """One apply rule (``apply_kind``): its arithmetic and the scheduling
    properties every engine flavor keys on.

    Attributes:
      name:     the apply_kind identifier.
      apply:    ``(ring, y, x, valid, damping, inv_n, tol) -> (x_new,
                improved)`` for a block of rows (see :func:`apply_rule`).
      bias:     has a constant term — every valid row must be applied at
                least once even if none of its inputs ever change (the
                fused sync loop's sweep-0 all-rows touch, the async
                engine's first-touch activation).
      monotone: idempotent + monotone — stale inputs are conservative
                bounds, so the rule is eligible for self-timed schedules
                (async cluster skipping, ``dist_flavor="async"``).
      exact:    schedule-independent at convergence — converged states
                are bit-identical across engine flavors (vs. tolerance-
                bounded for accumulation rules, where grouping of float
                adds differs between schedules).
    """

    name: str
    apply: Callable
    bias: bool
    monotone: bool
    exact: bool


# Registration order is the rule's id in the ISA's GCFG operand
# (``core/compile.py``), so the built-ins keep the ids they had.
UPDATE_RULES = {r.name: r for r in (
    UpdateRule("relax", _relax, bias=False, monotone=True, exact=True),
    # order-sensitive (a stale y is not a bound): sync schedules only
    UpdateRule("pagerank", _pagerank, bias=True, monotone=False,
               exact=False),
    UpdateRule("identity", _identity, bias=True, monotone=False,
               exact=False),
    # starting from x0 = (1−d)/n the iterates rise monotonically to the
    # same unique fixpoint, and the conditional assignment makes the rule
    # idempotent.  bias=False: a row with no in-edges is *born* converged.
    UpdateRule("pagerank_delta", _pagerank_delta, bias=False, monotone=True,
               exact=False),
    # monotone-decreasing on {0,1} — stale reads over-estimate liveness,
    # conservatively — and bit-exact everywhere.  bias=True: a vertex
    # with no in-edges must be touched once to die.
    UpdateRule("kcore", _kcore, bias=True, monotone=True, exact=True),
)}


def register_rule(r: UpdateRule, overwrite: bool = False) -> UpdateRule:
    if r.name in UPDATE_RULES and not overwrite:
        raise ValueError(
            f"update rule {r.name!r} is already registered; pass "
            "overwrite=True to replace it")
    UPDATE_RULES[r.name] = r
    return r


def rule(name: str) -> UpdateRule:
    try:
        return UPDATE_RULES[name]
    except KeyError:
        raise ValueError(
            f"unknown update rule {name!r}; have {sorted(UPDATE_RULES)}")


def apply_rule(name: str, ring: Semiring, y, x, valid, damping, inv_n,
               tol):
    """``(x_new, improved)`` of rule ``name`` for a block of rows; rows
    where ``valid`` is False keep ``x`` and are never improved."""
    x_new, imp = rule(name).apply(ring, y, x, valid, damping, inv_n, tol)
    return jnp.where(valid, x_new, x), imp & valid
