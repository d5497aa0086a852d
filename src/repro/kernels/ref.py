"""Pure-jnp oracles for every Pallas kernel (the ground truth for tests).

These are also the production fallback path on backends without Mosaic
(this CPU container, GPU): ``ops.py`` dispatches kernel vs. reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import algebra
from .bsr_spmv import LANES


# ---------------------------------------------------------------------------
# bsr_spmv — block-sparse semiring SpMV
# ---------------------------------------------------------------------------


def bsr_spmv_ref(block_vals: jnp.ndarray, block_cols: jnp.ndarray,
                 x: jnp.ndarray, semiring: str = "plus_times") -> jnp.ndarray:
    """y[r*b+i] = ⊕_{k,j} vals[r,i,k*b+j] ⊗ x[cols[r,k]*b+j].

    Args:
      block_vals: (R, B, K*B) destination-major tile values (padded with
        the ⊕-identity; see ``core.graph.BsrGraph``).
      block_cols: (R, K) int32 col-block ids (padding points anywhere; the
        padded tile's values are ⊕-identities so the result is unaffected).
      x: (C, B) input vector in block layout.
      semiring: any registered semiring name (``algebra.get``); each
        tile is contracted by ``Semiring.contract``.
    Returns:
      y: (R, B).
    """
    r = block_vals.shape[0]
    xs = x[block_cols].reshape(r, 1, -1)  # (R, 1, K*B), sources on lanes
    return algebra.get(semiring).contract(block_vals, xs)


def bsr_spmv_wave_ref(block_vals: jnp.ndarray, block_cols: jnp.ndarray,
                      x: jnp.ndarray,
                      semiring: str = "plus_times") -> jnp.ndarray:
    """``bsr_spmv_ref`` for a wave of Q vectors carried row-major.

    Args:
      block_vals, block_cols: as for ``bsr_spmv_ref``.
      x: (C, Q, B) — the wave's Q vectors, queries contiguous inside each
        row-block.
      semiring: as for ``bsr_spmv_ref``.
    Returns:
      y: (R, Q, B); ``y[:, q]`` is ``bsr_spmv_ref(..., x[:, q], ...)``,
      with the same ⊗ and ⊕ per element.

    The source gather fetches each tile's block once for the whole wave,
    one contiguous row of Q*B values; Q vectors carried apart would be Q
    strided rows of B values per tile.

    The relayout after the gather puts K on the lanes.  For a K that is
    no multiple of 128 the TPU compiler does it through a reshape that is
    no bitcast, at several times the cost per tile slot; so the gather
    takes whole lane columns of tiles (padding slots read block 0) where
    that adds at most an eighth to K, and the padding is cut before the
    ⊕-reduce.
    """
    r = block_vals.shape[0]
    c, q, b = x.shape
    k = block_cols.shape[1]
    pad = -k % LANES
    if pad > k // 8:
        pad = 0
    cols = jnp.pad(block_cols, ((0, 0), (0, pad))) if pad else block_cols
    rows = x.reshape(c, q * b)[cols]                  # (R, K+pad, Q*B)
    xs = rows.reshape(r, k + pad, q, b).transpose(0, 2, 1, 3)
    if pad:
        xs = xs[:, :, :k]
    xs = xs.reshape(r, q, 1, k * b)                   # (R, Q, 1, K*B)
    return algebra.get(semiring).contract(block_vals, xs)


# ---------------------------------------------------------------------------
# flash_attention — exact softmax attention oracle
# ---------------------------------------------------------------------------


def mha_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            causal: bool = True, window: int | None = None,
            scale: float | None = None) -> jnp.ndarray:
    """Exact attention.  q: (B, H, S, D); k,v: (B, H, Skv, D) (kv already
    repeated to H heads).  window = local attention span (None = global)."""
    b, h, s, d = q.shape
    skv = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(s)[:, None] + (skv - s)   # align last q with last k
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((s, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    # probs stored/saved in the value dtype (bf16): halves the dominant
    # backward residual; matches the fused-kernel numerics on real TPUs
    p = p.astype(v.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", p, v).astype(q.dtype)


def mha_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                causal: bool = True, window: int | None = None,
                scale: float | None = None,
                q_chunk: int = 1024) -> jnp.ndarray:
    """Memory-safe exact attention for long sequences: lax.scan over query
    chunks so the live score tensor is (B, H, q_chunk, Skv) instead of
    (B, H, S, Skv).  XLA path used by 32k prefill (and anything ≥ 16k)."""
    b, h, s, d = q.shape
    dv = v.shape[-1]            # MLA: v_head_dim may differ from qk dim
    skv = k.shape[2]
    scale_ = scale if scale is not None else 1.0 / (d ** 0.5)
    if s % q_chunk or s <= q_chunk:
        return mha_ref(q, k, v, causal, window, scale)
    nq = s // q_chunk
    qs = q.reshape(b, h, nq, q_chunk, d).transpose(2, 0, 1, 3, 4)
    kpos = jnp.arange(skv)[None, :]

    def one(carry, args):
        qi, qc = args
        logits = jnp.einsum("bhsd,bhtd->bhst", qc, k).astype(jnp.float32) \
            * scale_
        qpos = (qi * q_chunk + jnp.arange(q_chunk))[:, None] + (skv - s)
        mask = jnp.ones((q_chunk, skv), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p).astype(v.dtype)
        o = jnp.einsum("bhst,bhtd->bhsd", p, v)
        return carry, o.astype(q.dtype)

    # scanned (not unrolled): the live score tensor stays one chunk.
    # cost_analysis counts the body once — the roofline adds the known
    # (nq−1)× analytic correction for prefill cells (launch/roofline.py).
    _, outs = jax.lax.scan(one, (),
                           (jnp.arange(nq, dtype=jnp.int32), qs))
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, s, dv)
