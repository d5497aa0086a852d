"""Transformer building blocks (functional: init returns (params, axes)).

Params are plain pytrees; the parallel ``axes`` pytree holds logical-axis
strings (see sharding/rules.py) consumed by the launcher to build
NamedShardings.  Compute runs in cfg.compute_dtype (bf16 by default),
params are kept in cfg.param_dtype (f32 master).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding.rules import constrain


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def _init(key, shape, scale_dim, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            * (scale_dim ** -0.5)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_init(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return ({"scale": jnp.ones((d,), jnp.float32),
                 "bias": jnp.zeros((d,), jnp.float32)},
                {"scale": "norm", "bias": "norm"})
    return ({"scale": jnp.ones((d,), jnp.float32)}, {"scale": "norm"})


def norm_apply(cfg: ModelConfig, p, x):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (full / partial-fraction "2d")
# ---------------------------------------------------------------------------


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               fraction: float = 1.0) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S).  Rotates the first
    ``fraction`` of D (chatglm-style 2d/partial rotary when < 1)."""
    d = x.shape[-1]
    rot = int(d * fraction) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = xr[..., :half], xr[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return jnp.concatenate([out, xp], axis=-1) if rot < d else out


# ---------------------------------------------------------------------------
# GQA attention (self / cross / local)
# ---------------------------------------------------------------------------


def attn_init(cfg: ModelConfig, key, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg.param_dtype)
    p = {
        "wq": _init(ks[0], (d, h, hd), d, dt),
        "wk": _init(ks[1], (d, kv, hd), d, dt),
        "wv": _init(ks[2], (d, kv, hd), d, dt),
        "wo": _init(ks[3], (h, hd, d), h * hd, dt),
    }
    a = {"wq": "embed heads head_dim", "wk": "embed_kv kv_heads head_dim",
         "wv": "embed_kv kv_heads head_dim", "wo": "heads head_dim embed"}
    return p, a


def _qkv(cfg, p, x, kv_src, positions, rope: bool):
    cd = dtype_of(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(cd))
    if rope and cfg.pos_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        kpos = jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=jnp.int32)[None], k.shape[:2])
        k = apply_rope(k, kpos, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def attn_apply(cfg: ModelConfig, p, x, *, positions, window=None,
               causal=True, kv_src=None, attn_impl="ref"):
    """Full-sequence attention (train / prefill).  kv_src ≠ None → cross."""
    cross = kv_src is not None
    kv_in = kv_src if cross else x
    q, k, v = _qkv(cfg, p, x, kv_in, positions, rope=not cross)
    o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3),
                      causal=causal and not cross,
                      window=window, impl=attn_impl)
    o = o.transpose(0, 2, 1, 3)  # (B, S, H, hd)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


def attn_prefill(cfg: ModelConfig, p, x, *, positions, window=None,
                 cache_len: int, attn_impl="ref"):
    """Prefill: returns (out, cache{k,v}) with cache padded to cache_len."""
    q, k, v = _qkv(cfg, p, x, x, positions, rope=True)
    o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=True, window=window,
                      impl=attn_impl)
    o = o.transpose(0, 2, 1, 3)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    s = x.shape[1]
    pad = [(0, 0), (0, cache_len - s), (0, 0), (0, 0)]
    cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    return out, cache


def attn_decode(cfg: ModelConfig, p, x, cache, *, pos, window=None):
    """One-token decode against a (B, S_max, KV, hd) cache.  ``pos`` is the
    index of the new token (B,) or scalar."""
    cd = dtype_of(cfg.compute_dtype)
    b = x.shape[0]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cd))
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cd))
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, pos_arr[:, None], cfg.rope_theta,
                       cfg.rope_fraction)
        k_new = apply_rope(k_new, pos_arr[:, None], cfg.rope_theta,
                           cfg.rope_fraction)
    k = _scatter_time(cache["k"], k_new, pos_arr)
    v = _scatter_time(cache["v"], v_new, pos_arr)
    o = _decode_attend(cfg, q, k, v, pos_arr, window)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return out, {"k": k, "v": v}


def _scatter_time(cache, new, pos):
    """cache (B, S, KV, hd) ← new (B, 1, KV, hd) at per-batch pos."""
    b, s = cache.shape[:2]
    onehot = (jnp.arange(s, dtype=jnp.int32)[None] == pos[:, None])
    onehot = onehot[:, :, None, None].astype(cache.dtype)
    return cache * (1 - onehot) + onehot * new.astype(cache.dtype)


def _decode_attend(cfg, q, k, v, pos, window=None):
    """q (B,1,H,hd); k,v (B,S,KV,hd); masked softmax over cached length.

    When a production mesh is active and the KV cache is long enough to
    be seq-sharded over the model axis, uses the explicit flash-decoding
    path — otherwise GSPMD all-gathers the ENTIRE cache every step
    (measured: 43.9 GB/step for granite decode_32k; EXPERIMENTS.md §Perf).
    """
    from ..sharding.rules import _current_mesh
    mesh = _current_mesh()
    s_len = k.shape[1]
    if (mesh is not None and "model" in mesh.shape
            and s_len % mesh.shape["model"] == 0 and s_len >= 4096):
        return _decode_attend_flash(cfg, q, k, v, pos, window, mesh)
    return _decode_attend_local(q, k, v, pos, window, base=None)


def _decode_attend_local(q, k, v, pos, window, base):
    """Single-shard masked attend.  ``base``: global position of this
    shard's first cache slot (None → 0, full cache)."""
    h, kvh = q.shape[2], k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bthk,bshk->bhts", q, k).astype(jnp.float32) * scale
    kpos = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, None, :]
    if base is not None:
        kpos = kpos + base
    mask = kpos <= pos[:, None, None, None]
    if window is not None:
        mask &= kpos > pos[:, None, None, None] - window
    s = jnp.where(mask, s, -jnp.inf)
    if base is None:
        pda = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bshk->bthk", pda.astype(v.dtype), v)
    # flash-decoding partial: return (o_unnormalized, m, l)
    m = jnp.max(s, axis=-1)                               # (B,H,1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l_ = jnp.sum(p, axis=-1)                              # (B,H,1)
    o = jnp.einsum("bhts,bshk->bthk", p.astype(v.dtype), v)
    return o, m, l_


def _decode_attend_flash(cfg, q, k, v, pos, window, mesh):
    """Distributed flash-decoding: each model-shard attends over its LOCAL
    cache chunk, then combines (max, sum, weighted-V) with tiny psums —
    O(B·H·hd) collective instead of O(B·S·KV·hd) cache all-gather."""
    import functools
    from jax.sharding import PartitionSpec as P
    from ..sharding.rules import spec_for
    b, s_len = k.shape[0], k.shape[1]
    q_spec = spec_for(q.shape, "batch . . .", mesh)
    kv_spec = spec_for(k.shape, "batch kv_seq kv_heads head_dim", mesh)
    pos_spec = spec_for(pos.shape, "batch", mesh)
    seq_axes = kv_spec[1]
    if seq_axes is None:  # seq didn't shard after all
        return _decode_attend_local(q, k, v, pos, window, base=None)
    seq_axes = (seq_axes,) if isinstance(seq_axes, str) else tuple(seq_axes)
    n_shards = 1
    for ax in seq_axes:
        n_shards *= mesh.shape[ax]
    chunk = s_len // n_shards

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, pos_spec),
        out_specs=q_spec)
    def attend(ql, kl, vl, posl):
        idx = jnp.int32(0)
        for ax in seq_axes:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        base = idx * chunk
        o, m, l_ = _decode_attend_local(ql, kl, vl, posl, window,
                                        base=base)
        gmax = jax.lax.pmax(m, seq_axes)                 # (B,H,1)
        corr = jnp.exp(m - gmax)
        l_g = jax.lax.psum(l_ * corr, seq_axes)
        o_g = jax.lax.psum(o * corr.transpose(0, 2, 1)[..., None]
                           .astype(o.dtype), seq_axes)
        denom = jnp.maximum(l_g, 1e-30).transpose(0, 2, 1)[..., None]
        return (o_g / denom.astype(o_g.dtype)).astype(ql.dtype)

    return attend(q, k, v, pos)


def cross_attn_kv(cfg: ModelConfig, p, enc: jnp.ndarray):
    """Precompute cross-attention K/V from encoder states (prefill)."""
    cd = dtype_of(cfg.compute_dtype)
    k = jnp.einsum("bsd,dhk->bshk", enc, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", enc, p["wv"].astype(cd))
    return {"k": k, "v": v}


def cross_attn_decode(cfg: ModelConfig, p, x, kv):
    cd = dtype_of(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k, v = kv["k"], kv["v"]
    h, kvh = q.shape[2], k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bthk,bshk->bhts", q, k).astype(jnp.float32) * scale
    o = jnp.einsum("bhts,bshk->bthk",
                   jax.nn.softmax(s, -1).astype(v.dtype), v)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# MLA (MiniCPM3 / DeepSeek-style latent attention)
# ---------------------------------------------------------------------------


def mla_init(cfg: ModelConfig, key):
    d, h = cfg.d_model, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    dt = dtype_of(cfg.param_dtype)
    p = {
        "wq_a": _init(ks[0], (d, qr), d, dt),
        "q_norm": jnp.ones((qr,), jnp.float32),
        "wq_b": _init(ks[1], (qr, h, nope + rope), qr, dt),
        "wkv_a": _init(ks[2], (d, kr + rope), d, dt),
        "kv_norm": jnp.ones((kr,), jnp.float32),
        "wkv_b": _init(ks[3], (kr, h, nope + vd), kr, dt),
        "wo": _init(ks[4], (h, vd, d), h * vd, dt),
    }
    a = {"wq_a": "embed lora", "q_norm": "norm",
         "wq_b": "lora heads qk_dim", "wkv_a": "embed lora",
         "kv_norm": "norm", "wkv_b": "lora heads qk_dim",
         "wo": "heads head_dim embed"}
    return p, a


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                           + 1e-6) * scale
    return y.astype(x.dtype)


def _mla_qkv_latent(cfg, p, x, positions):
    cd = dtype_of(cfg.compute_dtype)
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    cq = _rms(x @ p["wq_a"].astype(cd), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(cd))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv_full = x @ p["wkv_a"].astype(cd)
    c_kv = _rms(ckv_full[..., : cfg.kv_lora_rank], p["kv_norm"])
    k_rope = ckv_full[..., cfg.kv_lora_rank:]  # (B,S,rope) shared heads
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    _ = nope
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(cfg: ModelConfig, p, x, *, positions, attn_impl="ref"):
    """Train/prefill MLA: materialize per-head K/V from latents."""
    cd = dtype_of(cfg.compute_dtype)
    nope, vd = cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(cfg, p, x, positions)
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(cd))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    h = cfg.num_heads
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :],
                                k_rope.shape[:2] + (h, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope_b], -1)
    o = ops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=True, impl=attn_impl)
    o = o.transpose(0, 2, 1, 3)
    _ = vd
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


def mla_prefill(cfg: ModelConfig, p, x, *, positions, cache_len: int,
                attn_impl="ref"):
    out = mla_apply(cfg, p, x, positions=positions, attn_impl=attn_impl)
    _, _, c_kv, k_rope = _mla_qkv_latent(cfg, p, x, positions)
    s = x.shape[1]
    cache = {
        "c_kv": jnp.pad(c_kv, [(0, 0), (0, cache_len - s), (0, 0)]),
        "k_rope": jnp.pad(k_rope, [(0, 0), (0, cache_len - s), (0, 0)]),
    }
    return out, cache


def mla_decode(cfg: ModelConfig, p, x, cache, *, pos):
    """Absorbed-weight MLA decode: attention runs in the latent space —
    the KV cache holds only (kv_lora + rope) per token, the MLA win."""
    cd = dtype_of(cfg.compute_dtype)
    b = x.shape[0]
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    nope, vd = cfg.qk_nope_dim, cfg.v_head_dim
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv_latent(
        cfg, p, x, pos_arr[:, None])
    wkv_b = p["wkv_b"].astype(cd)
    wk, wv = wkv_b[..., :nope], wkv_b[..., nope:]
    # absorb: q in latent space
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk)  # (B,1,H,kv_lora)
    s_max = cache["c_kv"].shape[1]
    onehot = (jnp.arange(s_max, dtype=jnp.int32)[None] == pos_arr[:, None])
    c_kv = cache["c_kv"] * (1 - onehot[..., None].astype(cd)) \
        + onehot[..., None].astype(cd) * c_kv_new.astype(cd)
    k_rope = cache["k_rope"] * (1 - onehot[..., None].astype(cd)) \
        + onehot[..., None].astype(cd) * k_rope_new.astype(cd)
    scale = 1.0 / ((nope + cfg.qk_rope_dim) ** 0.5)
    logits = (jnp.einsum("bthr,bsr->bhts", q_lat, c_kv)
              + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope)
              ).astype(jnp.float32) * scale
    kpos = jnp.arange(s_max, dtype=jnp.int32)[None, None, None, :]
    logits = jnp.where(kpos <= pos_arr[:, None, None, None], logits,
                       -jnp.inf)
    w = jax.nn.softmax(logits, -1).astype(cd)
    ctx_lat = jnp.einsum("bhts,bsr->bthr", w, c_kv)       # latent context
    v_ctx = jnp.einsum("bthr,rhk->bthk", ctx_lat, wv)     # (B,1,H,vd)
    _ = vd
    out = jnp.einsum("bshk,hkd->bsd", v_ctx, p["wo"].astype(cd))
    return out, {"c_kv": c_kv, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg.param_dtype)
    if cfg.mlp_kind == "swiglu":
        ks = jax.random.split(key, 3)
        p = {"wi": _init(ks[0], (d, ff), d, dt),
             "wg": _init(ks[1], (d, ff), d, dt),
             "wo": _init(ks[2], (ff, d), ff, dt)}
        a = {"wi": "embed mlp", "wg": "embed mlp", "wo": "mlp embed"}
    else:
        ks = jax.random.split(key, 2)
        p = {"wi": _init(ks[0], (d, ff), d, dt),
             "wo": _init(ks[1], (ff, d), ff, dt)}
        a = {"wi": "embed mlp", "wo": "mlp embed"}
    return p, a


def mlp_apply(cfg: ModelConfig, p, x):
    cd = dtype_of(cfg.compute_dtype)
    h = x @ p["wi"].astype(cd)
    if cfg.mlp_kind == "swiglu":
        g = x @ p["wg"].astype(cd)
        h = jax.nn.silu(g) * h
    elif cfg.mlp_kind == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:  # gelu
        h = jax.nn.gelu(h)
    return h @ p["wo"].astype(cd)


__all__ = [k for k in dir() if not k.startswith("_")]
_ = (dataclasses, Tuple, constrain)
