import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices stand in for 2 pods × 256 chips.
For every cell we:

  1. build abstract inputs (ShapeDtypeStruct + NamedSharding; nothing is
     allocated),
  2. jit-lower and compile the real entry point (train_step / prefill /
     decode_step),
  3. record memory_analysis (does it fit 16 GB/chip?), cost_analysis, and
     the collective schedule parsed from the post-SPMD HLO.

Cost composition: XLA's cost_analysis counts while-loop bodies ONCE
(verified empirically), so scanned-layer models would be undercounted by
~L×.  We therefore also compile the superblock *piece* (fwd and fwd+bwd)
separately and compose:   total = full + (reps−1)·piece (+ accum scaling
for the microbatch loop).  Residual error: collectives/flops inside the
recurrent time-chunk scans are still counted once per chunk-loop (≤ ~5%
of block flops for rwkv/griffin; noted in EXPERIMENTS.md).

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun
  python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ARCH_IDS, get_config, ModelConfig
from ..models import lm
from ..sharding.rules import parse_axes, spec_for, tree_spec
from ..train.optimizer import make_optimizer, warmup_cosine
from ..train.step import make_train_step
from . import specs as S
from .mesh import make_production_mesh

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(result_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(result_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Per-device collective payload bytes by type (result shapes of every
    collective op in the post-SPMD module; loop bodies appear once)."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        res, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0.0) + _shape_bytes(res)
        out["count"] = out.get("count", 0.0) + 1
    out["total_bytes"] = sum(v for k, v in out.items()
                             if k not in ("count", "total_bytes"))
    return out


def _cost(compiled) -> Dict[str, float]:
    c = compiled.cost_analysis() or {}
    return {"flops": float(c.get("flops", 0.0)),
            "bytes": float(c.get("bytes accessed", 0.0))}


def _mem(compiled) -> Dict[str, float]:
    m = compiled.memory_analysis()
    return {"argument_bytes": float(m.argument_size_in_bytes),
            "output_bytes": float(m.output_size_in_bytes),
            "temp_bytes": float(m.temp_size_in_bytes),
            "alias_bytes": float(m.alias_size_in_bytes),
            "peak_est_bytes": float(m.argument_size_in_bytes
                                    + m.output_size_in_bytes
                                    + m.temp_size_in_bytes
                                    - m.alias_size_in_bytes)}


def _compile(fn, args, donate=None, out_shardings=None):
    t0 = time.time()
    kw = {}
    if donate is not None:
        kw["donate_argnums"] = donate
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    lowered = jax.jit(fn, **kw).lower(*args)
    compiled = lowered.compile()
    dt = time.time() - t0
    txt = compiled.as_text()
    return {"cost": _cost(compiled), "mem": _mem(compiled),
            "collectives": parse_collectives(txt), "compile_s": dt}


def _scale(d: Dict[str, float], k: float) -> Dict[str, float]:
    return {key: v * k for key, v in d.items()}


def _add(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: a.get(k, 0.0) + b.get(k, 0.0)
            for k in set(a) | set(b)}


def _strip_stack(axes_tree):
    return jax.tree.map(
        lambda s: " ".join(t for t in s.split() if t != "stack"), axes_tree)


def _sb_param_sds(cfg: ModelConfig, mesh, params_sds, axes):
    """Abstract ONE slice of the stacked superblock params."""
    blocks = params_sds["blocks"]
    baxes = _strip_stack(axes["blocks"])
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), blocks)
    sp = tree_spec(shapes, baxes, mesh)
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shapes, sp)


def _sb_cache_sds(cfg: ModelConfig, mesh, cache_sds):
    blocks = cache_sds["blocks"]
    baxes = _strip_stack({"blocks": lm.cache_axes(cfg)["blocks"]})["blocks"]
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), blocks)
    sp = tree_spec(shapes, baxes, mesh)
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shapes, sp)


def _x_sds(cfg, mesh, batch, seq):
    return jax.ShapeDtypeStruct(
        (batch, seq, cfg.d_model), jnp.bfloat16,
        sharding=NamedSharding(mesh, spec_for((batch, seq, cfg.d_model),
                                              "batch seq .", mesh)))


def _enc_sds(cfg, mesh, batch):
    if cfg.img_seq:
        n = cfg.img_seq
    elif cfg.encdec:
        n = cfg.encoder_seq
    else:
        return None
    return jax.ShapeDtypeStruct(
        (batch, n, cfg.d_model), jnp.bfloat16,
        sharding=NamedSharding(mesh, spec_for((batch, n, cfg.d_model),
                                              "batch . .", mesh)))


def _sb_fwd_fn(cfg: ModelConfig, with_enc: bool):
    pat = cfg.block_pattern

    def f(ps, x, enc=None):
        b, s, _ = x.shape
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        aux = jnp.float32(0.0)
        for j, kind in enumerate(pat):
            x, a_ = lm.block_apply_train(cfg, kind, ps[f"b{j}"], x,
                                         positions=positions, enc=enc)
            aux = aux + a_
        return x, aux

    if with_enc:
        return f
    return lambda ps, x: f(ps, x, None)


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------


def run_train_cell(cfg: ModelConfig, mesh, pieces: bool = True,
                   shard_grads: bool = True) -> Dict[str, Any]:
    accum = S.accum_for(cfg.name, mesh)
    sh = S.SHAPES["train_4k"]
    opt = make_optimizer(cfg.optimizer, warmup_cosine(3e-4, 100, 10000))
    params_sds, axes = S.abstract_params(cfg, mesh)
    opt_sds = S.abstract_opt_state(opt, params_sds, axes, mesh)
    batch_sds = S.batch_specs(cfg, mesh, sh["batch"], sh["seq"], train=True)

    p_sh = jax.tree.map(lambda s: s.sharding, params_sds)
    # NOTE: also tried pinning per-layer grad shardings via in-scan-body
    # param constraints (with_sharding_constraint is its own transpose) —
    # no measurable change; the per-layer reduce is placed by GSPMD inside
    # the backward layer scan either way (EXPERIMENTS.md §Perf, dbrx it.2)
    sb_sh = None
    ts = make_train_step(cfg, opt, accum_steps=accum,
                         grad_shardings=p_sh if shard_grads else None,
                         sb_param_shardings=sb_sh)
    o_sh = jax.tree.map(lambda s: s.sharding, opt_sds)
    metrics_shape = jax.eval_shape(ts, params_sds, opt_sds, batch_sds)[2]
    m_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), metrics_shape)

    out: Dict[str, Any] = {"accum_steps": accum}
    with mesh:
        full = _compile(ts, (params_sds, opt_sds, batch_sds),
                        donate=(0, 1), out_shardings=(p_sh, o_sh, m_sh))
    out["full"] = full

    if not pieces:
        return out

    # --- composition pieces (single-pod roofline only) ---
    mb = sh["batch"] // accum
    with mesh:
        # (1) microbatch grad, accum=1 (tail + one superblock in cost)
        ts1 = make_train_step(cfg, opt, accum_steps=1,
                              grad_shardings=p_sh if shard_grads else None,
                              sb_param_shardings=sb_sh)
        mb_batch = S.batch_specs(cfg, mesh, mb, sh["seq"], train=True)
        mb_grad = _compile(ts1, (params_sds, opt_sds, mb_batch),
                           donate=(0, 1), out_shardings=(p_sh, o_sh, m_sh))
        out["mb_step"] = mb_grad

        # (2) superblock fwd and fwd+bwd pieces
        sb_sds = _sb_param_sds(cfg, mesh, params_sds, axes)
        x_sds = _x_sds(cfg, mesh, mb, sh["seq"])
        enc_sds = _enc_sds(cfg, mesh, mb)
        fwd = _sb_fwd_fn(cfg, enc_sds is not None)
        args = (sb_sds, x_sds) + ((enc_sds,) if enc_sds is not None else ())
        out["sb_fwd"] = _compile(fwd, args)

        def vjp_fn(*a):
            ct_x = a[-1]
            ins = a[:-1]
            y, pull = jax.vjp(fwd, *ins)
            return pull((ct_x, jnp.float32(1.0)))
        out["sb_vjp"] = _compile(vjp_fn, args + (x_sds,))

    reps = cfg.pattern_repeats
    rg = cfg.remat_group if (cfg.remat_group > 1
                             and reps % cfg.remat_group == 0) else 1
    # composed per-step cost: accum×(mb_step + (reps−rg)×(sb_fwd+sb_vjp))
    # — the full lowering's scan body already contains rg superblocks.
    sbc = _add(out["sb_fwd"]["cost"], out["sb_vjp"]["cost"])
    sbcoll = _add(out["sb_fwd"]["collectives"],
                  out["sb_vjp"]["collectives"])
    comp_cost = _scale(_add(out["mb_step"]["cost"],
                            _scale(sbc, reps - rg)), accum)
    comp_coll = _scale(_add(out["mb_step"]["collectives"],
                            _scale(sbcoll, reps - rg)), accum)
    out["composed"] = {"cost": comp_cost, "collectives": comp_coll,
                       "note": "optimizer counted accum× (≤ few % over)"}
    return out


def run_prefill_cell(cfg: ModelConfig, mesh, pieces: bool = True
                     ) -> Dict[str, Any]:
    sh = S.SHAPES["prefill_32k"]
    params_sds, axes = S.abstract_params(cfg, mesh)
    batch_sds = S.batch_specs(cfg, mesh, sh["batch"], sh["seq"],
                              train=False)

    def pf(p, batch):
        return lm.prefill(cfg, p, batch, cache_len=sh["seq"])

    out: Dict[str, Any] = {}
    with mesh:
        out["full"] = _compile(pf, (params_sds, batch_sds))
    if not pieces:
        return out

    with mesh:
        sb_sds = _sb_param_sds(cfg, mesh, params_sds, axes)
        x_sds = _x_sds(cfg, mesh, sh["batch"], sh["seq"])
        enc_sds = _enc_sds(cfg, mesh, sh["batch"])
        pat = cfg.block_pattern

        def sb_pf(ps, x, enc=None):
            b, s_ = x.shape[:2]
            positions = jnp.broadcast_to(
                jnp.arange(s_, dtype=jnp.int32)[None], (b, s_))
            caches = []
            for j, kind in enumerate(pat):
                x, c = lm.block_prefill(cfg, kind, ps[f"b{j}"], x,
                                        positions=positions,
                                        cache_len=sh["seq"], enc=enc)
                caches.append(c)
            return x, caches

        f = sb_pf if enc_sds is not None else (
            lambda ps, x: sb_pf(ps, x, None))
        args = (sb_sds, x_sds) + ((enc_sds,) if enc_sds is not None else ())
        out["sb"] = _compile(f, args)

    reps = cfg.pattern_repeats
    out["composed"] = {
        "cost": _add(out["full"]["cost"],
                     _scale(out["sb"]["cost"], reps - 1)),
        "collectives": _add(out["full"]["collectives"],
                            _scale(out["sb"]["collectives"], reps - 1))}
    return out


def run_decode_cell(cfg: ModelConfig, mesh, shape_name: str,
                    pieces: bool = True) -> Dict[str, Any]:
    sh = S.SHAPES[shape_name]
    params_sds, axes = S.abstract_params(cfg, mesh)
    cache_sds = S.cache_specs(cfg, mesh, sh["batch"], sh["seq"])
    tok_sds, pos_sds = S.decode_input_specs(cfg, mesh, sh["batch"])
    c_sh = jax.tree.map(lambda s: s.sharding, cache_sds)

    def step(p, c, t, pos):
        return lm.decode_step(cfg, p, c, t, pos)

    out: Dict[str, Any] = {}
    with mesh:
        out["full"] = _compile(step,
                               (params_sds, cache_sds, tok_sds, pos_sds),
                               donate=(1,),
                               out_shardings=(None, c_sh))
    if not pieces:
        return out

    with mesh:
        sb_sds = _sb_param_sds(cfg, mesh, params_sds, axes)
        sbc_sds = _sb_cache_sds(cfg, mesh, cache_sds)
        x_sds = _x_sds(cfg, mesh, sh["batch"], 1)
        pat = cfg.block_pattern

        def sb_dec(ps, cs, x, pos):
            new = []
            for j, kind in enumerate(pat):
                x, c = lm.block_decode(cfg, kind, ps[f"b{j}"], x,
                                       cs[f"b{j}"], pos=pos)
                new.append(c)
            return x, new

        out["sb"] = _compile(sb_dec, (sb_sds, sbc_sds, x_sds, pos_sds))

    reps = cfg.pattern_repeats
    out["composed"] = {
        "cost": _add(out["full"]["cost"],
                     _scale(out["sb"]["cost"], reps - 1)),
        "collectives": _add(out["full"]["collectives"],
                            _scale(out["sb"]["collectives"], reps - 1))}
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             pieces: bool = True, factored: bool = False,
             shard_grads: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    ok, why = S.cell_applicable(cfg, shape_name)
    base = {"arch": arch, "shape": shape_name,
            "mesh": ("2x16x16" if multi_pod else "16x16")
            + ("f" if factored else "")}
    if not ok:
        return dict(base, status="skipped", reason=why)
    if factored:
        from .mesh import make_factored_mesh
        mesh = make_factored_mesh(multi_pod=multi_pod)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    pieces = pieces and not multi_pod  # roofline is single-pod only
    t0 = time.time()
    try:
        if shape_name == "train_4k":
            r = run_train_cell(cfg, mesh, pieces, shard_grads=shard_grads)
        elif shape_name == "prefill_32k":
            r = run_prefill_cell(cfg, mesh, pieces)
        else:
            r = run_decode_cell(cfg, mesh, shape_name, pieces)
        return dict(base, status="ok", wall_s=time.time() - t0, **r)
    except Exception as e:  # a failure here is a bug in our sharding
        return dict(base, status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-2000:],
                    wall_s=time.time() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(S.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-pieces", action="store_true")
    ap.add_argument("--factored", action="store_true",
                    help="factored model axis (16,8,2) — §Perf variant")
    ap.add_argument("--no-shard-grads", action="store_true",
                    help="disable grad reduce-scatter pinning (baseline)")
    ap.add_argument("--out", default=None, help="directory for JSON dumps")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s_ in S.SHAPES:
                cells.append((a, s_))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    results = []
    for a, s_ in cells:
        r = run_cell(a, s_, multi_pod=args.multi_pod,
                     pieces=not args.no_pieces, factored=args.factored,
                     shard_grads=not args.no_shard_grads)
        results.append(r)
        status = r["status"]
        extra = ""
        if status == "ok":
            peak = r["full"]["mem"]["peak_est_bytes"] / 2**30
            extra = f"peak={peak:.2f}GiB compile={r['full']['compile_s']:.1f}s"
        elif status == "error":
            extra = r["error"][:160]
        else:
            extra = r["reason"][:80]
        print(f"[{r['mesh']}] {a:28s} {s_:12s} {status:8s} {extra}",
              flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            tag = f"{a}__{s_}__{r['mesh'].replace('x','_')}.json"
            with open(os.path.join(args.out, tag), "w") as f:
                json.dump(r, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n== dry-run summary: {n_ok} ok, {n_skip} skipped "
          f"(documented), {n_err} errors ==")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())


_ = (dataclasses, np, parse_axes, Optional)
