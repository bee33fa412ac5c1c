"""Per-layer blocks (pre-norm residual) + segment grouping for scan.

A model is a list of *segments*: consecutive layers of the same kind, with
params stacked on a leading (L_seg,) axis and iterated by lax.scan — this
keeps the HLO size O(#kinds), not O(#layers), which is what makes the 95-layer
deepseek-67b dry-run compile tractable. Hybrid patterns (zamba2's shared
attention block every k mamba layers) interleave non-scanned shared blocks
between segments.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.nn import attention, layers, mla, moe, ssm, xlstm

# When True, layer scans fully unroll (used by launch/roofline.py depth
# variants: XLA's cost analysis counts while-loop bodies once, so roofline
# probes compile shallow unrolled models).
UNROLL = False

# Activation-checkpoint policy for the per-layer remat in training scans:
#   "full" — recompute everything in backward (min live memory, max traffic)
#   "dots" — save matmul outputs (jax dots_with_no_batch_dims_saveable)
#   "none" — no remat (max live memory, min recompute)
# §Perf knob (launch/dryrun.py --remat).
REMAT_POLICY = "full"


def _wrap_remat(fn):
    if REMAT_POLICY == "none":
        return fn
    if REMAT_POLICY == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# single-layer init / apply
# ---------------------------------------------------------------------------
def init_block(key, cfg, kind: str, dtype, index: int = 0):
    """`index`: the block's place in the stack (the published sLSTM's
    forget-gate bias depends on it)."""
    ks = layers.split(key, 4)
    p: Dict[str, Any] = {"norm1": layers.init_norm(cfg.norm_kind, cfg.d_model, dtype)}
    if kind == "attn":
        if cfg.is_mla:
            p["attn"] = mla.init_mla(ks[0], cfg, dtype)
        else:
            p["attn"] = attention.init_gqa(ks[0], cfg.d_model, cfg.num_heads,
                                           cfg.num_kv_heads, cfg.head_dim, dtype)
        p["norm2"] = layers.init_norm(cfg.norm_kind, cfg.d_model, dtype)
        if cfg.num_experts:
            p["moe"] = moe.init_moe(ks[1], cfg.d_model, cfg.num_experts,
                                    cfg.moe_d_ff, cfg.num_shared_experts, dtype)
        else:
            p["mlp"] = layers.init_mlp(cfg.mlp_kind, ks[1], cfg.d_model,
                                       cfg.d_ff, dtype)
    elif kind == "mamba":
        p["mamba"] = ssm.init_mamba2(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["mlstm"] = xlstm.init_mlstm(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["slstm"] = xlstm.init_slstm(ks[0], cfg, dtype, index)
        if cfg.xlstm_form == "published":
            p["norm2"] = layers.init_norm(cfg.norm_kind, cfg.d_model, dtype)
            p["mlp"] = layers.init_mlp(cfg.mlp_kind, ks[1], cfg.d_model,
                                       cfg.d_ff, dtype)
    else:
        raise ValueError(kind)
    return p


def apply_block(p, cfg, kind: str, x, positions, *, window: int = 0,
                mode: str = "train", cache=None, cache_index=None,
                masked: bool = False):
    """mode: train | prefill | decode. Returns (x, aux, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.apply_norm(cfg.norm_kind, p["norm1"], x, cfg.norm_eps)
    new_cache = None
    if kind == "attn":
        if cfg.is_mla:
            if mode == "decode":
                y, new_cache = mla.mla_decode(p["attn"], cfg, h, cache,
                                              positions,
                                              cache_index=cache_index,
                                              masked=masked)
            elif mode == "prefill":
                y, new_cache = mla.mla_block(p["attn"], cfg, h, positions,
                                             window=window, return_cache=True)
            else:
                y = mla.mla_block(p["attn"], cfg, h, positions, window=window)
        else:
            kw = dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                      head_dim=cfg.head_dim, rope_kind=cfg.rope_kind,
                      rope_theta=cfg.rope_theta)
            if mode == "decode":
                ck, cv = cache
                y, nk, nv = attention.gqa_decode(
                    p["attn"], h, ck, cv, positions,
                    cache_index=cache_index, window=window, masked=masked,
                    **kw)
                new_cache = (nk, nv)
            elif mode == "prefill":
                y, new_cache = attention.gqa_block(
                    p["attn"], h, positions, causal=True, window=window,
                    return_kv=True, **kw)
            else:
                y = attention.gqa_block(p["attn"], h, positions, causal=True,
                                        window=window, **kw)
        x = x + y
        h2 = layers.apply_norm(cfg.norm_kind, p["norm2"], x, cfg.norm_eps)
        if cfg.num_experts:
            y2, aux = moe.moe_block(p["moe"], h2, num_experts=cfg.num_experts,
                                    k=cfg.experts_per_token,
                                    cf=cfg.capacity_factor,
                                    num_shared=cfg.num_shared_experts)
        else:
            y2 = layers.apply_mlp(cfg.mlp_kind, p["mlp"], h2)
        x = x + y2
    elif kind == "mamba":
        if mode == "decode":
            y, new_cache = ssm.mamba2_decode(p["mamba"], cfg, h, cache)
        elif mode == "prefill":
            y, new_cache = ssm.mamba2_block(p["mamba"], cfg, h, return_cache=True)
        else:
            y = ssm.mamba2_block(p["mamba"], cfg, h)
        x = x + y
    elif kind == "mlstm":
        if mode == "decode":
            y, new_cache = xlstm.mlstm_block(p["mlstm"], cfg, h, cache=cache,
                                             decode=True)
        elif mode == "prefill":
            y, new_cache = xlstm.mlstm_block(p["mlstm"], cfg, h,
                                             return_cache=True)
        else:
            y = xlstm.mlstm_block(p["mlstm"], cfg, h)
        x = x + y
    elif kind == "slstm":
        if mode == "decode":
            y, new_cache = xlstm.slstm_block(p["slstm"], cfg, h, cache=cache,
                                             decode=True)
        elif mode == "prefill":
            y, new_cache = xlstm.slstm_block(p["slstm"], cfg, h,
                                             return_cache=True)
        else:
            y = xlstm.slstm_block(p["slstm"], cfg, h)
        x = x + y
        if cfg.xlstm_form == "published":
            h2 = layers.apply_norm(cfg.norm_kind, p["norm2"], x, cfg.norm_eps)
            x = x + layers.apply_mlp(cfg.mlp_kind, p["mlp"], h2)
    else:
        raise ValueError(kind)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------
def segments_of(cfg) -> List[Tuple[str, int]]:
    """[(kind, n_layers), ...] grouping consecutive same-kind layers,
    additionally split at shared-attention insertion points (zamba2)."""
    segs: List[Tuple[str, int]] = []
    for i, kind in enumerate(cfg.block_pattern):
        boundary = (cfg.shared_attn_period
                    and i > 0 and i % cfg.shared_attn_period == 0)
        if segs and segs[-1][0] == kind and not boundary:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return segs


def init_segments(key, cfg, dtype):
    """-> list of (kind, stacked_params) following segments_of(cfg)."""
    segs = segments_of(cfg)
    out = []
    keys = layers.split(key, len(segs))
    first = 0
    for (kind, n), k in zip(segs, keys):
        layer_keys = layers.split(k, n)
        ps = [init_block(lk, cfg, kind, dtype, first + i)
              for i, lk in enumerate(layer_keys)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
        out.append({"kind": kind, "params": stacked, "n": n})
        first += n
    return out


def run_segment(seg_params, cfg, kind: str, x, positions, *, window: int,
                mode: str, cache=None, cache_index=None, remat: bool = True,
                masked: bool = False):
    """Scan a stacked segment. cache is stacked on the leading layer axis.
    Returns (x, aux_sum, new_cache_stacked)."""

    def body(carry, inp):
        xc = carry
        lp, lc = inp
        fn = lambda xx: apply_block(lp, cfg, kind, xx, positions,
                                    window=window, mode=mode, cache=lc,
                                    cache_index=cache_index, masked=masked)
        if remat and mode == "train":
            fn = _wrap_remat(fn)
        x2, aux, nc = fn(xc)
        return x2, (aux, nc)

    x, (auxs, new_cache) = jax.lax.scan(body, x, (seg_params, cache),
                                        unroll=True if UNROLL else 1)
    return x, jnp.sum(auxs), new_cache
