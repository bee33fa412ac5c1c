"""Decoder-only LM spine shared by all non-enc-dec architectures.

Params are a pure pytree (dicts/lists of arrays); the static structure
(segment kinds, shared-block insertion points) is derived from the config.
`forward` covers train (features+logits) and prefill (also returns caches);
`decode_step` is the one-token serve path. Features = post-final-norm last
hidden states — the `d'`-dimensional representations the paper shares.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.models import blocks
from repro.nn import layers, rope as rope_lib


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def shared_points(cfg) -> List[int]:
    """Cumulative-layer counts after which the shared attn block runs."""
    if not cfg.shared_attn_period:
        return []
    k = cfg.shared_attn_period
    return [i for i in range(k, cfg.num_layers + 1, k)]


def init_lm(key, cfg):
    dt = _dtype(cfg)
    ks = layers.split(key, 5)
    params: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        params["embed"] = layers.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt)
    params["segments"] = [s["params"] for s in blocks.init_segments(ks[1], cfg, dt)]
    params["final_norm"] = layers.init_norm(cfg.norm_kind, cfg.d_model, dt)
    if not cfg.tie_embeddings or cfg.input_kind != "tokens":
        params["lm_head"] = layers.dense_init(ks[2], cfg.d_model,
                                              cfg.vocab_size, dt)
    if cfg.shared_attn_period:
        params["shared"] = blocks.init_block(ks[3], cfg, "attn", dt)
    return params


def _embed(params, cfg, batch):
    if cfg.input_kind == "tokens":
        return jnp.take(params["embed"], batch["tokens"], axis=0)
    return batch["embeddings"].astype(_dtype(cfg))


def _head(params, cfg, features):
    w = params.get("lm_head")
    if w is None:                                   # tied
        w = params["embed"].T
    with jax.named_scope("head"):
        return jnp.einsum("bsd,dv->bsv", features, w)


def _positions(cfg, batch, B, S, offset=0):
    pos = batch.get("positions")
    if pos is None:
        pos = rope_lib.default_positions(B, S, cfg.rope_kind, offset=offset)
    return pos


def forward(params, cfg, batch, *, mode: str = "train", window: int = 0):
    """-> dict(features, logits, aux, caches). window>0 = sliding-window attn."""
    x = _embed(params, cfg, batch)
    B, S = x.shape[:2]
    positions = _positions(cfg, batch, B, S)
    segs = blocks.segments_of(cfg)
    points = shared_points(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    caches: Dict[str, Any] = {"segments": [], "shared": []}
    count = 0
    for seg_params, (kind, n) in zip(params["segments"], segs):
        x, aux, cache = blocks.run_segment(
            seg_params, cfg, kind, x, positions, window=window, mode=mode)
        aux_total = aux_total + aux
        caches["segments"].append(cache)
        count += n
        if count in points and count < cfg.num_layers + 1:
            x, aux2, sc = blocks.apply_block(
                params["shared"], cfg, "attn", x, positions, window=window,
                mode=mode)
            aux_total = aux_total + aux2
            caches["shared"].append(sc)
    features = layers.apply_norm(cfg.norm_kind, params["final_norm"], x,
                                 cfg.norm_eps)
    logits = _head(params, cfg, features)
    return {"features": features, "logits": logits, "aux": aux_total,
            "caches": caches if mode == "prefill" else None}


def decode_step(params, cfg, batch, caches, *, window: int = 0,
                cache_index=None, masked: bool = False):
    """One-token decode. batch: tokens (B,1) (or embeddings (B,1,d)).

    caches: pytree from `forward(mode="prefill")` (or `init_cache`).
    Default (dry-run) semantics: the new token overwrites the LAST cache
    slot, every slot valid — cost identical to a real rolling decode step.
    Serving semantics: pass `cache_index` (slot to write) and `masked=True`
    (attend only to slots <= cache_index) to generate incrementally into a
    fixed-size cache without reshaping/recompiling.
    """
    x = _embed(params, cfg, batch)
    B = x.shape[0]
    S_ctx = _cache_len(cfg, caches)
    positions = batch.get("positions")
    if positions is None:
        offset = (S_ctx - 1) if cache_index is None else cache_index
        positions = rope_lib.default_positions(B, 1, cfg.rope_kind,
                                               offset=offset)
    segs = blocks.segments_of(cfg)
    points = shared_points(cfg)
    new_caches: Dict[str, Any] = {"segments": [], "shared": []}
    count = 0
    shared_i = 0
    for seg_params, (kind, n), cache in zip(params["segments"], segs,
                                            caches["segments"]):
        x, _, nc = blocks.run_segment(
            seg_params, cfg, kind, x, positions, window=window, mode="decode",
            cache=cache, cache_index=cache_index, masked=masked)
        new_caches["segments"].append(nc)
        count += n
        if count in points and count < cfg.num_layers + 1:
            x, _, sc = blocks.apply_block(
                params["shared"], cfg, "attn", x, positions, window=window,
                mode="decode", cache=caches["shared"][shared_i],
                cache_index=cache_index, masked=masked)
            new_caches["shared"].append(sc)
            shared_i += 1
    features = layers.apply_norm(cfg.norm_kind, params["final_norm"], x,
                                 cfg.norm_eps)
    logits = _head(params, cfg, features)
    return {"features": features, "logits": logits, "caches": new_caches}


def _cache_len(cfg, caches) -> int:
    for seg, (kind, _) in zip(caches["segments"], blocks.segments_of(cfg)):
        if kind == "attn":
            if cfg.is_mla:
                return seg.shape[2]          # (L,B,S,r+dr)
            return seg[0].shape[2]           # (L,B,S,G,hd)
    if caches["shared"]:
        sc = caches["shared"][0]
        return sc.shape[1] if cfg.is_mla else sc[0].shape[1]
    return 1


def init_cache(cfg, batch_size: int, ctx_len: int, *, window: int = 0):
    """Zero caches shaped for decode at context length ctx_len (ShapeDtype-
    compatible: used by dryrun via eval_shape and by serve.py for real)."""
    dt = _dtype(cfg)
    S = min(ctx_len, window) if window else ctx_len
    segs = blocks.segments_of(cfg)
    caches: Dict[str, Any] = {"segments": [], "shared": []}

    def attn_cache(n):
        if cfg.is_mla:
            return jnp.zeros((n, batch_size, S,
                              cfg.kv_lora_rank + cfg.qk_rope_dim), dt)
        return (jnp.zeros((n, batch_size, S, cfg.num_kv_heads, cfg.head_dim), dt),
                jnp.zeros((n, batch_size, S, cfg.num_kv_heads, cfg.v_head_dim), dt))

    for kind, n in segs:
        if kind == "attn":
            caches["segments"].append(attn_cache(n))
        elif kind == "mamba":
            C = cfg.d_inner + 2 * cfg.ssm_state
            caches["segments"].append(
                (jnp.zeros((n, batch_size, cfg.ssm_conv - 1, C), dt),
                 jnp.zeros((n, batch_size, cfg.mamba_heads, cfg.mamba_head_dim,
                            cfg.ssm_state), jnp.float32)))
        elif kind == "mlstm":
            di = 2 * cfg.d_model
            P = di // cfg.num_heads
            caches["segments"].append(
                (jnp.zeros((n, batch_size, cfg.ssm_conv - 1, di), dt),
                 jnp.zeros((n, batch_size, cfg.num_heads, P + 1, P), jnp.float32)))
        elif kind == "slstm":
            d = cfg.d_model
            z = jnp.zeros((n, batch_size, d), jnp.float32)
            state = (z, z, jnp.full((n, batch_size, d), -30.0, jnp.float32), z)
            if cfg.xlstm_form == "published":
                state = (jnp.zeros((n, batch_size, cfg.ssm_conv - 1, d), dt),
                         state)
            caches["segments"].append(state)
    n_shared = len(shared_points(cfg))
    for _ in range(n_shared):
        c = attn_cache(1)
        c = jax.tree.map(lambda a: a[0], c)   # shared block is unstacked
        caches["shared"].append(c)
    return caches


def pad_cache_for_decode(cfg, caches):
    """Append one empty slot to every attention cache seq axis.

    decode_step writes the new token at the LAST cache slot; padding a
    prefill(S-1)-cache to length S makes the decode an exact append —
    decode(x_S | prefill(x_0..x_{S-1})) equals forward(x_0..x_S) at the last
    position. SSM/xLSTM caches are recurrent states and need no padding.
    """
    def pad_attn(c):
        return jax.tree.map(
            lambda a: jnp.pad(a, [(0, 1 if i == 2 else 0)
                                  for i in range(a.ndim)]), c)

    out = {"segments": [], "shared": []}
    for (kind, _), cache in zip(blocks.segments_of(cfg), caches["segments"]):
        out["segments"].append(pad_attn(cache) if kind == "attn" else cache)
    for cache in caches["shared"]:
        sc = jax.tree.map(
            lambda a: jnp.pad(a, [(0, 1 if i == 1 else 0)
                                  for i in range(a.ndim)]), cache)
        out["shared"].append(sc)
    return out
