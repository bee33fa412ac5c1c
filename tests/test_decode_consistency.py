"""Prefill→decode equals full forward at the appended position, per arch
family (the strongest correctness check for the serving path)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import encdec, lm

KEY = jax.random.PRNGKey(0)
S = 12

# attention-cache archs: decode appends via the padded-slot trick
APPEND_ARCHS = ["tinyllama-1.1b", "chatglm3-6b", "deepseek-v2-lite-16b",
                "minicpm3-4b", "granite-moe-1b-a400m"]
# pure-state archs: caches are recurrent states, append is native
STATE_ARCHS = ["xlstm-125m", "xlstm-7x1-125m"]


@pytest.mark.parametrize("arch", APPEND_ARCHS)
def test_decode_appends_exactly_attention(arch):
    cfg = get_arch(arch).reduced()
    if cfg.num_experts:
        # capacity effects differ between S-1 and S token dispatch: relax by
        # using ample capacity so routing is identical
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    p = lm.init_lm(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    full = lm.forward(p, cfg, {"tokens": toks}, mode="train")
    pre = lm.forward(p, cfg, {"tokens": toks[:, :S - 1]}, mode="prefill")
    padded = lm.pad_cache_for_decode(cfg, pre["caches"])
    dec = lm.decode_step(p, cfg, {"tokens": toks[:, S - 1:]}, padded)
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full["logits"][:, -1]),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("arch", STATE_ARCHS)
def test_decode_appends_exactly_state(arch):
    cfg = get_arch(arch).reduced()
    p = lm.init_lm(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    full = lm.forward(p, cfg, {"tokens": toks}, mode="train")
    pre = lm.forward(p, cfg, {"tokens": toks[:, :S - 1]}, mode="prefill")
    dec = lm.decode_step(p, cfg, {"tokens": toks[:, S - 1:]}, pre["caches"])
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full["logits"][:, -1]),
                               atol=2e-3, rtol=2e-3)


def test_decode_appends_exactly_mamba_only_zamba():
    cfg = get_arch("zamba2-1.2b").reduced()
    cfg = dataclasses.replace(cfg, shared_attn_period=0)   # pure-state path
    p = lm.init_lm(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    full = lm.forward(p, cfg, {"tokens": toks}, mode="train")
    pre = lm.forward(p, cfg, {"tokens": toks[:, :S - 1]}, mode="prefill")
    dec = lm.decode_step(p, cfg, {"tokens": toks[:, S - 1:]}, pre["caches"])
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full["logits"][:, -1]),
                               atol=2e-3, rtol=2e-3)


def test_decode_whisper_appends():
    cfg = get_arch("whisper-small").reduced()
    p = encdec.init_encdec(KEY, cfg)
    frames = jax.random.normal(jax.random.PRNGKey(2),
                               (2, cfg.encoder_seq, cfg.d_model))
    enc = encdec.encode(p, cfg, frames)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    full = encdec.decode_forward(p, cfg, toks, enc, mode="train")
    pre = encdec.decode_forward(p, cfg, toks[:, :S - 1], enc, mode="prefill")
    self_c = jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, 1), (0, 0), (0, 0)]),
        pre["caches"]["self"])
    dec = encdec.decode_forward(p, cfg, toks[:, S - 1:], None, mode="decode",
                                self_cache=self_c,
                                cross_kv=pre["caches"]["cross"])
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full["logits"][:, -1]),
                               atol=2e-3, rtol=2e-3)


def test_vlm_embeddings_decode():
    cfg = get_arch("qwen2-vl-7b").reduced()
    p = lm.init_lm(KEY, cfg)
    emb = jax.random.normal(jax.random.PRNGKey(3), (2, S, cfg.d_model))
    from repro.nn import rope
    pos = rope.default_positions(2, S, "mrope")
    full = lm.forward(p, cfg, {"embeddings": emb, "positions": pos},
                      mode="train")
    pre = lm.forward(p, cfg, {"embeddings": emb[:, :S - 1],
                              "positions": pos[:, :S - 1]}, mode="prefill")
    padded = lm.pad_cache_for_decode(cfg, pre["caches"])
    dec = lm.decode_step(p, cfg, {"embeddings": emb[:, S - 1:],
                                  "positions": pos[:, S - 1:]}, padded)
    np.testing.assert_allclose(np.asarray(dec["logits"][:, 0]),
                               np.asarray(full["logits"][:, -1]),
                               atol=2e-3, rtol=2e-3)


def test_masked_incremental_decode_matches_forward():
    """Serving path: fixed-size cache + cache_index + validity masking
    generates the same logits as teacher-forced full forwards."""
    cfg = get_arch("tinyllama-1.1b").reduced()
    p = lm.init_lm(KEY, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    # prefill 4, then feed tokens 4..7 one at a time into a size-8 cache
    pre = lm.forward(p, cfg, {"tokens": toks[:, :4]}, mode="prefill")
    caches = jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, 4)] + [(0, 0)] *
                          (a.ndim - 3)), pre["caches"]["segments"][0])
    caches = {"segments": [caches], "shared": []}
    outs = []
    for i in range(4, 8):
        o = lm.decode_step(p, cfg, {"tokens": toks[:, i:i + 1]}, caches,
                           cache_index=jnp.asarray(i, jnp.int32),
                           masked=True)
        caches = o["caches"]
        outs.append(o["logits"][:, 0])
    full = lm.forward(p, cfg, {"tokens": toks}, mode="train")
    for i, got in enumerate(outs):
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(full["logits"][:, 4 + i]),
                                   atol=2e-3, rtol=2e-3)
