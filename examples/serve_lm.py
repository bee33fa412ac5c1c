"""End-to-end serving driver: prefill a batch of prompts, then decode tokens
with the KV cache — the same `prefill_step` / `decode_step` that the
decode_32k / long_500k dry-runs lower, on a small model at CPU scale.

  PYTHONPATH=src python examples/serve_lm.py [--arch xlstm-125m] [--tokens N]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_arch
from repro.data import synthetic
from repro.launch import serve as serve_lib
from repro.models import lm


def _grow_caches(cfg, caches, extra: int):
    """Extend every attention-cache seq axis by `extra` empty slots."""
    from repro.models import blocks

    def pad(c, axis):
        return jax.tree.map(
            lambda a: jnp.pad(a, [(0, extra if i == axis else 0)
                                  for i in range(a.ndim)]), c)

    out = {"segments": [], "shared": []}
    for (kind, _), c in zip(blocks.segments_of(cfg), caches["segments"]):
        out["segments"].append(pad(c, 2) if kind == "attn" else c)
    for c in caches["shared"]:
        out["shared"].append(pad(c, 1))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced(num_layers=2, d_model=256, vocab_size=512)
    print(f"serving {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")

    key = jax.random.PRNGKey(0)
    params = lm.init_lm(key, cfg)
    stream = synthetic.token_stream(10_000, vocab=cfg.vocab_size, seed=1)
    prompts = np.stack([stream[i * 100:i * 100 + args.prompt_len]
                        for i in range(args.batch)])

    prefill = jax.jit(serve_lib.make_prefill_step(cfg))

    # fixed-size cache = prompt + generation budget; decode writes at
    # cache_index with validity masking -> ONE compile for all steps.
    total = args.prompt_len + args.tokens
    decode = jax.jit(lambda p, b, c, i: lm.decode_step(
        p, cfg, b, c, cache_index=i, masked=True))

    t0 = time.perf_counter()
    out = prefill(params, {"tokens": jnp.asarray(prompts)})
    caches = _grow_caches(cfg, out["caches"], args.tokens)
    logits = out["logits"]
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    generated = []
    t0 = time.perf_counter()
    for i in range(args.tokens):
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        generated.append(np.asarray(nxt)[:, 0])
        out = decode(params, {"tokens": nxt}, caches,
                     jnp.asarray(args.prompt_len + i, jnp.int32))
        logits = out["logits"]
        caches = out["caches"]
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    gen = np.stack(generated, axis=1)
    print(f"prefill: {args.batch}x{args.prompt_len} tokens in "
          f"{t_prefill*1e3:.1f} ms")
    print(f"decode : {args.tokens} steps x batch {args.batch} in "
          f"{t_decode*1e3:.1f} ms "
          f"({args.tokens*args.batch/t_decode:.1f} tok/s)")
    print("sample continuation ids:", gen[0][:12])


if __name__ == "__main__":
    compile_cache.enable()
    main()
