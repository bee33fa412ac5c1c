"""Traffic generators, copied from the program's `repro.data.synthetic`
(`class_images`, `token_stream`, `lm_batches`) and `repro.data.partition`
(`uniform_split`) so that a change to the program cannot move the
yardstick; `class_images` draws in bulk where the program loops. Outputs
are pinned by checksums in the benchmark's tests.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

# images made per block of the noise draw, which bounds its memory
IMAGE_BLOCK = 4096


def class_images(n: int, *, num_classes: int = 10, image: int = 28,
                 channels: int = 1, noise: float = 0.5, modes: int = 4,
                 seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """-> x (n, image, image, channels) float32, y (n,) int32.

    Each class is a mixture of `modes` templates that share two anchor
    blobs and differ in a third blob and a grating phase; every image is a
    shifted, scaled template plus Gaussian noise. The program's generator
    draws the same quantities image by image; this one draws them in bulk,
    so its images follow the same law but are not the same draws."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    m_id = rng.integers(0, modes, size=n)
    shift = rng.integers(-2, 3, size=(n, 2))
    scale = rng.uniform(0.8, 1.2, size=n)
    yy, xx = np.meshgrid(np.linspace(-1, 1, image), np.linspace(-1, 1, image),
                         indexing="ij")
    tpl_rng = np.random.default_rng(12345)
    blob = lambda cx, cy, s: np.exp(-((xx - cx) ** 2 + (yy - cy) ** 2)
                                    / (2 * s * s))
    templates = []
    for c in range(num_classes):
        base = sum(blob(*tpl_rng.uniform(-0.6, 0.6, 2),
                        tpl_rng.uniform(0.15, 0.3)) for _ in range(2))
        fx, fy = tpl_rng.uniform(2, 6, 2)
        per_class = []
        for m in range(modes):
            t = base + blob(*tpl_rng.uniform(-0.7, 0.7, 2),
                            tpl_rng.uniform(0.1, 0.25)) * 1.5
            ph = tpl_rng.uniform(0, 2 * np.pi)
            t = t + 0.5 * np.sin(fx * np.pi * xx + fy * np.pi * yy + ph)
            per_class.append(t / np.abs(t).max())
        templates.append(per_class)
    tpl = np.asarray(templates)
    # every template under each of the 5 x 5 shifts, so an image is a lookup
    rolled = np.stack([np.roll(tpl, (a, b), axis=(2, 3))
                       for a in range(-2, 3) for b in range(-2, 3)], axis=2)
    rolled = rolled.reshape(tpl.shape[:2] + (5, 5) + tpl.shape[2:])
    xs = np.zeros((n, image, image, channels), np.float32)
    for a in range(0, n, IMAGE_BLOCK):
        b = min(a + IMAGE_BLOCK, n)
        img = (rolled[y[a:b], m_id[a:b], shift[a:b, 0] + 2, shift[a:b, 1] + 2]
               * scale[a:b, None, None]
               + rng.normal(0, noise, (b - a, image, image)))
        xs[a:b, :, :, 0] = np.clip(img, -2, 2)
    return xs, y


def uniform_split(x: np.ndarray, y: np.ndarray, n_clients: int,
                  seed: int = 0) -> List[Tuple[np.ndarray, np.ndarray]]:
    """The paper's partition: shuffle, then split into equal parts."""
    rng = np.random.default_rng(seed)
    idx = rng.permutation(len(x))
    parts = np.array_split(idx, n_clients)
    return [(x[p], y[p]) for p in parts]


def token_stream(n_tokens: int, *, vocab: int = 512, order: int = 2,
                 seed: int = 0) -> np.ndarray:
    """Markov token stream: each context maps to 4 likely next tokens."""
    rng = np.random.default_rng(seed)
    n_ctx = 4096
    ctx_next = rng.integers(0, vocab, size=(n_ctx, 4))
    toks = np.zeros(n_tokens, np.int32)
    toks[:order] = rng.integers(0, vocab, order)
    h = 0
    for i in range(order, n_tokens):
        h = (h * 31 + int(toks[i - 1])) % n_ctx
        if rng.random() < 0.8:
            toks[i] = ctx_next[h, rng.integers(4)]
        else:
            toks[i] = rng.integers(vocab)
    return toks


def lm_batches(tokens: np.ndarray, batch: int, seq: int, steps: int,
               seed: int = 0):
    """Yield dicts(tokens (B,S), labels (B,S)) sliced from the stream."""
    rng = np.random.default_rng(seed)
    n = len(tokens) - seq - 1
    for _ in range(steps):
        idx = rng.integers(0, n, size=batch)
        x = np.stack([tokens[i:i + seq] for i in idx])
        y = np.stack([tokens[i + 1:i + seq + 1] for i in idx])
        yield {"tokens": x, "labels": y}


def sub_seeds(seed: int, n: int) -> List[int]:
    """`n` independent 31-bit seeds derived from any whole-number seed
    (the driver's seeds exceed 32 bits)."""
    ss = np.random.SeedSequence(int(seed))
    return [int(s) & 0x7FFFFFFF for s in ss.generate_state(n)]
