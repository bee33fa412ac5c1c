"""The benchmark's copied traffic generators, pinned to stored checksums
(not to the program's own generators, which a later change may move)."""
import hashlib

import numpy as np

import benchtest_util  # noqa: F401
from benchlib import gen


def _sha(*arrays) -> str:
    return hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes()
                                   for a in arrays)).hexdigest()


def test_class_images_and_split_are_pinned():
    x, y = gen.class_images(64, seed=3, noise=0.8)
    assert x.shape == (64, 28, 28, 1) and x.dtype == np.float32
    assert _sha(x, y) == ("31948fca77ae6ec709a6e3606e17f489"
                          "d71ca89d7ef76a7ec96bef5da00a3850")
    parts = gen.uniform_split(x, y, 4, seed=5)
    assert [len(p[1]) for p in parts] == [16] * 4
    assert _sha(*[p[1] for p in parts]) == (
        "ed808da02fdb4be33116f2850933713952064003cf947d9b593ccc351af1fb7a")


def test_token_stream_and_batches_are_pinned():
    t = gen.token_stream(5000, vocab=50304, seed=4)
    assert _sha(t) == ("6ce5ecc1049409c0aa1328deb2f96ad0"
                       "12099e1ba04d2e213b55232eaa103021")
    b = list(gen.lm_batches(t, 2, 64, 3, seed=6))
    assert all(np.array_equal(bb["tokens"][:, 1:], bb["labels"][:, :-1])
               for bb in b)
    assert _sha(*[v for bb in b for v in (bb["tokens"], bb["labels"])]) == (
        "d2a2f7ffd284f4e106e844b0ef4953080ce0158bae4c416e22a88910d3922455")


def test_sub_seeds_take_any_whole_seed():
    assert gen.sub_seeds(2 ** 40 + 5, 4) == [1619662603, 1524897621,
                                              2127416108, 68529128]
    assert gen.sub_seeds(7, 3) == gen.sub_seeds(7, 3)
    assert gen.sub_seeds(7, 3) != gen.sub_seeds(8, 3)
