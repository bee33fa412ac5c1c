"""Tiny shapes of the cells and configurations added after
`tests/benchtest_util.py` was written, registered beside its own, so that
the benchmark's tests that run every cell of BENCHMARK.json at a size a
CPU test can hold find them."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tests"))

import benchtest_util  # noqa: E402

benchtest_util.SMALL_TRAFFIC.setdefault(
    "lm-xlstm125m-silo", dict(batch=2, seq_len=64, distinct_batches=4))
benchtest_util.SMALL_CONFIG.setdefault(
    "xlstm-7x1-125m", dict(num_layers=3, d_model=64, d_ff=96, d_feature=64,
                           vocab_size=512,
                           block_pattern=["mlstm", "slstm", "mlstm"],
                           ssm_chunk=16, disc_tokens=96, num_negatives=63))
