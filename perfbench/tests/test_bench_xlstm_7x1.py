"""The published xLSTM[7:1] 125M configuration (`configs/xlstm-7x1-125m`)
and its cell `lm-xlstm125m-silo`: on the CPU at a small size (d_model 64,
an mLSTM, an sLSTM and an mLSTM block), the program's step against the
plain reference in float32, and each planted departure against the cell's
limits; at full size, the weights' layout and the hand counts of
parameters and FLOPs, checked without allocating them."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest

import benchtest_util
from benchlib import cells, compare

CELL = "lm-xlstm125m-silo"


def _small(dtype):
    w = benchtest_util.small_workload(CELL)
    cfg, mod = benchtest_util.small_config(w["config"])
    cfg["dtype"] = dtype
    return cfg, mod, w


def _program(cfg, mod, w, seed):
    cell = mod.Cell(cfg, w["traffic"], seed=seed, devices=jax.devices()[:1],
                    limits=w["limits"], log=lambda msg: None)
    cell.setup()
    cell.release()
    return cell


def test_program_step_is_the_reference_in_float32():
    """Loss terms, the first gradient leaf by leaf, the change after three
    Adam steps and the per-class feature sums of the program's timed step
    agree with the reference's to float32 rounding: the program's chunked
    mLSTM and the reference's quadratic form sum in different orders.
    Adam's change is near sign-like, so a leaf whose gradient is nought to
    rounding can move by a step either way: the change gets 1e-3."""
    cfg, mod, w = _small("float32")
    cell = _program(cfg, mod, w, 2 ** 35 + 11)
    ref = mod.Reference(cfg).run(cell.data)
    got = {c["name"]: c["value"]
           for c in mod.compare_summaries(cell.prog, ref, w["limits"])}
    assert got["loss_gap"] < 1e-5 and got["proto_gap"] < 1e-5, got
    assert got["grad_gap_all"] < 1e-4 and got["update_gap"] < 1e-3, got
    assert got["count_mismatch"] == 0, got


def test_program_passes_the_cell_limits():
    """In the configuration's precision, bfloat16."""
    cfg, mod, w = _small("bfloat16")
    cell = _program(cfg, mod, w, 2 ** 35 + 13)
    checks = mod.compare_summaries(cell.prog, mod.Reference(cfg).run(
        cell.data), w["limits"])
    assert compare.all_within(checks), checks


@pytest.fixture(scope="module")
def small_f32():
    """The cell at a small size in float32, where the program reads as the
    reference (above), with its data and the reference's summary."""
    cfg, mod, w = _small("float32")
    data = mod.Data(cfg, w["traffic"], 2 ** 35 + 13)
    return cfg, mod, w, data, mod.Reference(cfg).run(data)


@pytest.mark.parametrize("departure", [
    "half_batch", "mlstm_skip_dropped", "slstm_conv_dropped",
    "slstm_r_dropped"])
def test_a_departure_fails_the_cell_limits(small_f32, departure):
    """The half batch and each part of the model left out (`FAULTS`: the
    mLSTM's skip, the sLSTM's conv or its recurrent term) fail at least
    one of the cell's limits. The operand-only fp8 control separates from
    the program only at the cell's size (its feature sums, `proto_gap`):
    its readings come from the chip (PERF.md, section 4). The cross-head
    recurrence of `FAULTS` fails none: it departs only through the
    recurrent kernels, which start at 0, and the limits read norms."""
    cfg, mod, w, data, ref = small_f32
    if departure == "half_batch":
        other = mod.Reference(cfg, half_batch=True)
    else:
        other = mod.Reference(cfg, **mod.FAULTS[departure])
    checks = mod.compare_summaries(other.run(data), ref, w["limits"])
    assert not compare.all_within(checks), checks


def test_a_transposed_recurrent_kernel_is_the_same_model(small_f32):
    """Each head's recurrent kernel applied transposed reads as the
    reference to the last bit: the kernels start at 0 and Adam acts entry
    by entry, so it is the same model with its entries stored elsewhere,
    and no limit should fail it (`FAULTS`)."""
    cfg, mod, w, data, ref = small_f32
    other = mod.Reference(cfg, depart=("r_transposed",)).run(data)
    assert all(c["value"] == 0.0
               for c in mod.compare_summaries(other, ref, w["limits"]))


def test_weights_are_the_programs_layout_and_the_hand_count():
    from repro.models import lm
    cfg, mod = cells.config("xlstm-7x1-125m")
    key = jax.random.PRNGKey(0)
    got = jax.eval_shape(lambda k: mod.init_params(k, cfg), key)
    want = jax.eval_shape(lambda k: lm.init_lm(k, mod.model_config(cfg)), key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == [
        (a.shape, a.dtype) for a in jax.tree.leaves(want)]
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(got))
    assert mod.block_params(cfg, "mlstm") == 3_605_768
    assert mod.block_params(cfg, "slstm") == 3_548_160
    assert n == cfg["parameters"] == (21 * 3_605_768 + 3 * 3_548_160
                                      + 2 * 50304 * 768 + 768)
    assert 163.6e6 <= n <= 163.8e6


def test_train_flops_at_full_size_are_the_hand_count():
    cfg, mod = cells.config("xlstm-7x1-125m")
    # mLSTM, d=768, di=1536, H=4, P=384, blocks of 4, Q=256 (causal mean
    # 128.5): up 4,718,592, q/k/v 36,864, gates 73,728, down 2,359,296,
    # scores 394,752, values 395,780, chunk states and read-out
    # 2,365,440                                          -> 10,344,452
    # sLSTM, P=192: gates 1,179,648, recurrence 1,179,648,
    # feed-forward 2*3*768*1024 = 4,718,592               ->  7,077,888
    # head 2*768*50304 = 77,266,944; 21 mLSTM and 3 sLSTM blocks
    fwd = 21 * 10_344_452 + 3 * 7_077_888 + 77_266_944
    assert mod.forward_flops_per_token(cfg) == fwd == 315_734_100
    # a sample is 2048 tokens; the discriminator at T = 8192, K = 1023 over
    # a step of 16 samples: 3,953,440,456,704 FLOP (the staged cell's count)
    disc = 2 * (632_970_805_248 + 79_044_083_712) + 2_529_410_678_784
    assert mod.train_flops_per_sample(cfg, 16, 2048) == 3 * fwd * 2048 \
        + disc / 16 == 2_186_960_338_944


@pytest.mark.parametrize("kind", ["mlstm", "slstm"])
def test_hand_flops_are_xlas_count_at_small_size(kind):
    """Per token of one block, the hand count against XLA's cost analysis
    of the program's block (its HLO before optimisation), at d_model 256:
    the mLSTM over one chunk of 64 tokens, the sLSTM (with its
    feed-forward sub-block) over one token, where its scan runs once and
    XLA counts it once. The hand count leaves out the pointwise work XLA
    counts (norms, convolutions, gates, GeLU: under 6% here) and counts
    the causal half of the in-chunk products, which the program computes
    in full and masks."""
    from repro.models import blocks
    cfg, mod = cells.config("xlstm-7x1-125m")
    cfg.update(d_model=256, d_ff=384, ssm_chunk=64, vocab_size=1,
               num_layers=1, block_pattern=[kind], dtype="float32")
    mcfg = mod.model_config(cfg)
    p = blocks.init_block(jax.random.PRNGKey(0), mcfg, kind, jnp.float32)
    B, S = (4, 64) if kind == "mlstm" else (64, 1)
    x = jnp.ones((B, S, 256))
    xla = jax.jit(lambda p, x: blocks.apply_block(p, mcfg, kind, x, None)[0]
                  ).lower(p, x).cost_analysis()["flops"] / (B * S)
    hand = mod.forward_flops_per_token(cfg) - 2 * 256
    if kind == "mlstm":
        H, P, Q = 4, 128, 64
        hand += (Q - (Q + 1) / 2) * (2 * H * P + 2 * H * (P + 1))
    assert hand <= xla <= 1.06 * hand, (kind, hand, xla)


def test_model_config_is_the_registry_entry():
    """At the file's sizes the configuration is the program's registered
    `xlstm-7x1-125m`, field for field."""
    from repro.configs import get_arch
    cfg, mod = cells.config("xlstm-7x1-125m")
    assert mod.model_config(cfg) == dataclasses.replace(
        get_arch("xlstm-7x1-125m"), d_feature=768)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_fault_in_the_timed_step_makes_the_run_incorrect(monkeypatch,
                                                           fault):
    """A whole run of the cell at a small size, with the program's own
    step broken as `test_bench_faults.py` breaks the staged LM's: state
    returned unchanged, or half of every batch left out."""
    import test_bench_faults
    from repro.launch import train as train_lib
    plant = {"unchanged": test_bench_faults._lm_unchanged,
             "half_batch": test_bench_faults._lm_half}[fault]
    monkeypatch.setattr(train_lib, "make_train_step",
                        plant(train_lib.make_train_step))
    rc, line, _ = benchtest_util.run_small(monkeypatch, CELL, 2 ** 33 + 3)
    assert rc == 0
    assert line["correct"] is False, line["checks"]
