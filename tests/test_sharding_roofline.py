"""Sharding rules + roofline HLO parsing (pure-python units)."""
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.launch import roofline
from repro.configs import get_arch
from repro.types import ShapeConfig


def test_param_spec_vocab_over_model():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = sharding.param_spec("embed", (65024, 4096), FakeMesh(), fsdp=False)
    assert spec == P("model", None)
    # size-1 model axis -> no sharding
    class OneMesh:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 1}
    assert sharding.param_spec("embed", (65024, 4096), OneMesh(),
                               fsdp=False) == P(None, None)


def test_param_spec_non_divisible_replicates():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = sharding.param_spec("wq", (100, 37), FakeMesh(), fsdp=False)
    assert spec == P(None, None)            # 37 % 16 != 0


def test_param_spec_fsdp_adds_data_axis():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    spec = sharding.param_spec("w_up", (8192, 22016), FakeMesh(), fsdp=True)
    assert spec == P("data", "model")


def test_head_axis_plan():
    assert sharding.head_axis_plan(32, 128, 16) == "heads"
    assert sharding.head_axis_plan(28, 128, 16) == "head_dim"
    assert sharding.head_axis_plan(28, 100, 16) == "none"
    assert sharding.head_axis_plan(28, 100, 1) == "none"


HLO = """
ENTRY %main {
  %p0 = bf16[128,1024]{1,0} parameter(0)
  %ar = bf16[128,1024]{1,0} all-reduce(bf16[128,1024]{1,0} %p0), replica_groups={}
  %ag = f32[256,64]{1,0} all-gather(f32[16,64]{1,0} %p0b), dimensions={0}
  %rs = f32[2,4]{1,0} reduce-scatter(f32[32,4]{1,0} %x), dimensions={0}
  %cp = u32[8]{0} collective-permute(u32[8]{0} %y), source_target_pairs={{0,1}}
  %a2a = bf16[4,4]{1,0} all-to-all(bf16[4,4]{1,0} %z), dimensions={0}
  %not = f32[9]{0} add(f32[9]{0} %a, f32[9]{0} %b)
}
"""


def test_collective_bytes_parser():
    out = roofline.collective_bytes(HLO)
    assert out["all-reduce"] == 128 * 1024 * 2
    assert out["all-gather"] == 16 * 64 * 4
    assert out["reduce-scatter"] == 32 * 4 * 4
    assert out["collective-permute"] == 8 * 4
    assert out["all-to-all"] == 4 * 4 * 2
    assert out["count"] == 5
    assert out["total"] == sum(out[k] for k in
                               ("all-reduce", "all-gather", "reduce-scatter",
                                "all-to-all", "collective-permute"))


V5E = "TPU v5 lite"


def test_terms_bottleneck_identification():
    t = roofline.terms({"flops": 197e12, "bytes accessed": 1.0},
                       {"total": 0}, V5E)
    assert t["bottleneck"] == "compute"
    assert t["compute_s"] == pytest.approx(1.0)
    t = roofline.terms({"flops": 1.0, "bytes accessed": 819e9 * 2},
                       {"total": 0}, V5E)
    assert t["bottleneck"] == "memory"
    t = roofline.terms({"flops": 0.0, "bytes accessed": 0.0},
                       {"total": 50e9 * 3}, V5E)
    assert t["bottleneck"] == "collective"


def test_peaks_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.terms({"flops": 1.0}, {"total": 0}, "TPU v4")


def test_model_flops_train_vs_decode():
    cfg = get_arch("tinyllama-1.1b")
    train = ShapeConfig("t", 4096, 256, "train")
    dec = ShapeConfig("d", 32768, 128, "decode")
    ft = roofline.model_flops(cfg, train)
    fd = roofline.model_flops(cfg, dec)
    # train: 6*N*B*S; decode: 2*N*B
    assert ft / fd == pytest.approx(3 * 4096 * 256 / 128, rel=1e-6)


def test_active_params_close_to_nominal():
    # tinyllama ~1.1B
    n = roofline.active_params(get_arch("tinyllama-1.1b"))
    assert 0.9e9 < n < 1.3e9
    # deepseek-67b
    n = roofline.active_params(get_arch("deepseek-67b"))
    assert 60e9 < n < 72e9
    # granite MoE active ~400M << total
    n = roofline.active_params(get_arch("granite-moe-1b-a400m"))
    assert n < 0.8e9


def test_depth_variants_counts():
    cfg = get_arch("zamba2-1.2b")
    cfgs, counts, names = roofline.depth_variants(cfg)
    assert set(names) == {"mamba", "shared"}
    rc = roofline.real_counts(cfg)
    assert rc["mamba"] == 38 and rc["shared"] == 6
    cfg = get_arch("whisper-small")
    _, _, names = roofline.depth_variants(cfg)
    assert set(names) == {"enc", "dec"}
    rc = roofline.real_counts(cfg)
    assert rc == {"enc": 12, "dec": 12}
