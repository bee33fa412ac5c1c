"""Roofline terms from a compiled dry-run artifact (no hardware needed).

  compute_s    = HLO_FLOPs_per_device / peak_FLOPs_chip
  memory_s     = HLO_bytes_per_device / HBM_bw
  collective_s = collective_bytes_per_device / link_bw

The peaks come from `PEAKS`, keyed by the `device_kind` JAX reports for the
chip being modelled; a kind missing from the table is an error.

cost_analysis() reports the per-device (post-SPMD-partition) program, so no
further division by chip count is needed. collective_bytes is parsed from the
compiled HLO text: the summed operand sizes of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute (async *-start forms counted
once; *-done skipped).

Scan correction: XLA's cost analysis counts a while-loop body ONCE regardless
of trip count (verified empirically), and our models scan over layers. The
roofline therefore does NOT read the full compiled program's flops; instead
`estimate()` compiles 2-3 shallow *fully-unrolled* depth variants of the same
config (full width, same sharding) and solves the linear model
    cost = fixed + Σ_kind n_kind · per_layer_kind
for exact per-layer costs, then evaluates it at the real depth. The full
scanned compile remains the sharding/memory proof.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

# Per-chip peaks by `jax.Device.device_kind`. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 ICI links (50 GB/s per link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of `device_kind`; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"roofline.PEAKS with its source (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """-> {op_kind: operand_bytes_total, ..., 'total': sum, 'count': n_ops}."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    count = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z0-9-]+)(?:-start)?\(",
                      ls)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-done"):
            continue
        kind = next((k for k in _COLLECTIVES
                     if op == k or op == k + "-start"), None)
        if kind is None:
            continue
        count += 1
        shapes = _SHAPE_RE.findall(ls)
        if not shapes:
            continue
        # first shape(s) before the op name are the result; operands follow
        # inside parens. Split at the op position.
        paren = ls.index(op + "(") + len(op) + 1 if op + "(" in ls \
            else ls.index("(")
        operand_txt = ls[paren:]
        op_shapes = _SHAPE_RE.findall(operand_txt)
        use = op_shapes if op_shapes else shapes[:1]
        out[kind] += sum(_shape_bytes(d, s) for d, s in use)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["count"] = count
    return out


def terms(cost: Optional[dict], coll: Dict[str, int],
          device_kind: str) -> Dict[str, float]:
    pk = peaks(device_kind)
    flops = float((cost or {}).get("flops", 0.0))
    byts = float((cost or {}).get("bytes accessed", 0.0))
    t = {
        "flops": flops,
        "bytes": byts,
        "collective_bytes": float(coll.get("total", 0)),
        "compute_s": flops / pk["flops"],
        "memory_s": byts / pk["hbm_bw"],
        "collective_s": float(coll.get("total", 0)) / pk["link_bw"],
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: t[k])
    t["bottleneck"] = dom.replace("_s", "")
    return t


def model_flops(cfg, shape, n_clients: int = 1) -> float:
    """6·N_active·D per step (training: fwd+bwd; decode: 2·N·tokens)."""
    n = active_params(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.mode == "train"
                                   else 1)
    mult = 6.0 if shape.mode == "train" else 2.0
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mult = 2.0
    return mult * n * tokens


def active_params(cfg) -> float:
    """Parameter count with MoE counted at top-k active experts."""
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    total = 2.0 * V * d  # embed + head
    for kind in cfg.block_pattern:
        if kind == "attn":
            total += _attn_params(cfg) + _ffn_active(cfg)
        elif kind == "mamba":
            di, N, H = cfg.d_inner, cfg.ssm_state, cfg.mamba_heads
            total += d * (2 * di + 2 * N + H) + di * d
        elif kind in ("mlstm", "slstm"):
            total += xlstm_matmul_params(cfg, kind)
    if cfg.shared_attn_period:
        total += _attn_params(cfg) + _ffn_active(cfg)
    if cfg.is_encoder_decoder:
        total += cfg.num_encoder_layers * (
            4 * d * cfg.num_heads * cfg.head_dim + 2 * d * cfg.d_ff)
        total += cfg.num_layers * (4 * d * cfg.num_heads * cfg.head_dim)
    return total


def xlstm_matmul_params(cfg, kind: str) -> float:
    """Weights of one xLSTM block that multiply every token (2 FLOPs per
    token each, forward): projections, block-diagonal q/k/v at their block
    size, gate and recurrent kernels, the published sLSTM's feed-forward."""
    d, H = cfg.d_model, cfg.num_heads
    di, P = 2 * d, d // cfg.num_heads
    if cfg.xlstm_form != "published":
        if kind == "mlstm":
            return d * 2 * di + 3 * di * di + di * d
        return 4 * d * d + 4 * d * P + 3 * d * d
    if kind == "mlstm":
        # q/k/v block-diagonal in blocks of 4 (`nn.xlstm.QKV_BLOCK`)
        return d * 2 * di + 3 * di * 4 + 3 * di * 2 * H + di * d
    ffn = 3 if cfg.mlp_kind in ("swiglu", "geglu") else 2
    return 4 * d * P + 4 * d * P + ffn * d * cfg.d_ff


def _attn_params(cfg) -> float:
    d = cfg.d_model
    if cfg.is_mla:
        r_kv, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim,
                            cfg.qk_rope_dim, cfg.v_head_dim)
        H = cfg.num_heads
        q_in = cfg.q_lora_rank or d
        q = (d * cfg.q_lora_rank if cfg.q_lora_rank else 0) \
            + q_in * H * (dn + dr)
        kv = d * (r_kv + dr) + r_kv * H * (dn + dv)
        return q + kv + H * dv * d
    H, G, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return d * H * hd + 2 * d * G * hd + H * hd * d


def _ffn_active(cfg) -> float:
    d = cfg.d_model
    if cfg.num_experts:
        k = cfg.experts_per_token + cfg.num_shared_experts
        return 3.0 * d * cfg.moe_d_ff * k + d * cfg.num_experts
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    return 3.0 * d * cfg.d_ff if gated else 2.0 * d * cfg.d_ff


# ---------------------------------------------------------------------------
# scan-corrected estimation via shallow unrolled depth variants
# ---------------------------------------------------------------------------
def depth_variants(cfg) -> Tuple[List, List[Dict[str, float]], List[str]]:
    """Returns (configs, count-dicts, unknown-names). Each config is a
    shallow full-width variant; counts give the per-kind layer multiplicity
    (plus the implicit fixed term)."""
    kinds = sorted(set(cfg.block_pattern))
    mk = lambda **kw: dataclasses.replace(cfg, **kw)
    if cfg.is_encoder_decoder:
        names = ["enc", "dec"]
        att = lambda n: ("attn",) * n
        cfgs = [mk(num_encoder_layers=1, num_layers=1, block_pattern=att(1)),
                mk(num_encoder_layers=2, num_layers=1, block_pattern=att(1)),
                mk(num_encoder_layers=1, num_layers=2, block_pattern=att(2))]
        counts = [{"enc": 1, "dec": 1}, {"enc": 2, "dec": 1},
                  {"enc": 1, "dec": 2}]
        return cfgs, counts, names
    if cfg.shared_attn_period:
        # zamba2: unknowns = mamba layer, shared-attn application
        names = ["mamba", "shared"]
        cfgs = [mk(num_layers=2, block_pattern=("mamba",) * 2,
                   shared_attn_period=2),            # 2 mamba + 1 shared
                mk(num_layers=3, block_pattern=("mamba",) * 3,
                   shared_attn_period=3),            # 3 mamba + 1 shared
                mk(num_layers=2, block_pattern=("mamba",) * 2,
                   shared_attn_period=1)]            # 2 mamba + 2 shared
        counts = [{"mamba": 2, "shared": 1}, {"mamba": 3, "shared": 1},
                  {"mamba": 2, "shared": 2}]
        return cfgs, counts, names
    if len(kinds) == 1:
        k = kinds[0]
        cfgs = [mk(num_layers=1, block_pattern=(k,)),
                mk(num_layers=2, block_pattern=(k, k))]
        counts = [{k: 1}, {k: 2}]
        return cfgs, counts, [k]
    # mixed pattern (xlstm): one variant per extra kind + base
    names = kinds
    base = tuple(kinds)
    cfgs = [mk(num_layers=len(base), block_pattern=base)]
    counts = [{k: 1 for k in kinds}]
    for k in kinds:
        pat = base + (k,)
        cfgs.append(mk(num_layers=len(pat), block_pattern=pat))
        c = {kk: 1 for kk in kinds}
        c[k] += 1
        counts.append(c)
    return cfgs, counts, names


def real_counts(cfg) -> Dict[str, float]:
    if cfg.is_encoder_decoder:
        return {"enc": cfg.num_encoder_layers, "dec": cfg.num_layers}
    c: Dict[str, float] = {}
    for k in cfg.block_pattern:
        c[k] = c.get(k, 0) + 1
    if cfg.shared_attn_period:
        c["shared"] = len([i for i in range(cfg.shared_attn_period,
                                            cfg.num_layers + 1,
                                            cfg.shared_attn_period)])
    return c


def solve_linear(counts: List[Dict[str, float]], names: List[str],
                 values: List[float]) -> Dict[str, float]:
    """Least-squares solve values_i = fixed + Σ counts_i[k]·coef[k]."""
    A = np.array([[1.0] + [c.get(k, 0.0) for k in names] for c in counts])
    b = np.array(values, dtype=np.float64)
    coef, *_ = np.linalg.lstsq(A, b, rcond=None)
    out = {"fixed": float(coef[0])}
    for k, v in zip(names, coef[1:]):
        out[k] = float(v)
    return out


def evaluate_linear(coefs: Dict[str, float], counts: Dict[str, float]) -> float:
    tot = coefs.get("fixed", 0.0)
    for k, n in counts.items():
        tot += coefs.get(k, 0.0) * n
    return max(0.0, tot)


# ---------------------------------------------------------------------------
# analytic per-device memory floor (sanity bound next to the compiled
# memory_analysis, which on the CPU backend overestimates: no TPU buffer
# sharing, f32 upcasts of bf16 matmuls, no fusion)
# ---------------------------------------------------------------------------
def memory_floor_bytes(cfg, shape, n_devices: int, *, n_clients: int = 1,
                       dtype_bytes: int = 2) -> Dict[str, float]:
    P_count = active_params_total(cfg)
    out: Dict[str, float] = {}
    if shape.mode == "train":
        # params bf16 + grads bf16 + Adam m,v f32 (all sharded) per client
        per_client = P_count * (dtype_bytes * 2 + 8)
        out["states"] = n_clients * per_client / n_devices
        # one activation checkpoint per layer boundary
        tokens = shape.global_batch * shape.seq_len
        out["activations"] = (tokens * cfg.d_model * dtype_bytes
                              * cfg.num_layers) / n_devices
        out["logits"] = tokens * cfg.vocab_size * dtype_bytes / n_devices
        out["proto"] = cfg.vocab_size * (cfg.d_feature + 1) * 4 / n_devices
    else:
        out["params"] = P_count * dtype_bytes / n_devices
        if shape.mode == "decode":
            out["cache"] = _cache_bytes(cfg, shape, dtype_bytes) / n_devices
        else:
            tokens = shape.global_batch * shape.seq_len
            out["activations"] = (tokens * cfg.d_model * dtype_bytes * 2
                                  ) / n_devices
            out["cache"] = _cache_bytes(cfg, shape, dtype_bytes) / n_devices
    out["total"] = sum(out.values())
    return out


def active_params_total(cfg) -> float:
    """Total resident parameters (MoE counts ALL experts, not just top-k)."""
    n = active_params(cfg)
    if cfg.num_experts:
        d = cfg.d_model
        per_layer_extra = 3.0 * d * cfg.moe_d_ff * (
            cfg.num_experts - cfg.experts_per_token)
        n += per_layer_extra * sum(1 for k in cfg.block_pattern if k == "attn")
    return n


def _cache_bytes(cfg, shape, dtype_bytes: int) -> float:
    B = shape.global_batch
    S = shape.seq_len
    if getattr(cfg, "long_context_mode", "") == "swa" and S >= 1 << 19:
        S = cfg.swa_window
    total = 0.0
    for kind in cfg.block_pattern:
        if kind == "attn":
            if cfg.is_mla:
                total += B * S * (cfg.kv_lora_rank + cfg.qk_rope_dim)
            else:
                total += 2 * B * S * cfg.num_kv_heads * cfg.head_dim
        elif kind == "mamba":
            total += B * cfg.mamba_heads * cfg.mamba_head_dim * cfg.ssm_state * 2
        elif kind in ("mlstm", "slstm"):
            total += B * cfg.d_model * 8
    if cfg.shared_attn_period:
        n_sh = len(range(cfg.shared_attn_period, cfg.num_layers + 1,
                         cfg.shared_attn_period))
        total += n_sh * 2 * B * shape.seq_len * cfg.num_kv_heads * cfg.head_dim
    if cfg.is_encoder_decoder:
        total += 2 * B * cfg.encoder_seq * cfg.num_kv_heads * cfg.head_dim \
            * cfg.num_layers
    return total * dtype_bytes
