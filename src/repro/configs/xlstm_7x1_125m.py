"""xLSTM[7:1] 125M [arXiv:2405.04517], the paper's published blocks: 24
residual pre-LayerNorm blocks at d_model 768 with 4 heads, one sLSTM in
every 8 (at blocks 3, 11 and 19: the ratio is published, the positions
are assumed) and mLSTM elsewhere. mLSTM: up-projection by 2, causal conv
of 4, block-diagonal q/k/v in blocks of 4, gates from concat(q, k, v), a
learnable skip and a head-wise norm. sLSTM: causal conv of 4 into the i
and f gates, head-wise gate projections, then a gated GeLU feed-forward
up to 1024 (factor 1.3, rounded up to 64) behind its own pre-norm
residual. Untied LM head over a 50304-token vocabulary; 163,633,320
parameters (the paper: about 163.7M)."""
from repro.types import ModelConfig

_PATTERN = tuple("slstm" if i % 8 == 3 else "mlstm" for i in range(24))

CONFIG = ModelConfig(
    name="xlstm-7x1-125m",
    family="ssm",
    num_layers=24,
    d_model=768,
    num_heads=4,
    num_kv_heads=4,
    d_ff=1024,
    vocab_size=50304,
    block_pattern=_PATTERN,
    ssm_conv=4,
    ssm_chunk=256,
    norm_kind="layernorm_nobias",
    mlp_kind="geglu",
    xlstm_form="published",
    rope_kind="none",
    long_context_mode="native",
)
