"""The repo's compact 12-block xLSTM variant at 125M widths (d_model 768, 4
heads, 1 sLSTM per 6 blocks, dense q/k/v, RMSNorm, an in-block GLU after
the sLSTM; 193M parameters): after arXiv:2405.04517 but not the paper's
model, which is `xlstm_7x1_125m`. d_ff=0 (blocks carry their own
projections). Attention-free: long_500k runs natively (O(1) recurrent
state)."""
from repro.types import ModelConfig

_PATTERN = tuple("slstm" if i % 6 == 3 else "mlstm" for i in range(12))

CONFIG = ModelConfig(
    name="xlstm-125m",
    family="ssm",
    num_layers=12,
    d_model=768,
    num_heads=4,
    num_kv_heads=4,
    d_ff=0,
    vocab_size=50304,
    block_pattern=_PATTERN,
    ssm_conv=4,
    ssm_chunk=256,
    rope_kind="none",
    long_context_mode="native",
)
