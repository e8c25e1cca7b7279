"""jamba2-3b [hybrid] — 28L d_model=2560 20H (MQA kv=1) d_ff=8192
vocab=65536 (tied), Mamba-1 + attention 13:1, dense SwiGLU everywhere.
[arXiv:2403.19887; huggingface.co/ai21labs/AI21-Jamba2-3B]

Layer i attends iff i % 14 == 7 (`attn_layer_period` 14,
`attn_layer_offset` 7): attention at layers 7 and 21, with no positional
encoding, and a Mamba-1 mixer (d_state 16, d_conv 4, expand 2, dt_rank
160, conv bias, RMSNorms on dt / B / C) in the other 26. `num_experts` 1:
every layer's feed-forward is one dense MLP."""
from repro.configs.base import MambaConfig, ModelConfig

FULL = ModelConfig(
    name="jamba2-3b", family="hybrid", n_layers=28, d_model=2560,
    n_heads=20, n_kv_heads=1, d_head=128, d_ff=8192, vocab=65536,
    attn_every=14, attn_offset=7, rope=False, tie_embeddings=True,
    norm_eps=1e-6,
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2, dt_rank=160))

# two blocks (the unrolled restack FULL takes) of a period of two whose
# attention layer sits at offset 1, not 0; one KV head
SMOKE = ModelConfig(
    name="jamba2-3b-smoke", family="hybrid", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=1, d_head=16, d_ff=128, vocab=512,
    attn_every=2, attn_offset=1, rope=False, tie_embeddings=True,
    norm_eps=1e-6,
    mamba=MambaConfig(d_state=8, d_conv=4, expand=2, dt_rank=8, chunk=16),
    dtype="float32", remat=False)

SHARDING_OVERRIDES = {}
