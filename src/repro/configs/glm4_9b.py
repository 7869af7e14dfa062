"""glm4-9b [dense]: RoPE + GQA with only 2 KV heads and QKV bias
(hf:THUDM/glm-4-9b). kv=2 cannot shard 16-way -> the divisibility fallback
replicates KV projections and the KV cache shards its *sequence* dim
instead. The source rotates half of each head's dims in interleaved
pairs; this config keeps the repo's rotate-half RoPE over the whole head
(same shapes and work)."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="glm4-9b",
    family="dense",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=2,
    d_ff=13696,
    vocab_size=151552,
    head_dim=128,
    qkv_bias=True,
    act="swiglu",
    grad_accum=4,
)
