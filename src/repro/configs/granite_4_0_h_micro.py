"""granite-4.0-h-micro [hybrid]: 40L d_model=2048, 36 Mamba-2 layers
(64 heads x 64, d_state=128, one group, conv 4, expand 2) and 4 GQA
attention layers (32H, kv=8, d_head 64, no positional encoding) at
indices 5, 15, 25, 35; a SwiGLU MLP (8192) after every layer; vocab
100352, tied embeddings.  [hf: ibm-granite/granite-4.0-h-micro
config.json]

The published scalars replace the plain transformer's: embeddings times
12, softmax scale 1/64, every residual branch times 0.22, logits divided
by 8, RMSNorm eps 1e-5 (also the gated norm's inside the Mamba layers).
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_head=64,
    d_ff=8192,
    vocab_size=100_352,
    use_rope=False,
    # 256 keys a step: a warm-up prefill of 8 x 4224 tokens keeps its
    # attention logits to 1.1 GB beside the 6.4 GB of weights.
    attn_kv_chunk=256,
    ssm_state=128,
    ssm_expand=2,
    ssm_conv=4,
    ssm_head_dim=64,
    ssm_chunk=256,
    attn_period=10,
    attn_index=5,
    ssm_mlp=True,
    tie_embeddings=True,
    # Drawn at 0.02, the tied embedding times 12 would put each token's
    # own logit about ten spreads above the rest, so random weights would
    # echo their input whatever the context; at 0.002 it sits inside the
    # spread and the argmax follows the recurrent state and the KV.
    embed_init_std=0.002,
    rms_norm_eps=1e-5,
    embedding_multiplier=12.0,
    attention_multiplier=1.0 / 64,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    source="https://huggingface.co/ibm-granite/granite-4.0-h-micro",
)
