"""olmo-1b — 16L d_model=2048, MHA with 16 heads of 128, gated MLP of 8192
per branch, vocab 50304 (50280 padded), non-parametric LayerNorm, RoPE
theta 1e4, no biases, tied embeddings. [arXiv:2402.00838 Table 1; hf
allenai/OLMo-1B config.json]

Departure from the published model: the gate is ReLU in place of SwiGLU's
SiLU, the paper's recipe that makes the FFN sparse."""
from repro.config import ModelConfig, SparsityConfig

CONFIG = ModelConfig(
    name="olmo-1b",
    family="dense",
    num_layers=16,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=8192,                      # per branch: mlp_ratio 8 = 2 x 8192
    vocab_size=50304,
    norm="nonparametric_ln",
    tied_embeddings=True,
    gated=True,
    rope_theta=1e4,
    sparsity=SparsityConfig(enabled=True, l1_coeff=2e-5, activation="relu"),
    source="arXiv:2402.00838; hf",
)
