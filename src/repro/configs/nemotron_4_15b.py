"""nemotron-4-15b [dense] — 32L, d_model=6144, 48H (GQA kv=8), d_ff=24576,
vocab=256000.  Squared-ReLU MLP, LayerNorm, rotary over half of each head.
[arXiv:2402.16819]  The paper does not state the rotary fraction; the
family's released configs do (NeMo ``rotary_percentage: 0.5``, Hugging
Face ``NemotronConfig.partial_rotary_factor: 0.5``)."""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="nemotron-4-15b",
    family="dense",
    n_layers=32,
    d_model=6144,
    n_heads=48,
    n_kv_heads=8,
    d_ff=24576,
    vocab=256000,
    norm_type="layernorm",
    mlp_type="relu2",
    rotary_pct=0.5,
    tie_embeddings=False,
    remat="full",
    fsdp=True,
)
