"""stablelm-1.6b [dense]: StableLM 2 1.6B, 24L d_model=2048 32H (MHA, kv=32,
head 64) d_ff=5632 vocab=100352 — LayerNorm with bias, q/k/v bias, rotary
over the first quarter of each head, untied unembedding.
[hf:stabilityai/stablelm-2-1_6b config.json, StableLmForCausalLM;
https://huggingface.co/stabilityai/stablelm-2-1_6b/blob/main/config.json]"""
from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="stablelm-1.6b", family="dense",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=32,
    d_ff=5632, vocab=100352,
    layernorm=True, rotary_fraction=0.25, qkv_bias=True,
    rope_theta=10_000.0, norm_eps=1e-5,
    tie_embeddings=False,
    source="https://huggingface.co/stabilityai/stablelm-2-1_6b/blob/main/"
           "config.json",
)
