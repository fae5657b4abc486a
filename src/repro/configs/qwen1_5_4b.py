"""qwen1.5-4b [dense] — QKV bias [hf:Qwen/Qwen1.5-4B].

40L d_model=2560 20H (GQA kv=20 = MHA) dh=128 d_ff=6912 vocab=151936,
LM head untied from the embeddings.

Tensor parallel over four chips (a 1x4 mesh, as the benchmark's
``qwen4b-spike-tp4-decode`` cell serves it): each chip holds 5 heads,
1728 MLP columns, a quarter of the vocabulary (37984 rows) and a quarter
of the KV page pool; the attention partials and MLP outputs cross the
chips through the coded boundaries of ``core.boundary``.
"""
from . import register
from .base import ModelConfig


@register
def config() -> ModelConfig:
    return ModelConfig(
        name="qwen1.5-4b",
        family="dense",
        n_layers=40,
        d_model=2560,
        n_heads=20,
        n_kv_heads=20,
        d_head=128,
        d_ff=6912,
        vocab=151936,
        pattern=("attn",),
        qkv_bias=True,
    )
