"""Work arithmetic: the operations and bytes a step needs, and the peaks.

Everything here is computed from a configuration's published shapes and
from counts the harness records (tokens, pages in use), never from the
program's own bookkeeping, so a change to the program cannot change what
its work is said to be.  The peaks come from ``peaks.json`` alone, keyed
by the ``device_kind`` JAX reports.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes of a dense decoder with multi-head or grouped attention."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tie: bool

    @classmethod
    def of(cls, config: dict) -> "Dims":
        """Read the published sizes (Hugging Face ``config.json`` keys)."""
        heads = config["num_attention_heads"]
        return cls(layers=config["num_hidden_layers"],
                   d_model=config["hidden_size"],
                   heads=heads,
                   kv_heads=config["num_key_value_heads"],
                   head_dim=config["hidden_size"] // heads,
                   d_ff=config["intermediate_size"],
                   vocab=config["vocab_size"],
                   tie=bool(config["tie_word_embeddings"]))

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one position over every layer."""
        return self.layers * 2 * self.kv_heads * self.head_dim * 2


def matmul_params_per_token(d: Dims) -> int:
    """Weights one decode token multiplies by: every projection of every
    layer and the LM head (the embedding is a lookup, not a product)."""
    attn = (d.d_model * d.heads * d.head_dim
            + 2 * d.d_model * d.kv_heads * d.head_dim
            + d.heads * d.head_dim * d.d_model)
    mlp = 3 * d.d_model * d.d_ff
    return d.layers * (attn + mlp) + d.d_model * d.vocab


def attention_flops(d: Dims, context_tokens: int) -> int:
    """Scores and weighted values of one query over ``context_tokens``
    cached positions summed over queries (2 FLOPs per multiply-add, QK^T
    and PV), every layer."""
    return 4 * d.layers * d.heads * d.head_dim * context_tokens


def decode_flops(d: Dims, tokens: int, context_tokens: int) -> int:
    """Model FLOPs of ``tokens`` decode tokens whose contexts sum to
    ``context_tokens``: 2 x the weights used per token, plus attention."""
    return 2 * matmul_params_per_token(d) * tokens + attention_flops(
        d, context_tokens)


def paged_attn_bytes(d: Dims, pages: int, page_size: int, slots: int,
                     chips: int = 1) -> int:
    """HBM bytes one decode step's paged attention needs, over all chips
    and layers: the bf16 K and V of every mapped page, plus, on each chip,
    the bf16 queries of every slot and head and the partial it writes
    (int8 per element, an f32 scale and an f32 log-sum-exp per head)."""
    kv = pages * page_size * d.kv_bytes_per_token
    per_chip_io = d.layers * slots * d.heads * (d.head_dim * 3 + 8)
    return kv + chips * per_chip_io


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def peak_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of one chip kind; a kind missing from the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]
