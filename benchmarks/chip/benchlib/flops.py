"""Operations and bytes that a call needs, from its shapes alone.

These are what the algorithm requires, not what the program happens to
compute: a roofline share or an MFU divides the least time these allow
by the time measured, so work the program wastes shows as a lower share.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable


def gemm_block(bs: int, itemsize: int = 4) -> tuple:
    """C += A @ B on square blocks: (FLOPs, bytes read and written)."""
    return 2.0 * bs ** 3, 4.0 * bs * bs * itemsize


def flash_fwd(batch: int, seq: int, heads: int, kv_heads: int,
              head_dim: int, causal: bool = True, itemsize: int = 2) -> tuple:
    """Attention forward over [batch, seq]: scores and the weighted sum
    for each (query, key) pair the mask keeps; q, k, v read, o written."""
    pairs = seq * (seq + 1) / 2 if causal else float(seq * seq)
    flops = 4.0 * batch * heads * head_dim * pairs
    nbytes = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return flops, nbytes


@dataclass(frozen=True)
class Decoder:
    """The shapes of a dense decoder-only transformer."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: dict) -> "Decoder":
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"], hd,
                   c["intermediate_size"], c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff

    @property
    def matmul_params(self) -> int:
        """Weights each token multiplies by: the layers and the output head."""
        return self.layers * self.layer_matmul_params + self.vocab * self.d_model

    def token_flops(self, context: int) -> float:
        """Forward FLOPs of one token that attends to `context` positions."""
        attn = 4.0 * self.layers * self.heads * self.head_dim * context
        return 2.0 * self.matmul_params + attn

    def decode_step_flops(self, contexts: Iterable[int]) -> float:
        """One batched decode step: one token per slot at its context."""
        return sum(self.token_flops(c) for c in contexts)

    def train_step_flops(self, batch: int, seq: int) -> float:
        """Forward and backward (twice the forward) of batch x seq tokens
        under a causal mask; recomputation is not counted."""
        per_seq = (2.0 * self.matmul_params * seq + 4.0 * self.layers
                   * self.heads * self.head_dim * seq * (seq + 1) / 2)
        return 3.0 * batch * per_seq
