"""Operations and bytes a model step needs, computed from shapes.

Counts are of the work the model requires, not of what an implementation
happens to run: a multiply-add is 2 FLOPs; causal attention over ``c``
keys costs ``4 * layers * q_heads * head_dim * c`` per query (QK^T and PV);
masked-out keys (the future, other packed documents, padding) cost nothing;
the LM head is counted only for rows whose logits are used. Bytes are the
weights read once per step plus the valid KV rows read and the new rows
written.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp_mats: int            # 3 for a gated MLP (SwiGLU), 2 for GELU
    elt_bytes: int           # bytes per weight / KV element

    @property
    def block_params(self) -> int:
        """Matmul weights of all blocks (norm weights are negligible and
        not matmuls)."""
        d, hd = self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return self.layers * (attn + self.mlp_mats * d * self.d_ff)

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.elt_bytes

    @property
    def weight_bytes(self) -> int:
        """Weights one forward step reads: every block matrix and the head."""
        return (self.block_params + self.head_params) * self.elt_bytes

    def attn_flops(self, keys: float) -> float:
        """Attention FLOPs of queries that attend ``keys`` keys in total."""
        return 4.0 * self.layers * self.heads * self.head_dim * keys


def causal_keys(start: int, n: int) -> int:
    """Keys attended by rows ``start .. start+n-1`` of one sequence, each
    seeing itself and everything before it."""
    return n * start + n * (n + 1) // 2


def prefill_flops(D: Dims, start: int, n: int, head_rows: int) -> float:
    return (2.0 * D.block_params * n + D.attn_flops(causal_keys(start, n))
            + 2.0 * D.head_params * head_rows)


def prefill_bytes(D: Dims, chunks: list[tuple[int, int]]) -> float:
    """One prefill call over ``chunks`` = [(start, length), ...]: weights
    once, each sequence's KV prefix read, the chunk rows written."""
    kv = sum((s + n) + n for s, n in chunks)
    return float(D.weight_bytes + kv * D.kv_bytes_per_token)


def decode_flops(D: Dims, lanes: int, keys: int) -> float:
    """One decode step of ``lanes`` lanes attending ``keys`` keys in total
    (each lane's new row included)."""
    return 2.0 * (D.block_params + D.head_params) * lanes + D.attn_flops(keys)


def decode_bytes(D: Dims, lanes: int, keys: int) -> float:
    return float(D.weight_bytes + (keys + lanes) * D.kv_bytes_per_token)


def train_flops(D: Dims, n_tokens: int, seg_lengths: list[int]) -> float:
    """Forward and backward (3x forward) of one step; recomputation is not
    counted. Attention is causal within each packed document."""
    keys = sum(n * (n + 1) // 2 for n in seg_lengths)
    fwd = 2.0 * (D.block_params + D.head_params) * n_tokens + D.attn_flops(keys)
    return 3.0 * fwd


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
