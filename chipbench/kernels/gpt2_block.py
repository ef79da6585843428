"""Operations a GPT-2 block model requires, from its shapes alone."""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that sit in a matrix multiplication: per layer qkv
    (3 d^2), attention out (d^2) and the MLP of ratio 4 (8 d^2), plus the
    untied head (d V). The embedding is a look-up and counts nothing."""
    d = model["d_model"]
    return model["num_layers"] * 12 * d * d + d * model["vocab_size"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward operations one trained token requires: 6 per
    matmul parameter, and causal attention's two products (QK^T and PV,
    2 d flops per key each, over the seq_len / 2 keys an average query
    sees), three times over for the backward pass. Recomputed work is
    not counted."""
    attention = model["num_layers"] * 3 * 2 * 2 * model["d_model"] * (
        seq_len / 2)
    return 6.0 * matmul_params(model) + attention
