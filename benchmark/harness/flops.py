"""Operations and bytes a training step of the decoder needs, from its sizes.

A function of the configuration's sizes and the mix's shapes, never of the
implementation: a product of an (m, k) by a (k, n) matrix is 2 m k n
operations, the backward pass twice the forward, attention is counted over
the causal half of the score matrix only, and nothing that is recomputed
(``--remat``, the flash backward, the streamed head) is counted twice.
"""

from __future__ import annotations


def matmul_params(sizes: dict) -> int:
    """Parameters that multiply every token: q, k, v, output projection and
    the two MLP matrices of each block, and the output head. The token and
    position tables are looked up, not multiplied."""
    d, ffn = sizes["d_model"], sizes["ffn_dim"]
    per_block = 4 * d * d + 2 * d * ffn
    return sizes["num_blocks"] * per_block + d * sizes["vocab_size"]


def total_params(sizes: dict) -> int:
    d, ffn, vocab = sizes["d_model"], sizes["ffn_dim"], sizes["vocab_size"]
    per_block = 4 * d * d + 2 * d * ffn + ffn + d + 4 * d
    return (sizes["num_blocks"] * per_block + vocab * d
            + sizes["seq_len"] * d + 2 * d + d * vocab + vocab)


def attention_flops_per_token(sizes: dict, seq_len: int) -> float:
    """Forward and backward of QK^T and PV over the causal half: a token
    attends to S/2 keys on average, 2 products of 2·d operations each,
    times three for forward plus backward: 6·L·d·S."""
    return 6.0 * sizes["num_blocks"] * sizes["d_model"] * seq_len


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """6 operations per matmul parameter and token (2 forward, 4 backward)
    plus causal attention."""
    return 6.0 * matmul_params(sizes) + attention_flops_per_token(sizes, seq_len)


def matmul_flops_per_token(sizes: dict) -> float:
    """The part of ``train_flops_per_token`` that XLA runs as plain matrix
    products of activations with weights."""
    return 6.0 * matmul_params(sizes)


def adam_bytes_per_step(sizes: dict) -> int:
    """f32 master, gradient, m and v read, master, m and v written."""
    return 7 * 4 * total_params(sizes)


def state_bytes(sizes: dict) -> int:
    """f32 master, m and v resident between steps."""
    return 3 * 4 * total_params(sizes)


def allreduce_bytes_per_step(sizes: dict) -> int:
    """f32 gradients of every parameter."""
    return 4 * total_params(sizes)
