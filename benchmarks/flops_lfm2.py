"""What the LFM2-MoE expert layer needs, from shapes: the bytes of an
expert's three matrices and the operations of one (token, expert) pair.
The same work whatever computes it (a grouped-matmul kernel, XLA's
``ragged_dot``, a dense loop); nothing here is taken from the program.
"""

from __future__ import annotations


def expert_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """One expert's ``W1``, ``W3`` [d, f] and ``W2`` [f, d]."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * bytes_per_value


def pair_flops(c: dict) -> int:
    """One token through one expert: three matmuls, 2 ops a
    multiply-add."""
    return 3 * 2 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_work(c: dict, experts_live: int, pairs: int,
             bytes_per_value: int = 2) -> tuple:
    """(operations, bytes) an expert layer's kernel has to do for
    ``pairs`` routed (token, expert) pairs that hit ``experts_live``
    distinct experts (summed over layers and steps as the caller
    counted them): each live expert's weights read once, each pair
    computed once. The activations' bytes (a few KB a pair) are left
    out: the count is a floor."""
    return (pairs * pair_flops(c),
            experts_live * expert_bytes(c, bytes_per_value))


def n_moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]
