"""What the SDAR-MoE expert layer and a block's attend need, from
shapes: the bytes of an expert's three matrices, the operations of one
(position, expert) pair, the bytes of a page and of a block's own rows.
The same work whatever computes it (a grouped-matmul kernel, XLA's
``ragged_dot``, a dense loop; a walk over pages, a gather); nothing here
is taken from the program.
"""

from __future__ import annotations


def expert_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """One expert's ``W_gate``, ``W_up`` [d, f] and ``W_down`` [f, d]."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * bytes_per_value


def pair_flops(c: dict) -> int:
    """One position through one expert: three matmuls, 2 ops a
    multiply-add."""
    return 3 * 2 * c["hidden_size"] * c["moe_intermediate_size"]


def moe_work(c: dict, experts_live: int, pairs: int,
             bytes_per_value: int = 2) -> tuple:
    """(operations, bytes) the expert layers have to do for ``pairs``
    routed (position, expert) pairs that hit ``experts_live`` distinct
    experts (summed over layers and forwards as the caller counted
    them): each live expert's weights read once a forward, each pair
    computed once; the roofline takes the larger of the two times. The
    activations' bytes (a few KB a pair) are left out: the count is a
    floor."""
    return (pairs * pair_flops(c),
            experts_live * expert_bytes(c, bytes_per_value))


def page_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """K and V of one page of one layer: every K/V head, ``page_tokens``
    tokens."""
    return (2 * c["num_key_value_heads"] * c["head_dim"]
            * c["serve"]["page_tokens"] * bytes_per_value)


def block_attend_work(c: dict, pages_walked: int, live_block_forwards: int,
                      bytes_per_value: int = 2) -> tuple:
    """(operations, bytes) of the attends of ONE layer: the live slots'
    pages read once a forward (``pages_walked``: summed over slots and
    forwards) and, a forward of a live block, the block's own K/V rows.
    The operations: every one of a block's ``W x query heads`` rows
    against every fetched key and value, 2 ops a multiply-add."""
    W, D = c["generation"]["block_length"], c["head_dim"]
    keys = pages_walked * c["serve"]["page_tokens"] + live_block_forwards * W
    return (2 * 2 * W * c["num_attention_heads"] * D * keys,
            pages_walked * page_bytes(c, bytes_per_value)
            + live_block_forwards * W * 2 * c["num_key_value_heads"] * D
            * bytes_per_value)
