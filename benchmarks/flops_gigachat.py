"""What a GigaChat3 (``deepseek_v3``) configuration needs, from shapes:
the bytes of a token's latent row and the work of the latent decode
attend, of the prefill attention and of the held experts. The same work
whatever computes it (a Pallas walk over pages, a gathered dense attend,
an absorbed or an up-projected form); nothing here is taken from the
program, and the counts come from the REQUESTS (which slots delivered a
token at what length), not from a kernel's shapes.
"""

from __future__ import annotations


def latent_row_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """One token's row in ONE layer: ``[c_kv | k_rope]``."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * bytes_per_value


def kv_bytes_token(c: dict, bytes_per_value: int = 2) -> int:
    """What a token keeps in the cache over all the layers."""
    return c["num_hidden_layers"] * latent_row_bytes(c, bytes_per_value)


def latent_attend_work(c: dict, live_rows: int,
                       bytes_per_value: int = 2) -> tuple:
    """(operations, bytes) of ONE layer's absorbed decode attends over
    ``live_rows`` cached rows in all (summed over the slots that
    delivered a token and the steps): every head meets each row twice,
    the scores over the row's whole width and ``P @ V`` over its latent
    part, 2 ops a multiply-add; each row is read once for all heads."""
    per_row = c["num_attention_heads"] * 2 * (
        (c["kv_lora_rank"] + c["qk_rope_head_dim"]) + c["kv_lora_rank"])
    return live_rows * per_row, live_rows * latent_row_bytes(
        c, bytes_per_value)


def prefill_attention_flops(c: dict, rows: int, history: int) -> float:
    """ONE layer's causal attention of ``rows`` new tokens behind
    ``history`` cached ones, K and V formed for every head: row r meets
    ``history + r + 1`` keys at width ``nope + rope`` and as many values
    at width ``v``, 2 ops a multiply-add."""
    pairs = rows * history + rows * (rows + 1) / 2
    return c["num_attention_heads"] * 2 * pairs * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def expert_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """One expert's ``W1``, ``W3`` [d, f] and ``W2`` [f, d]."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * bytes_per_value


def pair_flops(c: dict) -> int:
    """One token through one expert: three matmuls, 2 ops a
    multiply-add."""
    return 3 * 2 * c["hidden_size"] * c["moe_intermediate_size"]


def held_experts_work(c: dict, experts_live: int, pairs_held: int,
                      bytes_per_value: int = 2) -> tuple:
    """(operations, bytes) the held experts' grouped matmuls have to do
    for ``pairs_held`` routed pairs that hit ``experts_live`` distinct
    HELD experts (summed over layers and steps as the caller counted
    them): each live expert's weights read once, each pair computed
    once. The activations' bytes are left out: the count is a floor."""
    return (pairs_held * pair_flops(c),
            experts_live * expert_bytes(c, bytes_per_value))


def n_moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]
