"""What a Nemotron-H (``nemotron_h``) configuration needs, from shapes:
the bytes of one slot's Mamba-2 state, the work of the state-space
recurrence in a decode step and in a chunked prefill, and the work of the
latent experts. The same work whatever computes it (a Pallas call, plain
JAX); nothing here is taken from the program, and the counts of slots,
experts and pairs come from the REQUESTS (which slots could deliver a
token, which experts they chose), not from a kernel's shapes.
"""

from __future__ import annotations


def mamba_dims(c: dict) -> tuple:
    """(H heads, P values a head, N state numbers a value, G groups)."""
    return (c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
            c["n_groups"])


def n_layers(c: dict, kind: str) -> int:
    """Layers of one pattern letter (``M``, ``*``, ``E``)."""
    return c["hybrid_override_pattern"].count(kind)


def state_bytes_layer(c: dict, window_bytes: int = 2) -> tuple:
    """(the recurrence's state [H, P, N] in float32, the conv's window of
    ``conv_kernel - 1`` inputs over x, B and C in ``window_bytes`` a
    value), one slot, one Mamba-2 layer."""
    H, P, N, G = mamba_dims(c)
    return (H * P * N * 4,
            (c["conv_kernel"] - 1) * (H * P + 2 * G * N) * window_bytes)


def state_bytes_slot(c: dict) -> int:
    return n_layers(c, "M") * sum(state_bytes_layer(c))


def ssd_update_work(c: dict, slots: int) -> dict:
    """One Mamba-2 layer's decode update over ``slots`` slots: each
    slot's state read and written once (the floor whatever computes it;
    the window, 1.5% of it, is moved outside the timed call by XLA's
    fusions and is not counted), and the elementwise work a state number
    (the decay's product, the outer product's, the add, the read-out's
    multiply-add)."""
    H, P, N, _ = mamba_dims(c)
    cells = slots * H * P * N
    return {"bytes": 2 * slots * state_bytes_layer(c)[0],
            "vector_ops": 5 * cells}


def ssd_scan_work(c: dict, tokens: int, snapshots: int) -> dict:
    """One Mamba-2 layer's chunked scan over a bucket of ``tokens``
    (whole chunks of ``chunk_size``): a chunk's products, 2 ops a
    multiply-add (``C B^T`` once a group; a head's masked scores times
    its ``dt x``, ``C`` times its state, and the state's update), and
    the bytes in (``x``, ``B``, ``C`` 2 a value, ``dt`` 4, the state
    before) and out (``y`` 4 a value, ``snapshots`` states and the end
    state)."""
    H, P, N, G = mamba_dims(c)
    Q = c["chunk_size"]
    chunks = -(-tokens // Q)
    state = state_bytes_layer(c)[0]
    return {"ops": chunks * (G * 2 * Q * Q * N
                             + H * (2 * Q * Q * P + 4 * Q * N * P)),
            "bytes": chunks * Q * (H * P * (2 + 4) + 2 * G * N * 2 + H * 4)
            + (2 + snapshots) * state}


def expert_bytes(c: dict, bytes_per_value: int = 2) -> int:
    """One expert's ``W1`` [latent, f] and ``W2`` [f, latent]."""
    return (2 * c["moe_latent_size"] * c["moe_intermediate_size"]
            * bytes_per_value)


def pair_flops(c: dict) -> int:
    """One token's latent row through one expert: two matmuls, 2 ops a
    multiply-add."""
    return 2 * 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def latent_experts_work(c: dict, experts_live: int, pairs_held: int,
                        bytes_per_value: int = 2) -> tuple:
    """(operations, bytes) the held experts' grouped matmuls have to do
    for ``pairs_held`` routed pairs that hit ``experts_live`` distinct
    HELD experts (summed over layers and steps as the caller counted
    them): each live expert's two matrices read once, each pair computed
    once. The activations' bytes are left out: the count is a floor."""
    return (pairs_held * pair_flops(c),
            experts_live * expert_bytes(c, bytes_per_value))
