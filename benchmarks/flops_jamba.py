"""What a Jamba configuration needs, from shapes: parameters, the bytes
of one slot's Mamba state, and the work of the selective scan in a
decode step and in a prefill. The same work whatever computes it (a
Pallas call, ``lax.scan``, an associative scan); nothing here is taken
from the program.

The scan has no matrix product: per (token, channel, state number) it
is one ``exp`` and seven elementwise operations (``dt * A``, ``dA * h``,
``dt u * B``, the add, ``h * C``, the sum over the state, and a share
of ``D u`` and the gate). ``peaks.json`` holds an HBM and an MXU peak
and no vector peak, so the roofline shares below are against HBM bytes;
the operation counts are printed by the entry and set against the
chip's vector rate by arithmetic in PERF.md.
"""

from __future__ import annotations


def channels(c: dict) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def n_mamba_layers(c: dict) -> int:
    return sum(i % c["attn_layer_period"] != c["attn_layer_offset"]
               for i in range(c["num_hidden_layers"]))


def n_params(c: dict) -> int:
    """Every parameter of the published model, the tied embedding once."""
    d, ff, ch = c["hidden_size"], c["intermediate_size"], channels(c)
    n, r, k = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    head = d // c["num_attention_heads"]
    mlp = 3 * d * ff + 2 * d                     # and the layer's two norms
    mamba = (d * 2 * ch + ch * k + ch + ch * (r + 2 * n) + r * ch + ch
             + ch * n + ch + (r + 2 * n) + ch * d)
    attn = 2 * d * d + 2 * d * c["num_key_value_heads"] * head
    m = n_mamba_layers(c)
    return (m * (mamba + mlp) + (c["num_hidden_layers"] - m) * (attn + mlp)
            + c["vocab_size"] * d + d)


def state_bytes_layer(c: dict, window_bytes: int = 2) -> tuple:
    """(the scan's state [C, N] in float32, the conv's window of
    ``d_conv - 1`` inputs in ``window_bytes`` a value), one slot, one
    Mamba layer."""
    ch = channels(c)
    return (ch * c["mamba_d_state"] * 4,
            (c["mamba_d_conv"] - 1) * ch * window_bytes)


def state_bytes_slot(c: dict) -> int:
    return n_mamba_layers(c) * sum(state_bytes_layer(c))


def ssm_update_work(c: dict, slots: int) -> dict:
    """One Mamba layer's decode update over ``slots`` slots: every
    slot's scan state read and written once (the floor whatever
    computes it; the window, 5% of it, is moved outside the timed call
    by XLA's fusions and is not counted), and the elementwise work."""
    cells = slots * channels(c) * c["mamba_d_state"]
    return {"bytes": 2 * slots * state_bytes_layer(c)[0],
            "vector_ops": 7 * cells, "exps": cells}


def ssm_scan_work(c: dict, tokens: int, snapshots: int) -> dict:
    """One Mamba layer's scan over a bucket of ``tokens``: ``u``, ``z``
    (2 bytes a channel), ``dt`` (4) and ``B``, ``C`` in, ``y`` (2) out,
    the state before in, ``snapshots`` states and the end state out."""
    ch, n = channels(c), c["mamba_d_state"]
    state = state_bytes_layer(c)[0]
    cells = tokens * ch * n
    return {"bytes": tokens * (ch * (2 + 2 + 4 + 2) + 2 * n * 4)
            + (2 + snapshots) * state,
            "vector_ops": 7 * cells, "exps": cells}
