"""What an algorithm needs: operations and bytes from shapes, and the
chip's published peaks. The yardstick for every roofline share and MFU
the benchmark prints; nothing here is taken from the program.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmarks/peaks.json (has {sorted(table)})")
    return table[device_kind]


def gpt2_matmul_params(c: dict) -> int:
    """Parameters that a token is multiplied by: the four block matrices
    of every layer and the tied unembedding. Positions, LayerNorms and
    biases are adds; the embedding lookup is a gather."""
    d, ff = c["n_embd"], c["n_inner"]
    return c["n_layer"] * (3 * d * d + d * d + 2 * d * ff) \
        + c["vocab_size"] * d


def attention_flops(seq: int, d_model: int, n_layer: int,
                    causal: bool = True, backward: bool = False) -> float:
    """Attention's own operations for ONE sequence of ``seq`` tokens over
    all layers: QK^T and PV, 2 ops a multiply-add, half of the square
    when causal. Backward costs twice the forward (dQ, dK, dV, dP)."""
    fwd = n_layer * 2 * 2 * seq * seq * d_model * (0.5 if causal else 1.0)
    return fwd * (3.0 if backward else 1.0)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward operations a token of a ``seq``-long sequence
    requires: 6 per matmul parameter plus attention. Recomputation
    (remat) is NOT counted — it is the program's choice, not the
    algorithm's need."""
    return 6.0 * gpt2_matmul_params(c) + attention_flops(
        seq, c["n_embd"], c["n_layer"], backward=True) / seq


def decode_attend_bytes(live_lens, block_tokens: int, n_kv_heads: int,
                        head_dim: int, n_layer: int,
                        bytes_per_value: int = 2) -> int:
    """Bytes of K and V one decode step has to fetch: for every live
    slot its length rounded up to the kernel's block, both K and V, all
    heads and layers. ``live_lens`` are the tokens each slot attends
    (its position + 1)."""
    blocks = sum(-(-int(n) // block_tokens) for n in live_lens)
    return (blocks * block_tokens * n_kv_heads * head_dim * 2
            * bytes_per_value * n_layer)


def flash_attention_flops(batch: int, n_heads: int, seq: int,
                          head_dim: int, causal: bool = True,
                          backward: bool = False) -> float:
    """One flash-attention call on [batch, n_heads, seq, head_dim]."""
    fwd = batch * n_heads * 2 * 2 * seq * seq * head_dim \
        * (0.5 if causal else 1.0)
    return fwd * (2.0 if backward else 1.0)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(share in %, which bound) — the least time the chip could take
    (the larger of ops / peak ops and bytes / peak bytes) over the time
    it took."""
    t_ops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
