"""Quantized all-reduce over a mesh axis — bandwidth-compressed gradient
synchronization (after EQuARX, arXiv:2506.17615; see PAPERS.md).

Data-parallel gradient sync moves full f32 gradients over the wire every
step. This module implements the all-reduce as the standard ring
reduce-scatter + all-gather decomposition, but QUANTIZES every hop's
payload to int8 (symmetric, one f32 max-abs scale per 128-lane block) —
~4x less ICI/DCN traffic, at a bounded relative error: each hop
re-quantizes the partial sum at ~1/254 of its block max, so worst-case
elementwise error grows linearly in ring length (measured ~1.5% of the
result's max-norm on an 8-ring) while the mean error stays an order of
magnitude tighter (tests/test_quantized.py pins max < 2.5%, mean < 0.6%
— ~0.2% measured on 1024-element tensors).

Everything is SPMD inside ``shard_map``: the ring is ``lax.ppermute``
steps (int8 chunk + f32 scale riding together), chunk bookkeeping is
static Python over the (static) axis size, and the per-rank chunk index
is the only traced scalar — XLA sees a fixed schedule of n-1 sends per
phase, exactly like its native all-reduce, just narrower.

Use :func:`quantized_pmean` as a drop-in for ``lax.pmean`` on gradient
leaves when the dp axis rides a slow link (DCN cross-slice sync is the
EQuARX target); keep exact pmean when ICI is not the bottleneck. The
distributed train step exposes this as ``dp_quant_bits``
(mpi_acx_tpu.train.make_loss_and_grads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


_BLOCK = 128   # lanes per quantization block (one f32 scale per block,
               # ~3% wire overhead; block-wise scales localize outliers —
               # the EQuARX design choice that keeps per-hop error tight)


def _quant(x: jax.Array, qmax: float):
    """Symmetric block-wise max-abs quantization: f32 [C] (C a multiple
    of _BLOCK) -> (int8 [C//B, B], f32 scales [C//B, 1])."""
    xb = x.reshape(-1, _BLOCK)
    s = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / qmax
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(xb / s), -qmax, qmax).astype(jnp.int8)
    return q, s


def ring_psum(x: jax.Array, axis_name: str,
              quantize: bool = True) -> jax.Array:
    """Ring reduce-scatter + all-gather all-reduce (per-shard function).

    ``quantize=True`` sends every hop as int8 + per-block f32 scales
    (~4x less wire traffic, the EQuARX scheme); ``quantize=False`` sends
    raw f32 — the EXACT all-reduce on the IDENTICAL hop schedule, which
    is what a wire-byte comparison measures against (one
    skeleton, so the two variants cannot silently diverge).
    """
    n = lax.axis_size(axis_name)
    qmax = 127.0
    r = lax.axis_index(axis_name)
    ring = [(i, (i + 1) % n) for i in range(n)]

    shape, size = x.shape, x.size
    c = -(-size // n)                                   # ceil chunk size
    c = -(-c // _BLOCK) * _BLOCK                        # round to blocks
    flat = jnp.zeros((n * c,), jnp.float32).at[:size].set(
        x.astype(jnp.float32).reshape(-1))
    acc = flat.reshape(n, c)

    def hop(chunk):
        """Encode, permute one step along the ring, decode -> [c] f32."""
        if not quantize:
            return lax.ppermute(chunk, axis_name, ring)
        q, s = _quant(chunk, qmax)
        q = lax.ppermute(q, axis_name, ring)
        s = lax.ppermute(s, axis_name, ring)
        return (q * s).reshape(-1)

    # -- reduce-scatter: n-1 hops; after step t, the chunk each rank
    # just accumulated holds t+2 ranks' contributions. Rank r ends
    # owning the fully reduced chunk (r + 1) mod n.
    for t in range(n - 1):
        si = (r - t) % n                                # traced index
        chunk = lax.dynamic_slice_in_dim(acc, si, 1, 0)[0]
        got = hop(chunk)
        ri = (r - t - 1) % n
        upd = lax.dynamic_slice_in_dim(acc, ri, 1, 0)[0] + got
        acc = lax.dynamic_update_slice_in_dim(acc, upd[None], ri, 0)

    owned = (r + 1) % n
    reduced = lax.dynamic_slice_in_dim(acc, owned, 1, 0)[0]

    # -- all-gather: every rank broadcasts its reduced chunk around the
    # ring, encoded ONCE (the owner also keeps the decode-of-encode
    # value so all ranks hold bit-identical results).
    if quantize:
        q, s = _quant(reduced, qmax)
        cur = (q * s).reshape(-1)
    else:
        q = s = None
        cur = reduced
    out = jnp.zeros((n, c), jnp.float32)
    out = lax.dynamic_update_slice_in_dim(out, cur[None], owned, 0)
    for t in range(1, n):
        if quantize:
            q = lax.ppermute(q, axis_name, ring)
            s = lax.ppermute(s, axis_name, ring)
            cur = (q * s).reshape(-1)
        else:
            cur = lax.ppermute(cur, axis_name, ring)
        idx = (r - t + 1) % n
        out = lax.dynamic_update_slice_in_dim(out, cur[None], idx, 0)

    return out.reshape(-1)[:size].reshape(shape)


def quantized_psum(x: jax.Array, axis_name: str, bits: int = 8) -> jax.Array:
    """All-reduce-sum of ``x`` over ``axis_name`` with int8-quantized ring
    hops (per-shard function — call inside shard_map). Returns f32 of
    ``x``'s shape, identical on every rank.

    bits: only 8 currently (int8 wire dtype); the parameter documents the
    knob the EQuARX design space exposes.
    """
    assert bits == 8, "int8 is the implemented wire format"
    n = lax.axis_size(axis_name)
    if n == 1:
        return x.astype(jnp.float32)
    if x.size < n * _BLOCK:
        # Small leaves (norm gains, biases): block padding + 2(n-1)
        # serialized hops would move MORE bytes at MORE latency than the
        # exact all-reduce — fall back to it (also exact, a bonus).
        return lax.psum(x.astype(jnp.float32), axis_name)
    return ring_psum(x, axis_name, quantize=True)


def quantized_pmean(x: jax.Array, axis_name: str, bits: int = 8):
    """Mean-reducing sibling of :func:`quantized_psum` (the gradient-sync
    drop-in for ``lax.pmean``)."""
    return quantized_psum(x, axis_name, bits) / lax.axis_size(axis_name)
