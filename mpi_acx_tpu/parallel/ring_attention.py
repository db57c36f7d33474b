"""Ring attention: exact attention over a sequence sharded across devices.

The long-context capability of the framework (first-class per the build
goals): each device holds a sequence block of Q, K, V; K/V blocks rotate
around the ring (collective-permute over ICI) while each device
accumulates its Q-block's attention over every K/V block using the
numerically stable logsumexp merge (flash-attention style). After `n`
steps every Q block has attended to the full sequence, with peak memory
O(seq/n) and the K/V transfer of step k overlapping the attention compute
of step k-1 — the same produce/transmit overlap the reference's
partitioned primitive provides on the host plane (SURVEY.md §5.7 maps
partitioned comm to exactly this pipelined exchange).

Each ring step's block-pair attention runs the Pallas flash kernel
(:func:`mpi_acx_tpu.ops.attention.flash_attention_lse`) when profitable —
the kernel returns (normalized output, row logsumexp), exactly the merge
state the ring needs, so the sequence-parallel path keeps the single-chip
flash advantage. A K/V block is, per the causal structure, entirely
visible (source block before this device's block: unmasked flash call),
entirely masked (source after: skipped — no FLOPs at all), or diagonal
(the standard causal flash call); the three cases dispatch by
``lax.switch`` on the rotating source index.

An axis of ONE communicates nothing and merges nothing: there
:func:`ring_attention_batched` returns its one block's attention directly
(:func:`mpi_acx_tpu.ops.attention.flash_attention`, or one dense block),
with no accumulator, scan, switch, permute or float32 copy of the output.
The values are the ring-of-one's bit for bit (a merge with an empty
accumulator is the identity in floating point too).
:func:`attention_calls_traced` says which of the two a program holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu import backend
from mpi_acx_tpu.parallel.collective import _ring_perm

_NEG = float(jnp.finfo(jnp.float32).min)

# Flash engages automatically only when the PER-SHARD Q block is at
# least this long (and 128-aligned): below it the kernel's grid/launch
# overhead loses to one fused dense block on the measured v5e crossover.
# NOTE the cliff when choosing tp: the shard is S/tp, so e.g. S=2048 at
# tp=8 gives 256-long shards and the SP path runs the (exact,
# identical-math) dense blocks — pass use_flash=True to force the
# kernel, or keep S/tp >= this threshold for the flash win at scale.
FLASH_MIN_SHARD = 1024

_calls_traced = {"direct": 0, "ring": 0}


def attention_calls_traced() -> dict[str, int]:
    """How many :func:`ring_attention_batched` calls this process has
    TRACED so far as one direct block (an axis of one) and as a ring (an
    axis of two or more): counted in the function's Python body, which
    runs only under a trace. All or nothing per program: a program's mesh
    gives every one of its attention calls the same axis size."""
    return dict(_calls_traced)


def _dense_block(q32, kk, vv, mask):
    """One Q-block x K-block dense attention: returns (normalized_out
    [mb, Sq, H, D] f32, lse [mb, H, Sq] f32). Fully-masked rows get
    lse = finfo.min (an additive identity for the logaddexp merge)."""
    d = q32.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q32, kk.astype(jnp.float32))
    logits = logits / jnp.sqrt(d)
    logits = jnp.where(mask, logits, _NEG)
    m = jnp.max(logits, axis=-1)                      # [mb, H, Sq]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(mask, p, 0.0)                       # kill fully-masked rows
    l = jnp.sum(p, axis=-1)                           # [mb, H, Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vv.astype(jnp.float32))
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), _NEG)
    o = o / jnp.moveaxis(jnp.maximum(l, 1e-37), 1, 2)[..., None]
    return o, lse


def ring_attention_batched(q: jax.Array, k: jax.Array, v: jax.Array,
                           axis_name: str, causal: bool = True,
                           use_flash: bool | None = None,
                           kv_repeat: int = 1) -> jax.Array:
    """Exact (optionally causal) attention with K/V rotating on the ring.

    Per-shard shapes: q = [mb, seq_shard, heads, head_dim]; k, v =
    [mb, seq_shard, heads/kv_repeat, head_dim]; the global sequence is the
    concatenation of shards in mesh order. Returns the attention output
    for the local Q block, same shape as q.

    kv_repeat > 1 is grouped-query attention: the ring rotates the
    UN-expanded K/V heads (kv_repeat x less ICI traffic per ppermute —
    the bandwidth GQA exists to save) and each block broadcasts them to
    the query heads locally, where XLA fuses the broadcast into the dots.

    use_flash: None -> auto (Pallas kernel on TPU when the PER-SHARD
    length reaches :data:`FLASH_MIN_SHARD` and is 128-aligned — note
    the shard is the global sequence over the tp/sp degree, so high tp
    can silently drop the auto path below the crossover; see
    FLASH_MIN_SHARD), True/False -> force. The dense and flash paths
    produce identical math; both yield (normalized block output, lse) and
    merge with logaddexp, so switching kernels never changes numerics
    beyond float roundoff.

    An axis of ONE (``lax.axis_size``, a Python int at trace time: a
    ``tp = 1`` mesh) runs no ring: the one block IS the answer, so the
    flash path is one :func:`~mpi_acx_tpu.ops.attention.flash_attention`
    call and the dense path one :func:`_dense_block`, with no
    accumulator, ``scan``, ``switch``, ``ppermute`` or float32 copy of
    the output, and no lse cotangent in the backward. Bit-equal to the
    ring of one it replaces, outputs and gradients
    (tests/test_ring_attention.py); from 16384 tokens on
    ``flash_attention`` picks its streaming kernel, which a ring's
    ``flash_attention_lse`` blocks do not have.
    """
    n = lax.axis_size(axis_name)
    mb, sq, h, dh = q.shape
    assert k.shape[2] * kv_repeat == h, (k.shape, h, kv_repeat)
    if use_flash is None:
        use_flash = (backend.on_tpu()
                     and sq >= FLASH_MIN_SHARD and sq % 128 == 0)

    def expand(x):
        # kv-head g serves query heads [g*kv_repeat, (g+1)*kv_repeat) —
        # the same layout as the model families' _repeat_kv.
        if kv_repeat == 1:
            return x
        hkv = x.shape[2]
        return jnp.broadcast_to(
            x[:, :, :, None, :],
            (mb, x.shape[1], hkv, kv_repeat, dh)).reshape(
                mb, x.shape[1], h, dh)

    _calls_traced["direct" if n == 1 else "ring"] += 1
    if n == 1:
        if use_flash:
            from mpi_acx_tpu.ops.attention import flash_attention
            return flash_attention(q, expand(k), expand(v), causal=causal)
        mask = (jnp.tril(jnp.ones((sq, sq), bool))[None, None] if causal
                else jnp.ones((1, 1, sq, sq), bool))
        return _dense_block(q.astype(jnp.float32), expand(k), expand(v),
                            mask)[0].astype(q.dtype)

    my = lax.axis_index(axis_name)
    if use_flash:
        from mpi_acx_tpu.ops.attention import flash_attention_lse

        def full_fn(q_, kk, vv):
            o, lse = flash_attention_lse(q_, expand(kk), expand(vv),
                                         causal=False)
            return o.astype(jnp.float32), lse

        def diag_fn(q_, kk, vv):
            o, lse = flash_attention_lse(q_, expand(kk), expand(vv),
                                         causal=True)
            return o.astype(jnp.float32), lse

        def skip_fn(q_, kk, vv):
            return (jnp.zeros((mb, sq, h, dh), jnp.float32),
                    jnp.full((mb, h, sq), _NEG, jnp.float32))

        def block_fn(q_, kk, vv, src):
            if not causal:
                return full_fn(q_, kk, vv)
            idx = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            return lax.switch(idx, (full_fn, diag_fn, skip_fn), q_, kk, vv)

        q_in = q
    else:
        def block_fn(q_, kk, vv, src):
            if causal:
                qpos = my * sq + jnp.arange(sq)[:, None]            # [Sq,1]
                kpos = src * sq + jnp.arange(kk.shape[1])[None, :]  # [1,Sk]
                mask = (kpos <= qpos)[None, None]              # [1,1,Sq,Sk]
            else:
                mask = jnp.ones((1, 1, sq, kk.shape[1]), bool)
            return _dense_block(q_, expand(kk), expand(vv), mask)

        q_in = q.astype(jnp.float32)

    # Accumulators are device-varying from step 0 (they mix in rotated K/V);
    # mark them so the scan carry type is stable under shard_map's vma check.
    o0 = lax.pcast(jnp.zeros(q.shape, jnp.float32), axis_name, to="varying")
    lse0 = lax.pcast(jnp.full((mb, h, sq), _NEG, jnp.float32), axis_name,
                     to="varying")

    def step(carry, t):
        o_acc, lse_acc, kk, vv = carry
        # K/V block currently held arrived from `t` ring steps back.
        src = (my - t) % n
        o_b, lse_b = block_fn(q_in, kk, vv, src)
        # logaddexp merge. finfo.min sentinels stay finite, so the weights
        # are well-defined with no NaN guard: a finfo.min-vs-finfo.min
        # merge gives weight 1 on a zero block output.
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        wa = jnp.exp(lse_acc - lse_new)                   # [mb, H, Sq]
        wb = jnp.exp(lse_b - lse_new)
        o_new = (o_acc * jnp.moveaxis(wa, 1, 2)[..., None]
                 + o_b * jnp.moveaxis(wb, 1, 2)[..., None])
        # Rotate K/V to the right neighbor for the next step; XLA overlaps
        # this transfer with the next iteration's compute.
        kk = lax.ppermute(kk, axis_name, perm=_ring_perm(n, 1))
        vv = lax.ppermute(vv, axis_name, perm=_ring_perm(n, 1))
        return (o_new, lse_new, kk, vv), None

    (o, _, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(n))
    return o.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str,
                   causal: bool = True,
                   use_flash: bool | None = None) -> jax.Array:
    """3-D per-shard form: q, k, v = [seq_shard, heads, head_dim]."""
    return ring_attention_batched(q[None], k[None], v[None], axis_name,
                                  causal=causal, use_flash=use_flash)[0]


def blockwise_attention_reference(q, k, v, causal=True):
    """Single-device reference attention (for tests): [S, H, D] inputs."""
    d = q.shape[-1]
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(d)
    if causal:
        s = q.shape[0]
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None], logits, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "x",
                           causal: bool = True,
                           use_flash: bool | None = None):
    """Array-level wrapper: q/k/v sharded on the sequence (leading) axis."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(axis_name)
    # check_vma=False: the Pallas interpreter (CPU path) can't yet mix
    # varying and non-varying operands inside its internal dynamic_slice
    # ("Primitive dynamic_slice requires varying manual axes to match ...
    # as a temporary workaround pass check_vma=False"); the distributed
    # train step (train.py) runs the same per-shard function with
    # check_vma=False as well.
    f = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                          use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return f(q, k, v)
