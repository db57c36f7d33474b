"""Device-side partition signaling as Pallas TPU kernels.

The reference lets a *running CUDA kernel* participate in the partitioned
communication state machine through two ``__host__ __device__`` functions:

* ``MPIX_Pready(p, req)`` — store ``PENDING`` into the flag word for
  partition ``p`` (reference partitioned.cu:200-212, a raw store into
  host-mapped memory: ``preq->flags[preq->idx[p]] = MPIACX_OP_STATE_PENDING``);
* ``MPIX_Parrived(req, p, &flag)`` — read the flag word, true iff
  ``COMPLETED`` (partitioned.cu:215-231).

TPU kernels cannot dereference host pointers, so the TPU-native form keeps
the flag table in an **HBM int32 buffer** and expresses both operations as
Pallas kernels over it (SURVEY.md §7.1: "device side: Pallas kernel doing a
DMA store to / copy-poll of a flag buffer"). The state values are the
shared protocol constants of the whole framework (include/acx/state.h,
reference mpi-acx-internal.h:196-203), so a flag buffer produced here can
be mirrored to the host page the native proxy polls.

Functional form: every mutator returns the updated flag buffer (donated /
aliased, so XLA performs the update in place in HBM). ``jit``-compatible,
static-shaped; runs compiled on TPU and interpreted on CPU meshes.

The deadlock rule from the reference (README.md:152-159: a single kernel
that both marks partitions ready and polls arrivals can deadlock) is
preserved structurally: ``pready*`` and ``parrived*`` are separate kernels,
and ``parrived`` is a non-blocking poll — there is no blocking wait
primitive on purpose.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_acx_tpu import backend

# Op states — the wire protocol shared with the native runtime
# (include/acx/state.h; reference mpi-acx-internal.h:196-203).
AVAILABLE = 0
RESERVED = 1
PENDING = 2
ISSUED = 3
COMPLETED = 4
CLEANUP = 5

_LANE = 128
_MIN_ROWS = 8  # int32 min tile is (8, 128)


def _padded(flags: jax.Array):
    """Reshape a 1-D int32 flag table to the 2-D (rows, 128) layout the VPU
    wants, padding to the (8, 128) int32 min tile. Returns (2-D array, n)."""
    n = flags.shape[0]
    rows = max(_MIN_ROWS, -(-n // _LANE))
    pad = rows * _LANE - n
    if pad:
        flags = jnp.pad(flags, (0, pad))
    return flags.reshape(rows, _LANE), n


def _linear_ids(shape):
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return r * _LANE + c


def _pready_kernel(idx_ref, flags_ref, out_ref):
    lin = _linear_ids(flags_ref.shape)
    out_ref[:] = jnp.where(lin == idx_ref[0, 0], PENDING, flags_ref[:])


def pready(flags: jax.Array, idx: jax.Array | int) -> jax.Array:
    """Mark the flag slot `idx` PENDING from device code.

    TPU-native ``MPIX_Pready`` (reference partitioned.cu:200-212): the
    whole-table masked select compiles to one VPU pass over the table —
    no scalar scatter, no host round trip. Returns the updated table
    (input donated: in-place in HBM under jit).
    """
    f2, n = _padded(flags)
    idx = jnp.asarray(idx, jnp.int32).reshape(1, 1)
    out = pl.pallas_call(
        _pready_kernel,
        out_shape=jax.ShapeDtypeStruct(f2.shape, jnp.int32),
        in_specs=[
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        input_output_aliases={1: 0},
        interpret=not backend.on_tpu(),
    )(idx, f2)
    return out.reshape(-1)[:n]


def _pready_many_kernel(idxs_ref, flags_ref, out_ref):
    lin = _linear_ids(flags_ref.shape)
    k = idxs_ref.shape[1]

    def body(i, cur):
        return jnp.where(lin == idxs_ref[0, i], PENDING, cur)

    out_ref[:] = jax.lax.fori_loop(0, k, body, flags_ref[:])


def pready_many(flags: jax.Array, idxs: jax.Array) -> jax.Array:
    """Mark several slots PENDING in one kernel (the ``mark_ready<<<1,N>>>``
    launch of reference ring-partitioned.cu:38-40, collapsed into a single
    vector pass)."""
    f2, n = _padded(flags)
    idxs = jnp.asarray(idxs, jnp.int32).reshape(1, -1)
    out = pl.pallas_call(
        _pready_many_kernel,
        out_shape=jax.ShapeDtypeStruct(f2.shape, jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        input_output_aliases={1: 0},
        interpret=not backend.on_tpu(),
    )(idxs, f2)
    return out.reshape(-1)[:n]


def _parrived_kernel(idx_ref, flags_ref, out_ref):
    lin = _linear_ids(flags_ref.shape)
    word = jnp.sum(jnp.where(lin == idx_ref[0, 0], flags_ref[:], 0))
    out_ref[0, 0] = (word == COMPLETED).astype(jnp.int32)


def parrived(flags: jax.Array, idx: jax.Array | int) -> jax.Array:
    """Non-blocking poll: is slot `idx` COMPLETED? Returns a 0/1 int32
    scalar (TPU-native ``MPIX_Parrived``, reference partitioned.cu:215-231)."""
    f2, _ = _padded(flags)
    idx = jnp.asarray(idx, jnp.int32).reshape(1, 1)
    out = pl.pallas_call(
        _parrived_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        in_specs=[
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=not backend.on_tpu(),
    )(idx, f2)
    return out[0, 0]


def _parrived_all_kernel(idxs_ref, flags_ref, out_ref):
    lin = _linear_ids(flags_ref.shape)
    k = idxs_ref.shape[1]

    def body(i, acc):
        word = jnp.sum(jnp.where(lin == idxs_ref[0, i], flags_ref[:], 0))
        return jnp.logical_and(acc, word == COMPLETED)

    done = jax.lax.fori_loop(0, k, body, jnp.bool_(True))
    out_ref[0, 0] = done.astype(jnp.int32)


def parrived_all(flags: jax.Array, idxs: jax.Array) -> jax.Array:
    """Poll a set of slots; 1 iff every one is COMPLETED (the condition the
    ``wait_until_arrived`` spin of ring-partitioned.cu:42-47 waits for —
    exposed as a poll, never a device-side spin: see module docstring)."""
    f2, _ = _padded(flags)
    idxs = jnp.asarray(idxs, jnp.int32).reshape(1, -1)
    out = pl.pallas_call(
        _parrived_all_kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=not backend.on_tpu(),
    )(idxs, f2)
    return out[0, 0]


def produce_and_pready(
    produce: Callable[[jax.Array], jax.Array],
    x: jax.Array,
    flags: jax.Array,
    idx: jax.Array | int,
) -> tuple[jax.Array, jax.Array]:
    """Fused produce-then-signal: one kernel computes a partition's payload
    and marks its flag PENDING — the pattern the reference's partitioned
    API exists for ("a kernel marks partitions of a message ready as it
    produces them", reference README.md:60-66). The flag store is in the
    same kernel as the payload store, so readiness is published with the
    data, with no separate launch between them.

    ``produce`` is any shape-preserving traced function of the payload
    block (runs on VPU/MXU in VMEM). ``x`` must be 2-D and tile-aligned.
    Returns ``(payload, updated_flags)``.
    """
    f2, n = _padded(flags)
    idx = jnp.asarray(idx, jnp.int32).reshape(1, 1)

    def kernel(idx_ref, x_ref, flags_ref, payload_ref, fout_ref):
        payload_ref[:] = produce(x_ref[:])
        lin = _linear_ids(flags_ref.shape)
        fout_ref[:] = jnp.where(lin == idx_ref[0, 0], PENDING, flags_ref[:])

    payload, fout = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(f2.shape, jnp.int32),
        ),
        in_specs=[
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        input_output_aliases={2: 1},
        interpret=not backend.on_tpu(),
    )(idx, x, f2)
    return payload, fout.reshape(-1)[:n]
