"""Point-to-point-shaped collectives over a mesh axis.

The TPU-native form of the reference's enqueued Isend/Irecv ring exchange
(reference test/src/ring.c:78-90): inside ``shard_map``, a
``lax.ppermute`` IS "send to right neighbor / receive from left neighbor",
compiled by XLA into a collective-permute that rides ICI — the device
itself reaches the op in its execution stream, which is exactly the
"enqueued" property the reference builds a proxy thread to get. No host
round-trip, no flag table: on the ICI plane the hardware gives us the
semantics the host plane has to emulate.

All functions are per-shard functions: call them inside ``shard_map`` (or
use the ``*_sharded`` convenience wrappers that do it for you).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _ring_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


def ring_shift(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Rotate shards around the ring: each device sends its shard `shift`
    steps to the right and receives from the left. The enqueued-sendrecv
    primitive of the ICI plane."""
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, perm=_ring_perm(n, shift))


def neighbor_exchange(right_going: jax.Array, left_going: jax.Array,
                      axis_name: str) -> tuple[jax.Array, jax.Array]:
    """Bidirectional neighbor exchange: returns (from_left, from_right).

    Two opposite collective-permutes, which XLA schedules onto both ICI
    directions concurrently (full-duplex links).
    """
    n = lax.axis_size(axis_name)
    from_left = lax.ppermute(right_going, axis_name, perm=_ring_perm(n, 1))
    from_right = lax.ppermute(left_going, axis_name, perm=_ring_perm(n, -1))
    return from_left, from_right


def halo_exchange_1d(x: jax.Array, axis_name: str, halo: int) -> jax.Array:
    """1D halo exchange (periodic): pads shard `x` (leading axis) with
    `halo` rows from both ring neighbors.

    TPU-native counterpart of the reference's partitioned halo use-case
    (its 8-partition 1D halo config): the neighbor's boundary block arrives as
    one fused collective-permute instead of per-partition MPI messages.
    """
    top = x[:halo]          # my first rows -> left neighbor's bottom halo
    bottom = x[-halo:]      # my last rows  -> right neighbor's top halo
    from_left, from_right = neighbor_exchange(bottom, top, axis_name)
    return jnp.concatenate([from_left, x, from_right], axis=0)


def halo_exchange_2d(x: jax.Array, row_axis: str, col_axis: str,
                     halo: int) -> jax.Array:
    """2D halo exchange (periodic) over a 2D mesh (the reference's
    2D 5-point stencil config): rows first, then columns of the already-padded block — so
    edge halos carry the 4 axis neighbors and corner cells carry the
    DIAGONAL neighbors' corners (sufficient for 9-point as well as 5-point
    stencils).

    `x` is the local [H, W] block; returns [H+2h, W+2h].
    """
    x = halo_exchange_1d(x, row_axis, halo)                # pad rows
    left = x[:, :halo]
    right = x[:, -halo:]
    from_left, from_right = neighbor_exchange(right, left, col_axis)
    return jnp.concatenate([from_left, x, from_right], axis=1)


def all_to_all_seq(x: jax.Array, axis_name: str, split_axis: int,
                   concat_axis: int) -> jax.Array:
    """All-to-all reshard (the Ulysses sequence-parallelism primitive):
    redistributes a [.., seq_shard, .., heads, ..] layout between
    sequence-sharded and head-sharded, in one ICI all-to-all."""
    n = lax.axis_size(axis_name)
    parts = jnp.split(x, n, axis=split_axis)
    stacked = jnp.stack(parts, axis=0)  # [n, ...]
    swapped = lax.all_to_all(stacked, axis_name, split_axis=0, concat_axis=0,
                             tiled=False)
    return jnp.concatenate([swapped[i] for i in range(n)], axis=concat_axis)


# ---- array-level wrappers (shard_map plumbing) ---------------------------


def ring_shift_sharded(arr: jax.Array, mesh: Mesh, axis_name: str = "x",
                       shift: int = 1) -> jax.Array:
    """Array-level ring shift: `arr` sharded on its leading dim over
    `axis_name`; every shard moves one ring step."""
    spec = P(axis_name)
    f = shard_map(
        functools.partial(ring_shift, axis_name=axis_name, shift=shift),
        mesh=mesh, in_specs=(spec,), out_specs=spec)
    return f(arr)


def halo_exchange_1d_sharded(arr: jax.Array, mesh: Mesh, halo: int,
                             axis_name: str = "x") -> jax.Array:
    """Array-level 1D halo exchange; returns the per-shard padded blocks
    stacked on a new leading axis (shape [n_shards, shard+2*halo, ...])."""
    spec = P(axis_name)
    out_spec = P(axis_name)

    def body(x):
        padded = halo_exchange_1d(x, axis_name, halo)
        return padded[None]  # add shard axis so out stays shardable

    f = shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=out_spec)
    return f(arr)
