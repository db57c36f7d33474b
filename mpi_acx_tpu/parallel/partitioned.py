"""Partitioned (per-partition-ready) exchange on the ICI plane.

The reference's partitioned communication (MPI_Psend_init + device-side
MPIX_Pready/Parrived, reference partitioned.cu:36-231) exists to overlap a
kernel's *production* of message fragments with their *transmission*. On
TPU, XLA programs are static, so "the kernel marks partition p ready" is
expressed structurally instead of dynamically: a ``lax.scan`` whose steps
interleave (a) computing/consuming one partition with (b) transmitting
another via collective-permute. XLA overlaps the ppermute of step k with
the compute of step k (async collective start/done), giving the same
pipelining the reference gets from its proxy thread — without a proxy.

Two shapes are provided:

* :func:`partitioned_ring_exchange` — fixed-size partitioned neighbor
  exchange with a per-partition consumer, the analogue of
  ring-partitioned.cu's mark_ready/wait_until_arrived pair.
* :func:`partitioned_pipeline` — produce-send-consume: a producer makes
  partition k while partition k-1 is in flight, the exact overlap pattern
  pipeline-parallel microbatch exchange needs (the reference's driver
  configs 3 and 4, SURVEY.md).

Host-plane partitioned channels (real out-of-order Pready across process
boundaries) live in the native runtime: mpi_acx_tpu.runtime.psend_init.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.parallel.collective import _ring_perm


def partitioned_ring_exchange(
    x: jax.Array,
    axis_name: str,
    partitions: int,
    consume: Callable[[jax.Array], jax.Array] | None = None,
) -> jax.Array:
    """Send local shard `x` one ring step in `partitions` chunks, applying
    `consume` to each arriving chunk as it lands.

    Per scan step, chunk k is on the wire while chunk k-1 is being
    consumed — compute/comm overlap per partition, the property the
    reference's per-partition flags exist to provide.

    `x`'s leading dim must divide into `partitions`. Returns the received
    shard with `consume` applied chunkwise (identity if None).
    """
    n = lax.axis_size(axis_name)
    chunks = x.reshape((partitions, -1) + x.shape[1:])

    def step(_, chunk):
        arrived = lax.ppermute(chunk, axis_name, perm=_ring_perm(n, 1))
        out = arrived if consume is None else consume(arrived)
        return None, out

    _, received = lax.scan(step, None, chunks)
    return received.reshape((-1,) + x.shape[1:])


def partitioned_pipeline(
    produce: Callable[[jax.Array | int], jax.Array],
    consume: Callable[[jax.Array, jax.Array], jax.Array],
    init_acc: jax.Array,
    partitions: int,
    axis_name: str,
) -> jax.Array:
    """Produce partition k, transmit it right, consume on arrival — with
    production of k+1 overlapping transmission of k (software-pipelined by
    one step, matching "Pready fires as soon as a partition is produced",
    reference README.md:105-115).

    produce(k) -> partition payload (same shape each k)
    consume(acc, payload) -> acc
    Returns the final accumulator of arrivals from the left neighbor.
    """
    n = lax.axis_size(axis_name)

    def step(acc, k):
        payload = produce(k)
        arrived = lax.ppermute(payload, axis_name, perm=_ring_perm(n, 1))
        return consume(acc, arrived), None

    # The accumulator becomes device-varying after the first arrival; mark
    # the initial value varying so the scan carry type is stable.
    init_acc = lax.pcast(init_acc, axis_name, to="varying")
    acc, _ = lax.scan(step, init_acc, jnp.arange(partitions))
    return acc
