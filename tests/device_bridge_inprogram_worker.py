"""Worker for the IN-PROGRAM partitioned publish test: one acxrun rank.

In-program partitioned signaling: the earlier bridge worker drove the
publish loop from the HOST between kernel launches; the reference signals from inside a running kernel
while later partitions are still being produced
(reference partitioned.cu:200-212 -> init.cpp:82-115). This worker is
the TPU-native equivalent with the host making exactly ONE jitted call
per rank:

rank 0 (sender): one jitted ``lax.scan`` over partitions. Each step runs
the fused Pallas produce_and_pready kernel, then an ORDERED
``io_callback`` node — compiled into the program, firing when execution
reaches it — lands the payload in the wire buffer and mirrors the
device flag word into the proxy-polled native table
(publish_partition_flags). The proxy pushes partition p onto the wire
while the program is still producing partitions p+1.. — the
produce->publish overlap the partitioned API exists for, and it is
ASSERTED: the receiver must witness a partially-complete flag table.

rank 1 (receiver): one jitted program whose ``lax.while_loop`` polls the
native table through an ordered ``io_callback`` (fetch_partition_flags)
and lets the Pallas parrived_all kernel decide arrival; a final callback
returns the received payloads as the program's value.

Prints INPROGRAM_OK <parts> <min_partial> on success, where min_partial
is the smallest nonzero completed-count the receiver observed while
polling (0 < min_partial < parts proves overlap).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import io_callback  # noqa: E402

from mpi_acx_tpu.ops import flags as fl  # noqa: E402
from mpi_acx_tpu.runtime import Runtime  # noqa: E402

PARTS = 4
ROWS, LANES = 8, 128
# Sender-side per-partition production stagger (seconds): makes the
# overlap deterministic enough for the receiver to witness a partial
# table without busy-tuning (total program ~4 * 0.04 s). The launching
# test overrides/reads it through the environment so its trace-spread
# assertion and this delay share one value.
STAGGER_S = float(os.environ.get("ACX_IP_STAGGER_S", "0.04"))


def main():
    rt = Runtime()
    assert rt.size == 2, rt.size
    peer = 1 - rt.rank
    buf = np.zeros((PARTS, ROWS, LANES), dtype=np.float32)

    if rt.rank == 0:
        req = rt.psend_init(buf, PARTS, dest=peer)
        rt.start(req)

        def publish(p, payload, dev_flags):
            # Payload must be on the wire buffer BEFORE readiness is
            # visible; both happen inside this one ordered node.
            buf[int(p)] = np.asarray(payload)
            rt.publish_partition_flags(req, np.asarray(dev_flags))
            time.sleep(STAGGER_S)   # emulate producing the next partition

        @jax.jit
        def sender_program(dev_flags):
            def step(dev_flags, p):
                x = jnp.full((ROWS, LANES), 0.0, jnp.float32) + (
                    p + 1).astype(jnp.float32)
                payload, dev_flags = fl.produce_and_pready(
                    lambda t: t * 2.0 + 1.0, x, dev_flags, p)
                io_callback(publish, None, p, payload, dev_flags,
                            ordered=True)
                return dev_flags, payload[0, 0]
            return lax.scan(step, dev_flags, jnp.arange(PARTS))

        dev_flags0 = jnp.full((PARTS,), fl.RESERVED, jnp.int32)
        # THE one host call on this rank: everything above happens
        # inside this single jitted program's execution.
        dev_flags, firsts = jax.block_until_ready(
            sender_program(dev_flags0))
        assert [int(v) for v in dev_flags] == [fl.PENDING] * PARTS
        rt.wait(req)
        rt.request_free(req)
        rt.barrier()
        print(f"INPROGRAM_OK {PARTS} -")
    else:
        req = rt.precv_init(buf, PARTS, source=peer)
        rt.start(req)
        idxs = jnp.arange(PARTS)
        partials = []

        def fetch():
            mirror = np.asarray(rt.fetch_partition_flags(req),
                                dtype=np.int32)
            partials.append(int((mirror == fl.COMPLETED).sum()))
            time.sleep(0.002)
            return mirror

        def collect():
            return buf.copy()

        @jax.jit
        def receiver_program():
            def cond(state):
                done, _ = state
                return done == 0

            def body(state):
                _, it = state
                mirror = io_callback(
                    fetch, jax.ShapeDtypeStruct((PARTS,), jnp.int32),
                    ordered=True)
                # The KERNEL decides arrival, not the host.
                return fl.parrived_all(mirror, idxs), it + 1

            _, polls = lax.while_loop(
                cond, body, (jnp.asarray(0, jnp.int32),
                             jnp.asarray(0, jnp.int32)))
            payload = io_callback(
                collect,
                jax.ShapeDtypeStruct((PARTS, ROWS, LANES), jnp.float32),
                ordered=True)
            return polls, payload

        # THE one host call on this rank.
        polls, payload = jax.block_until_ready(receiver_program())
        rt.wait(req)
        for p in range(PARTS):
            np.testing.assert_array_equal(
                np.asarray(payload)[p], (p + 1) * 2.0 + 1.0)
        # Overlap witness: some poll saw a PARTIAL table — partitions
        # were arriving while the sender's program was still producing.
        partial = [c for c in partials if 0 < c < PARTS]
        assert partial, (partials[:50], int(polls))
        rt.request_free(req)
        rt.barrier()
        print(f"INPROGRAM_OK {PARTS} {min(partial)}")

    rt.finalize()


if __name__ == "__main__":
    main()
