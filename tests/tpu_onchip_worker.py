"""Worker for the ON-CHIP trigger/bridge proof: one acxrun rank.

The reference's entire reason to exist is the REAL device firing
communication (reference src/sendrecv.cu:152-208,
partitioned.cu:200-212); this worker is the single-chip TPU variant.

With ``ACX_RANK0_PLATFORM=tpu`` (chip_smoke.py's trigger phase) rank 0
runs on the chip — it keeps whatever platform the machine gives JAX
and asserts it is a TPU: a COMPILED jitted program computes a matmul
on the MXU and fires an in-program ``io_callback`` send with the
result; then a COMPILED (not interpret-mode — asserted) Pallas
produce_and_pready kernel publishes partition readiness through the
flag bridge, driving a real 2-rank wire transfer. Rank 1 always pins
the CPU (a chip belongs to one process) and verifies both payloads.
With ``ACX_RANK0_PLATFORM=cpu`` (the default; tests/test_tpu_onchip.py)
both ranks run on the CPU, so the plumbing stays continuously tested.

Prints ONCHIP_OK <backend> per rank on success.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RANK = int(os.environ.get("ACX_RANK", "0"))
ON_CHIP = RANK == 0 and os.environ.get("ACX_RANK0_PLATFORM", "cpu") == "tpu"
if not ON_CHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import io_callback  # noqa: E402

from mpi_acx_tpu import backend  # noqa: E402
from mpi_acx_tpu import xla_triggers as xt  # noqa: E402
from mpi_acx_tpu.ops import flags as fl  # noqa: E402
from mpi_acx_tpu.runtime import Runtime  # noqa: E402

PARTS = 2
ROWS, LANES = 8, 128


def main():
    rt = Runtime()
    assert rt.size == 2, rt.size
    peer = 1 - rt.rank
    platform = jax.default_backend()
    if ON_CHIP:
        backend.enable_compile_cache()

    if rt.rank == 0:
        if ON_CHIP:
            # The whole point: the CHIP, not a CPU stand-in.
            assert platform == "tpu", platform
            assert backend.on_tpu(), "Pallas must compile, not interpret"

        # -- 1) in-program trigger from a compiled program ------------
        w = jnp.eye(LANES, dtype=jnp.float32) * 3.0

        @jax.jit
        def program(x):
            y = x @ w                      # MXU work before the trigger
            y = xt.send_in_program(rt, y, dest=peer, tag=5)
            return y.sum()

        x = jnp.ones((ROWS, LANES), jnp.float32)
        s = float(jax.block_until_ready(program(x)))
        assert s == 3.0 * ROWS * LANES, s
        assert xt.drain_sends(rt) == 1

        # -- 2) compiled Pallas flag kernel drives the bridge ---------
        buf = np.zeros((PARTS, ROWS, LANES), dtype=np.float32)
        req = rt.psend_init(buf, PARTS, dest=peer)
        rt.start(req)

        def publish(p, payload, dev_flags):
            buf[int(p)] = np.asarray(payload)
            rt.publish_partition_flags(req, np.asarray(dev_flags))

        @jax.jit
        def sender(dev_flags):
            def step(dev_flags, p):
                xp = jnp.full((ROWS, LANES), 0.0, jnp.float32) + (
                    p + 2).astype(jnp.float32)
                payload, dev_flags = fl.produce_and_pready(
                    lambda t: t * t, xp, dev_flags, p)
                io_callback(publish, None, p, payload, dev_flags,
                            ordered=True)
                return dev_flags, None
            return lax.scan(step, dev_flags, jnp.arange(PARTS))[0]

        flags_out = jax.block_until_ready(
            sender(jnp.full((PARTS,), fl.RESERVED, jnp.int32)))
        assert [int(v) for v in flags_out] == [fl.PENDING] * PARTS
        rt.wait(req)
        rt.request_free(req)
        rt.barrier()
        print(f"ONCHIP_OK {platform}")
    else:
        # Plain host-side receive of the triggered send.
        got = np.zeros((ROWS, LANES), np.float32)
        r = rt.irecv_enqueue(got, source=peer, tag=5)
        rt.wait(r)
        np.testing.assert_array_equal(got, 3.0)

        # Bridge receive: poll the mirror, kernel decides arrival.
        buf = np.zeros((PARTS, ROWS, LANES), dtype=np.float32)
        req = rt.precv_init(buf, PARTS, source=peer)
        rt.start(req)
        idxs = jnp.arange(PARTS)
        deadline = time.time() + 120
        while int(fl.parrived_all(
                jnp.asarray(rt.fetch_partition_flags(req)), idxs)) != 1:
            if time.time() > deadline:
                raise TimeoutError("partitions never arrived")
            time.sleep(0.002)
        rt.wait(req)
        for p in range(PARTS):
            np.testing.assert_array_equal(buf[p], float((p + 2) ** 2))
        rt.request_free(req)
        rt.barrier()
        print(f"ONCHIP_OK {platform}")

    rt.finalize()


if __name__ == "__main__":
    main()
