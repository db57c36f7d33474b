#!/usr/bin/env python3
"""Read the numbers `correct` is decided on in a Jamba serving cell, for
the sound program and for its controls, over several seeds in ONE
process. This is how the limits in ``configs/jamba2_3b_serve.json`` were
set and how to read them again; the benchmark's own runs never call it.

    python3 benchmarks/control_jamba.py --workload jamba2_reason_burst --seeds 1,2,3 [--broken 1]

Per seed: one burst of the cell's own traffic through the timed path as
the configuration states it (**sound**), with every number `correct`
compares; then, from the SAME burst, the controls that need no second
run: its cached pages and conv windows **rounded to 8 bits** (a token
and head, a row: the nearest precision below bfloat16) and its served
tokens **altered** (each + 1).
``--broken N`` adds, for the first N seeds, one more burst for each way
of :data:`BROKEN` the scan is broken underneath the serve programs
(which are traced anew for it): **the scan state carried in bfloat16**
(rounded after every token, in prefill and decode: the nearest precision
below the float32 the configuration states) and **the ``D u`` term left
out** (part of the mathematics left out).
Needs the chip the cell asks for, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks import harness, weights_jamba  # noqa: E402
from benchmarks.control_lfm2 import one_burst, to_8_bits  # noqa: E402


def _bf16_h(h, dt, u, z, b, c, a, d):
    """``ops.ssm._token`` with the state rounded to bfloat16 after the
    token, as a state carried in bfloat16 is."""
    import jax
    import jax.numpy as jnp
    h = jnp.exp(dt * a) * h + (dt * u) * b
    h = h.astype(jnp.bfloat16).astype(jnp.float32)
    y = jnp.sum(h * c, axis=0, keepdims=True) + d * u
    return h, y * (z * jax.nn.sigmoid(z))


def _no_d_u(h, dt, u, z, b, c, a, d):
    """``ops.ssm._token`` without its ``D u`` term."""
    import jax
    import jax.numpy as jnp
    h = jnp.exp(dt * a) * h + (dt * u) * b
    return h, jnp.sum(h * c, axis=0, keepdims=True) * (z * jax.nn.sigmoid(z))


BROKEN = {"bf16_h": _bf16_h, "no_D_u": _no_d_u}


def read(e, params, c, bursts, seed, leg, cached=None, **more):
    if cached is None:
        rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0xC4EC])
        cached = e.cached_state(bursts[-1], c, rng)
    m = bursts[-1].outs.metrics
    t0 = time.perf_counter()
    _, facts = e.compare(params, c, bursts, seed, cached=cached)
    harness.say("control", seed=seed, leg=leg,
                reference_seconds=time.perf_counter() - t0,
                failed=sum(e.failed_requests(b) for b in bursts),
                burst_s=bursts[-1].seconds, pages_hwm=m.pages_hwm,
                preemptions=m.preemptions, prefix_hits=m.prefix_hits,
                conv_tail_restores=m.conv_tail_restores,
                state_snapshots_taken=m.state_snapshots_taken,
                programs_traced=m.programs_traced, **facts, **more)
    return cached


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--broken", type=int, default=0)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    harness.require_chips(cell.cell["chips"])
    import jax
    from mpi_acx_tpu import backend
    from mpi_acx_tpu.ops import ssm
    backend.enable_compile_cache()
    from benchmarks.entries import serve_paged_greedy_jamba as e
    c = cell.config
    cfg = e.program_config(c, c["weights_dtype"])
    warmed = False                  # the process has its serve programs
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        params = weights_jamba.make_jamba(c, seed, cfg.dtype)
        bursts = one_burst(e, params, cfg, c, cell, seed, warm=not warmed)
        warmed = True
        cached = read(e, params, c, bursts, seed, "sound")
        # the same burst, its pages and windows rounded to 8 bits
        low = [(tok, to_8_bits(k, 2), to_8_bits(v, 2), h, to_8_bits(w, -1))
               for tok, k, v, h, w in cached]
        harness.say("control", seed=seed, leg="cache_in_8_bits",
                    **e.state_rms(params, c, low))
        # the same burst, every served token + 1
        for b in bursts:
            for rid, p in enumerate(b.prompts):
                out = np.array(b.outs[rid])
                out[len(p):] = (out[len(p):] + 1) % c["vocab_size"]
                b.outs[rid] = out
        read(e, params, c, bursts, seed, "altered_tokens", cached=cached)
        if n < a.broken:
            token = ssm._token
            for leg, broken in BROKEN.items():
                ssm._token = broken
                jax.clear_caches()
                try:
                    bursts = one_burst(e, params, cfg, c, cell, seed,
                                       warm=True)
                    read(e, params, c, bursts, seed, leg)
                finally:
                    ssm._token = token
                    jax.clear_caches()
                    warmed = False
        del bursts, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
