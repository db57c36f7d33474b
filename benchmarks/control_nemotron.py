#!/usr/bin/env python3
"""Read the numbers `correct` is decided on in a Nemotron-H serving cell,
for the sound program and for its controls, over several seeds in ONE
process. This is how the limits in
``configs/nemotron3_super_120b_ep4_serve.json`` were set and how to read
them again; the benchmark's own runs never call it.

    python3 benchmarks/control_nemotron.py --workload nemotron3_agent_burst --seeds 1,2,3 [--broken 1]

Per seed: one burst of the cell's own traffic through the timed path as
the configuration states it (**sound**), with every number `correct`
compares; then, from the SAME burst, the controls that need no second
run: its cached pages and conv windows **rounded to 8 bits** (the nearest
precision below bfloat16) and its served tokens **altered** (each + 1;
``--altered N``: for how many seeds).
``--broken N`` adds, for the first N seeds, one more burst for each way
of :data:`BROKEN` (``--legs``) the program is broken underneath the
serve programs (which are traced anew for it): **the SSM state kept in
bfloat16** (rounded after every token, prefill and decode: the nearest
precision below the float32 the configuration states), **the shared
expert left out**, **every held expert left out**, **plain top-22**
without the selection bias, and **the snapshot restored from the wrong
row**.
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

from benchmarks import harness, weights_nemotron  # noqa: E402
from benchmarks.control_gigachat import _Patches, _route_with  # noqa: E402
from benchmarks.control_lfm2 import one_burst, to_8_bits  # noqa: E402


def _bf16_state(patch):
    """The recurrence's state rounded to bfloat16 after every token: the
    prefill scans token by token in plain JAX with the rounding inside,
    the decode update's layer is rounded behind the call.
    ``lax.reduce_precision``, not a cast there and back: that the
    compiler may not take out (a float32 -> bfloat16 -> float32 pair
    inside one fusion left the chip's state as it was: PERF.md, PR 44)."""
    from jax import lax
    from mpi_acx_tpu.ops import ssd
    token, pair = ssd._token, ssd.select_ssd

    def low(h):
        return lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)

    def rounded(h, *a):
        h, y = token(h, *a)
        return low(h), y

    def select(use_kernel):
        update = pair(use_kernel)[0]

        def update_low(h, layer, *a):
            y, h = update(h, layer, *a)
            return y, lax.dynamic_update_index_in_dim(
                h, low(lax.dynamic_index_in_dim(h, layer, 0)), layer, 0)
        return update_low, ssd.ssd_scan_ref
    patch.setattr(ssd, "_token", rounded)
    patch.setattr(ssd, "select_ssd", select)


def _no_shared_expert(patch):
    import jax.numpy as jnp
    from mpi_acx_tpu.models import nemotron_h
    patch.setattr(nemotron_h, "_shared_ffn",
                  lambda cfg, lp, u: jnp.zeros(u.shape, jnp.float32))


def _held_experts_left_out(patch):
    """The whole routed part left out: no held expert contributes."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import moe
    patch.setattr(moe, "route_sigmoid_group_topk", _route_with(
        lambda idx, p: jnp.zeros_like(p)))


def _plain_topk(patch):
    """The ``top_k`` best scores themselves: the selection bias left
    out."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import moe
    route = moe.route_sigmoid_group_topk
    patch.setattr(moe, "route_sigmoid_group_topk",
                  lambda x, gate, bias, *a, **kw: route(
                      x, gate, jnp.zeros_like(bias), *a, **kw))


def _wrong_snapshot_row(patch):
    """A hit's suffix prefill starts from ANOTHER page's snapshot (the
    lowest other row the store holds)."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import kvpage

    def restore(self, page):
        others = sorted(set(self.snaps.row_of.values())
                        - {self.snaps.row_of[page]})
        self.tail_restores += 1
        return kvpage._tail(self.snaps.rows, jnp.int32(
            others[0] if others else self.snaps.sink))
    patch.setattr(kvpage.PagedKV, "restore_tail", restore)


BROKEN = {"bf16_state": _bf16_state, "no_shared_expert": _no_shared_expert,
          "held_experts_left_out": _held_experts_left_out,
          "plain_topk": _plain_topk,
          "wrong_snapshot_row": _wrong_snapshot_row}


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
                state_snapshot_seats=m.state_snapshot_seats,
                state_snapshots_taken=m.state_snapshots_taken,
                moe_pairs_routed=m.moe_assignments,
                moe_pairs_held=m.moe_pairs_held,
                moe_live_expert_share=m.moe_live_expert_share,
                programs_traced=m.programs_traced, **facts, **more)
    return cached


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--broken", type=int, default=0)
    ap.add_argument("--altered", type=int, default=1)
    ap.add_argument("--legs", default=",".join(BROKEN))
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    harness.require_chips(cell.cell["chips"])
    import jax
    from mpi_acx_tpu import backend
    backend.enable_compile_cache()
    from benchmarks.entries import serve_paged_greedy_nemotron as e
    c = cell.config
    cfg = e.program_config(c, c["weights_dtype"])
    warmed = False                  # the process has its serve programs
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        params = weights_nemotron.make_nemotron(c, seed, cfg.dtype)
        bursts = one_burst(e, params, cfg, c, cell, seed, warm=not warmed)
        warmed = True
        cached = read(e, params, c, bursts, seed, "sound")
        # the same burst, its pages and windows rounded to 8 bits
        low = [(tok, to_8_bits(k, 2), to_8_bits(v, 2), h, to_8_bits(w, -1))
               for tok, k, v, h, w in cached]
        harness.say("control", seed=seed, leg="cache_in_8_bits",
                    **e.state_rms(params, c, low))
        # the same burst, every served token + 1
        for b in bursts if n < a.altered else ():
            for rid, p in enumerate(b.prompts):
                out = np.array(b.outs[rid])
                out[len(p):] = (out[len(p):] + 1) % c["vocab_size"]
                b.outs[rid] = out
        if n < a.altered:
            read(e, params, c, bursts, seed, "altered_tokens", cached=cached)
        if n < a.broken:
            for leg in a.legs.split(","):
                patch = _Patches()
                BROKEN[leg](patch)
                jax.clear_caches()
                try:
                    bursts = one_burst(e, params, cfg, c, cell, seed,
                                       warm=True)
                    read(e, params, c, bursts, seed, leg)
                finally:
                    patch.undo()
                    jax.clear_caches()
                    warmed = False
        del bursts, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
