#!/usr/bin/env python3
"""Read the numbers `correct` is decided on in a GigaChat3 serving cell,
for the sound program and for its controls, over several seeds in ONE
process. This is how the limits in
``configs/gigachat31_702b_ep16_serve.json`` were set and how to read
them again; the benchmark's own runs never call it.

    python3 benchmarks/control_gigachat.py --workload gigachat_doc_qa_burst --seeds 1,2,3 [--broken 1]

Per seed: one burst of the cell's own traffic through the timed path as
the configuration states it (**sound**), with every number `correct`
compares; then, from the SAME burst, the controls that need no second
run: its cached latent rows **rounded to 8 bits** a token (the nearest
precision below bfloat16) and its served tokens **altered** (each + 1).
``--broken N`` adds, for the first N seeds, one more burst for each way
of :data:`BROKEN` (``--legs``) part of the mathematics is left out
underneath the serve programs (which are traced anew for it): **one held
expert left out** (the first held one's weight set to 0), **every held
expert left out**,
**the shared expert left out**, and **plain top-k** in place of the
group-limited selection. ``--altered N``: for how many seeds the served
tokens are altered (the reference runs again for them).
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

from benchmarks import harness, weights_gigachat  # noqa: E402
from benchmarks.control_lfm2 import one_burst, to_8_bits  # noqa: E402


def _route_with(change):
    """``moe.route_sigmoid_group_topk`` with ``change(idx, p)`` on what
    it returns."""
    from mpi_acx_tpu.models import moe
    route = moe.route_sigmoid_group_topk

    def changed(*a, **kw):
        idx, p, kept = route(*a, **kw)
        return idx, change(idx, p), kept
    return changed


def _one_expert_left_out(patch):
    """The held expert 0 contributes nothing, whoever chose it."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import moe
    patch.setattr(moe, "route_sigmoid_group_topk", _route_with(
        lambda idx, p: jnp.where(idx == 0, 0.0, p)))


def _held_experts_left_out(patch):
    """The whole routed part left out: no held expert contributes."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import moe
    patch.setattr(moe, "route_sigmoid_group_topk", _route_with(
        lambda idx, p: jnp.zeros_like(p)))


def _no_shared_expert(patch):
    import jax.numpy as jnp
    from mpi_acx_tpu.models import gigachat
    patch.setattr(gigachat, "_shared_ffn",
                  lambda cfg, lp, u: jnp.zeros_like(u))


def _plain_topk(patch):
    """The ``top_k`` best biased scores of ALL the experts, no group
    dropped (the router ``route_sigmoid_topk``; every group "kept")."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import moe

    def plain(x, gate, bias, top_k, n_group, topk_group, scale=1.0,
              normalise=True):
        idx, p = moe.route_sigmoid_topk(x, gate, bias, top_k, scale,
                                        normalise)
        return idx, p, jnp.ones((x.shape[0], n_group), bool)
    patch.setattr(moe, "route_sigmoid_group_topk", plain)


def _pages_in_8_bits(patch):
    """What the cache hands back, rounded to 8 bits a token: no second
    burst needs it (``main`` rounds the sound burst's rows); the tests
    patch the entry's read."""
    from benchmarks.entries import serve_paged_greedy_gigachat as e
    read = e.cached_state
    patch.setattr(e, "cached_state", lambda *a: [
        (tok, to_8_bits(rows, 1)) for tok, rows in read(*a)])


BROKEN = {"one_expert_left_out": _one_expert_left_out,
          "held_experts_left_out": _held_experts_left_out,
          "no_shared_expert": _no_shared_expert, "plain_topk": _plain_topk,
          "pages_in_8_bits": _pages_in_8_bits}


class _Patches:
    """``setattr`` / ``undo`` as pytest's ``monkeypatch`` has them."""

    def __init__(self):
        self.was = []

    def setattr(self, obj, name, value):
        self.was.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        for obj, name, value in reversed(self.was):
            setattr(obj, name, value)
        self.was = []


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
                moe_pairs_routed=m.moe_assignments,
                moe_pairs_held=m.moe_pairs_held,
                moe_group_hits=m.moe_group_hits,
                moe_live_expert_share=m.moe_live_expert_share,
                programs_traced=m.programs_traced, **facts, **more)
    return cached


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--broken", type=int, default=0)
    ap.add_argument("--altered", type=int, default=1)
    ap.add_argument("--legs", default=",".join(
        k for k in BROKEN if k != "pages_in_8_bits"))
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    harness.require_chips(cell.cell["chips"])
    import jax
    from mpi_acx_tpu import backend
    backend.enable_compile_cache()
    from benchmarks.entries import serve_paged_greedy_gigachat as e
    c = cell.config
    cfg = e.program_config(c, c["weights_dtype"])
    warmed = False                  # the process has its serve programs
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        params = weights_gigachat.make_gigachat(c, seed, cfg.dtype)
        bursts = one_burst(e, params, cfg, c, cell, seed, warm=not warmed)
        warmed = True
        cached = read(e, params, c, bursts, seed, "sound")
        # the same burst, its rows rounded to 8 bits a token
        low = [(tok, to_8_bits(rows, 1)) for tok, rows in cached]
        harness.say("control", seed=seed, leg="pages_in_8_bits",
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
