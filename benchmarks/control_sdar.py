#!/usr/bin/env python3
"""Read the numbers `correct` is decided on in an SDAR-MoE serving cell,
for the sound program and for its controls, over several seeds in ONE
process. This is how the limits in ``configs/sdar_30b_a3b_pp8_serve.json``
were set and how to read them again; the benchmark's own runs never call
it.

    python3 benchmarks/control_sdar.py --workload sdar_chat_block_burst --seeds 1,2,3 [--broken 1] [--legs no_store,causal_block]

Per seed: one burst of the cell's own traffic through the timed path as
the configuration states it (**sound**), with every number `correct`
compares; then, from the SAME burst, what its pool handed back **rounded
to 8 bits** a token and head (the nearest precision below bfloat16).
``--broken N`` adds, for the first N seeds, a burst a leg served with
the timed path broken underneath (each leg traces every program anew:
minutes):

* ``no_store``: the fifth forward left out: a block's K/V are what its
  last denoising forward that still held a mask left in the stage;
* ``causal_block``: a causal mask inside the block while it is denoised
  (the attend's window form, each row seeing the block up to itself);
* ``causal_prefill``: the prompt attended causally, not block-causally;
* ``drop_expert``: every position's eighth expert left out (its weight
  set to 0 behind the router);
* ``plain_softmax``: the chosen experts' softmax weights NOT divided by
  their sum (``norm_topk_prob`` ignored);
* ``kv_int8``: the program's own ``kv_int8=True`` pages.

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

from benchmarks import harness, traffic, weights_sdar  # noqa: E402

LEGS = ("no_store", "causal_block", "causal_prefill", "drop_expert",
        "plain_softmax", "kv_int8")


def to_8_bits(a: np.ndarray, axis: int) -> np.ndarray:
    """Symmetric 8-bit codes along ``axis`` and back."""
    s = np.maximum(np.abs(a).max(axis=axis, keepdims=True), 1e-30) / 127.0
    return np.clip(np.round(a / s), -127, 127) * s


def break_program(setattr_, leg: str) -> dict:
    """Break the timed path underneath for ``leg`` by replacing
    attributes of the program's modules through ``setattr_(obj, name,
    value)`` (a test hands ``monkeypatch.setattr``, which also undoes
    it). Returns the serve arguments the leg overrides. The caller
    clears jit's caches."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import kvpage, moe
    from mpi_acx_tpu.ops import attention, flash_decode
    if leg == "kv_int8":
        return {"kv_int8": True}
    if leg == "no_store":
        real = kvpage.paged_block_forward

        def forward(params, cfg, state, tokens, page_tokens, family):
            x, out = real(params, cfg, state, tokens, page_tokens, family)
            spec = kvpage.paged_spec(family, cfg)
            done = ~(tokens == spec.mask_token).any(-1)         # [B]
            (old, row), (new, _) = state["stage"], out["stage"]

            def keep(o, n):
                return jnp.where(
                    done.reshape((1, -1) + (1,) * (n.ndim - 2)), o, n)
            return x, dict(out, stage=(tuple(map(keep, old, new)), row))
        setattr_(kvpage, "paged_block_forward", forward)
    elif leg == "causal_block":
        select = flash_decode.select_paged_decode_attend

        def causal(decode_flash, page_tokens):
            attend = select(decode_flash, page_tokens)

            def windowed(q, kp, vp, table, pos, pt, n_rep, layer=None,
                         stage=None, left=None, **kw):
                Hkv = (kp[0] if isinstance(kp, tuple) else kp).shape[-3]
                B, D = q.shape[0], q.shape[-1]
                W = 4
                r = n_rep // W
                q = q.reshape(B, Hkv, W, r, D).transpose(0, 2, 1, 3, 4)
                o = attend(q.reshape(B, W, Hkv * r, D), kp, vp, table,
                           pos - (W - 1), pt, r, layer=layer,
                           stage=(stage[0], stage[1] - (W - 1)),
                           left=None if left is None else left - (W - 1),
                           **kw)
                return o.reshape(B, W, Hkv, r * D).transpose(
                    0, 2, 1, 3).reshape(B, 1, -1)
            return windowed
        setattr_(flash_decode, "select_paged_decode_attend", causal)
    elif leg == "causal_prefill":
        setattr_(attention, "select_block_attention",
                 lambda use_flash, block: attention.select_attention(
                     use_flash))
    elif leg in ("drop_expert", "plain_softmax"):
        route = moe.route_softmax_topk

        def broken(x, gate, top_k, normalise=True):
            if leg == "plain_softmax":
                return route(x, gate, top_k, False)
            idx, p = route(x, gate, top_k, normalise)
            return idx, p.at[:, -1].set(0.0)
        setattr_(moe, "route_softmax_topk", broken)
    else:
        raise ValueError(f"unknown leg {leg!r} (has {LEGS})")
    return {}


def one_burst(e, params, cfg, c, cell, seed, warm: bool, **over):
    gen = traffic.ServeBursts(cell.traffic, seed, c["vocab_size"])
    if warm:
        e.serve_burst(params, cfg, c["serve"], *gen.warmup(), **over)
    return [e.serve_burst(params, cfg, c["serve"], *gen.burst(), **over)]


def read(e, params, c, bursts, seed, leg) -> dict:
    m = bursts[-1].outs.metrics
    failed = sum(e.failed_requests(b) for b in bursts)
    t0, kept = time.perf_counter(), {}
    _, facts = e.compare(params, c, bursts, seed, keep=kept)
    harness.say("control", seed=seed, leg=leg,
                reference_seconds=time.perf_counter() - t0, failed=failed,
                burst_s=bursts[-1].seconds, pages_hwm=m.pages_hwm,
                preemptions=m.preemptions, prefix_hits=m.prefix_hits,
                moe_live_expert_share=m.moe_live_expert_share,
                programs_traced=m.programs_traced, **facts)
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--broken", type=int, default=0)
    ap.add_argument("--legs", default=",".join(LEGS))
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    harness.require_chips(cell.cell["chips"])
    import jax
    from mpi_acx_tpu import backend
    backend.enable_compile_cache()
    from benchmarks.entries import serve_paged_greedy_sdar as e
    c = cell.config
    cfg = e.program_config(c, c["weights_dtype"])
    warmed = False                  # the process has its serve programs
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        params = weights_sdar.make_sdar(c, seed, cfg.dtype)
        bursts = one_burst(e, params, cfg, c, cell, seed, warm=not warmed)
        warmed = True
        kept = read(e, params, c, bursts, seed, "sound")
        # the same burst, its pages rounded to 8 bits a token and head
        if kept:
            low = [(s, p, at, to_8_bits(k, 2), to_8_bits(v, 2))
                   for s, p, at, k, v in kept["cached"]]
            harness.say("control", seed=seed, leg="cache_in_8_bits",
                        requests=len(low), **e.kv_rms(low, kept["refs"], c))
        del kept
        if n < a.broken:
            for leg in a.legs.split(","):
                undo = []

                def set_(obj, name, value):
                    undo.append((obj, name, getattr(obj, name)))
                    setattr(obj, name, value)
                over = break_program(set_, leg)
                jax.clear_caches()
                try:
                    bursts = one_burst(e, params, cfg, c, cell, seed,
                                       warm=True, **over)
                    read(e, params, c, bursts, seed, leg)
                finally:
                    for obj, name, value in reversed(undo):
                        setattr(obj, name, value)
                    jax.clear_caches()
                    warmed = False
        del bursts, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
