#!/usr/bin/env python3
"""Read the numbers `correct` is decided on in an LFM2-MoE serving cell,
for the sound program and for its controls, over several seeds in ONE
process. This is how the limits in ``configs/lfm2_24b_a2b_serve.json``
were set and how to read them again; the benchmark's own runs never
call it (``control.py`` is the GPT-2 cells').

    python3 benchmarks/control_lfm2.py --workload lfm2_chat_burst --seeds 1,2,3 [--drop 1] [--flips 1]

Per seed: one burst of the cell's own traffic through the timed path as
the configuration states it (**sound**), with every number `correct`
compares; then, from the SAME burst, the controls that need no second
run: what its cache handed back **rounded to 8 bits** (keys and values a
token and head, tails a row: the nearest precision below bfloat16) and
its served tokens **altered** (each + 1). ``--drop N`` adds, for the
first N seeds, a second burst served with **the last expert of every
token left out** (the router's weight for it set to 0 underneath the
serve programs, which are traced anew for it): part of the mathematics
left out. ``--flips N`` adds, for the first N seeds, how often the
program's router (bfloat16 activations) and the reference's (float32)
choose another SET of experts for a token in a layer, over one finished
request run eagerly through both.
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

from benchmarks import harness, traffic, weights_lfm2  # noqa: E402


def to_8_bits(a: np.ndarray, axis: int) -> np.ndarray:
    """Symmetric 8-bit codes along ``axis`` and back."""
    s = np.maximum(np.abs(a).max(axis=axis, keepdims=True), 1e-30) / 127.0
    return np.clip(np.round(a / s), -127, 127) * s


def one_burst(e, params, cfg, c, cell, seed, warm: bool):
    gen = traffic.ServeBursts(cell.traffic, seed, c["vocab_size"])
    if warm:
        e.serve_burst(params, cfg, c["serve"], *gen.warmup())
    return [e.serve_burst(params, cfg, c["serve"], *gen.burst())]


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
                moe_live_expert_share=m.moe_live_expert_share,
                moe_load_max_over_mean=m.moe_load_max_over_mean,
                programs_traced=m.programs_traced, **facts, **more)
    return cached


def router_flips(e, params, cfg, c, seq: np.ndarray) -> dict:
    """Share of (token, MoE layer) pairs for which the program's router
    and the reference's select another set of experts, and the share of
    single selections that differ; both run eagerly on ``seq``."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import lfm2 as ref
    from mpi_acx_tpu.models import lfm2, moe
    got, want = [], []
    route, ref_route = moe.route_sigmoid_topk, ref.route

    def rec(*a, **kw):
        idx, p = route(*a, **kw)
        got.append(np.sort(np.asarray(idx), -1))
        return idx, p

    def ref_rec(*a, **kw):
        comb = ref_route(*a, **kw)
        want.append(np.sort(np.argsort(~(np.asarray(comb) > 0), -1,
                                       kind="stable")
                            [:, :c["num_experts_per_tok"]], -1))
        return comb
    moe.route_sigmoid_topk, ref.route = rec, ref_rec
    try:
        with jax.disable_jit():
            lfm2.forward(params, cfg, jnp.asarray(seq)[None])
            with jax.default_matmul_precision("highest"):
                ref._layers(params, jnp.asarray(seq), weights_lfm2.plan(c),
                            dict(ref.hyper(c)))
    finally:
        moe.route_sigmoid_topk, ref.route = route, ref_route
    got, want = np.stack(got), np.stack(want)          # [layers, T, k]
    return {"router_layers": int(got.shape[0]), "router_tokens": len(seq),
            "router_set_flip_share": float((got != want).any(-1).mean()),
            "router_choice_flip_share": float(np.mean(
                [len(set(g) - set(w)) for g, w in
                 zip(got.reshape(-1, got.shape[-1]),
                     want.reshape(-1, want.shape[-1]))])
                / got.shape[-1])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--drop", type=int, default=0)
    ap.add_argument("--flips", type=int, default=0)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    harness.require_chips(cell.cell["chips"])
    import jax
    from mpi_acx_tpu import backend
    from mpi_acx_tpu.models import moe
    backend.enable_compile_cache()
    from benchmarks.entries import serve_paged_greedy_lfm2 as e
    c = cell.config
    cfg = e.program_config(c, c["weights_dtype"])
    warmed = False                  # the process has its serve programs
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        params = weights_lfm2.make_lfm2(c, seed, cfg.dtype)
        bursts = one_burst(e, params, cfg, c, cell, seed, warm=not warmed)
        warmed = True
        done = e.finished(bursts)
        cached = read(e, params, c, bursts, seed, "sound")
        # the same burst, its cache rounded to 8 bits
        low = [(tok, to_8_bits(k, 2), to_8_bits(v, 2), to_8_bits(t, -1))
               for tok, k, v, t in cached]
        harness.say("control", seed=seed, leg="cache_in_8_bits",
                    **e.state_rms(params, c, low))
        # the same burst, every served token + 1
        for b in bursts:
            for rid, p in enumerate(b.prompts):
                out = np.array(b.outs[rid])
                out[len(p):] = (out[len(p):] + 1) % c["vocab_size"]
                b.outs[rid] = out
        read(e, params, c, bursts, seed, "altered_tokens", cached=cached)
        if n < a.flips:
            seq = max((s for s, _ in done), key=len)[:512]
            harness.say("control", seed=seed, leg="router_flips",
                        **router_flips(e, params, cfg, c, seq))
        if n < a.drop:
            route = moe.route_sigmoid_topk

            def dropping(*args, **kw):
                idx, p = route(*args, **kw)
                return idx, p.at[:, -1].set(0.0)
            moe.route_sigmoid_topk = dropping
            jax.clear_caches()
            try:
                bursts = one_burst(e, params, cfg, c, cell, seed, warm=True)
                read(e, params, c, bursts, seed, "last_expert_left_out")
            finally:
                moe.route_sigmoid_topk = route
                jax.clear_caches()
                warmed = False
        del bursts, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
