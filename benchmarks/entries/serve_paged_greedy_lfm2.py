"""Driver for LFM2-MoE configurations served through
``serving.serve_paged_greedy(..., family=lfm2)``: bursts served to
completion through the paged cache (GQA pages beside the conv layers'
fixed state), timed by the benchmark's own ``on_token`` clock, and
compared with the plain reference (``reference/lfm2.py``) once the
window has closed.

The window, the tracer and the request checks are the GPT-2 driver's
(``entries/serve_paged_greedy.py``, imported, nothing of it edited);
what is this family's own is the program config, the weights, the call
(``family=``) and what ``correct`` compares: the first attention
layer's pages, the first (conv) layer's page tails, the served tokens'
reference gaps (the widest and the mean), and that no tail was restored
where nothing is shared.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks import harness, traffic, weights_lfm2
from benchmarks.entries.serve_paged_greedy import (Burst, _Tracer,
                                                   failed_requests, finished)
from benchmarks.harness import check_line, say


def program_config(c: dict, dtype: str):
    """The program's own config object for an LFM2-MoE configuration
    file; ``dtype`` is what the entry computes in."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import lfm2
    held = c.get("experts_held", {})
    return lfm2.Lfm2Config(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        moe_d_ff=c["moe_intermediate_size"], n_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"], layer_types=tuple(c["layer_types"]),
        num_dense_layers=c["num_dense_layers"],
        conv_L_cache=c["conv_L_cache"], norm_eps=c["norm_eps"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        max_seq=c["max_position_embeddings"],
        experts_first=held.get("first", 0), experts_held=held.get("count"),
        dtype=jnp.dtype(dtype))


def serve_burst(params, cfg, s, prompts, n_new, tick=None) -> Burst:
    """One burst through ``serve_paged_greedy`` with the configuration's
    ``serve`` arguments ``s``; the result keeps the call's ``PagedKV``
    for the comparison (whoever keeps the burst drops it before the
    next call)."""
    from mpi_acx_tpu.models import lfm2, serving
    gc.collect()
    log = harness.TokenLog(len(prompts), time.perf_counter())

    def on_token(rid, tok):
        log.on_token(rid, tok)
        if tick is not None:
            tick(log, rid)

    outs = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=s["n_slots"],
        max_len=s["max_len"], family=lfm2, chunk=s["chunk"],
        kv_int8=s["kv_int8"], page_tokens=s["page_tokens"],
        n_pages=s["n_pages"], prefix_cache=s["prefix_cache"],
        on_token=on_token, max_request_retries=0, return_paged_state=True)
    return Burst(prompts, n_new, log, outs,
                 time.perf_counter() - log.t_handed)


def serve_window(params, cfg, s, gen, seconds, tracer=None) -> list:
    """Burst after burst until ``seconds`` have passed; only the last
    burst keeps its page pool."""
    bursts, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if bursts:
            bursts[-1].outs.paged_state = None
        bursts.append(serve_burst(params, cfg, s, *gen.burst(),
                                  tick=tracer.tick if tracer else None))
    return bursts


def served_gaps(tree, c, seq: np.ndarray, n_prompt: int) -> np.ndarray:
    """Per served token of one finished request: how far its reference
    logit lies below the reference's best (0: the reference's own
    choice)."""
    import jax.numpy as jnp
    from benchmarks.reference import lfm2 as ref
    T = min(-(-len(seq) // 256) * 256, c["serve"]["max_len"])
    padded = jnp.asarray(np.pad(seq, (0, T - len(seq))).astype(np.int32))
    n_served = len(seq) - n_prompt
    rows = jnp.zeros((-(-n_served // 64) * 64,), jnp.int8)
    first = min(n_prompt - 1, T - rows.shape[0])
    skip = n_prompt - 1 - first
    got = np.asarray(ref.logits_from(
        tree, padded, first, rows, plan=weights_lfm2.plan(c),
        hp=ref.hyper(c)))[skip:][:n_served]
    return got.max(-1) - got[np.arange(n_served), seq[n_prompt:]]


def cached_state(burst: Burst, c: dict, rng) -> list:
    """What the burst's call left in its prefix cache, for up to
    ``check.kv_prompts`` prompts that it still holds ``check.kv_pages``
    whole pages of, read through the calls the serve loop itself makes
    on a prefix hit (``prefix.match``, ``gather_history``,
    ``restore_tail``): [(tokens [n_tok], k, v [L_attn, Hkv, Dh, n_tok],
    tails [pages, L_conv, taps, d])]."""
    pkv, chk = burst.outs.paged_state, c["check"]
    n_tok = chk["kv_pages"] * c["serve"]["page_tokens"]
    out, seen = [], set()
    for rid in rng.permutation(len(burst.prompts)):
        head = burst.prompts[rid][:n_tok + 1]
        if len(head) <= n_tok or head[:n_tok].tobytes() in seen:
            continue
        pages = pkv.prefix.match(head)
        if len(pages) < chk["kv_pages"]:
            continue
        seen.add(head[:n_tok].tobytes())
        k, v = pkv.gather_history(pages)
        tails = np.stack([np.asarray(pkv.restore_tail(p), np.float32)
                          for p in pages])
        out.append((head[:n_tok], np.asarray(k, np.float32),
                    np.asarray(v, np.float32), tails))
        if len(out) == chk["kv_prompts"]:
            break
    return out


def state_rms(tree, c, cached: list) -> dict:
    """Relative RMS error of the FIRST attention layer's cached keys and
    values together, and of the FIRST conv layer's page tails, against
    the reference's (``reference.lfm2.states``): nothing but the
    cache's own precision and one or two matmuls' rounding separates
    them there; deeper layers carry the bf16 residual stream's."""
    import jax.numpy as jnp
    from benchmarks.reference import lfm2 as ref
    plan = weights_lfm2.plan(c)
    ops = [e[0] for e in plan]
    upto = 1 + max(ops.index("full_attention"), ops.index("conv"))
    pt, taps = c["serve"]["page_tokens"], c["conv_L_cache"] - 1
    sums = np.zeros(4)
    for tokens, k, v, tails in cached:
        rk, rv, rz = (np.asarray(a[0], np.float64) for a in ref.states(
            tree, jnp.asarray(tokens), plan=plan, hp=ref.hyper(c),
            upto=upto))
        # cache layout [Hkv, Dh, T] -> the reference's [T, Hkv, Dh]
        for got, want in ((k[0], rk), (v[0], rv)):
            sums[0] += np.square(got.transpose(2, 0, 1) - want).sum()
            sums[1] += np.square(want).sum()
        for j in range(tails.shape[0]):
            want = rz[(j + 1) * pt - taps:(j + 1) * pt]
            sums[2] += np.square(tails[j, 0] - want).sum()
            sums[3] += np.square(want).sum()
    return {"kv_page_rms": float(np.sqrt(sums[0] / sums[1])),
            "conv_tail_rms": float(np.sqrt(sums[2] / sums[3]))}


def compare(tree, c, bursts, seed, cached=None) -> tuple:
    """(correct, facts), each number printed beside its limit.

    ``kv_page_rms`` and ``conv_tail_rms``: the pages and the page tails
    of the window's last call against the reference's keys, values and
    gated conv inputs, in the first layer of each kind: the numbers a
    lower cache precision fails. ``widest_gap``: over a sample of the
    finished requests drawn from the seed, the longest among them, the
    widest gap by which a served token's reference logit lies below the
    reference's best: the number a wrong token fails; ``mean_gap``,
    the same gaps' mean: the number part of the mathematics left out
    fails (a router's near-tie flips an expert between the bfloat16
    program and the float32 reference in one (token, layer) in ten, so
    the sound program's WIDEST gap reads as wide as a dropped expert's;
    its mean a sixth: ``limits_from``). ``cached`` replaces what is read
    from the last call's cache (the control's 8-bit rounding)."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0xC4EC])
    lim, chk = c["limits"], c["check"]
    if cached is None:
        cached = cached_state(bursts[-1], c, rng)
    bursts[-1].outs.paged_state = None
    gc.collect()
    if not cached:
        return check_line("kv_pages_compared", 0, ">0", False), {}
    facts = state_rms(tree, c, cached)
    ok = True
    for name in ("kv_page_rms", "conv_tail_rms"):
        ok &= check_line(name, facts[name], lim[name],
                         facts[name] <= lim[name])
    done = finished(bursts)
    if not done:
        return check_line("served_tokens_compared", 0, ">0", False), facts
    longest = max(range(len(done)), key=lambda i: len(done[i][0]))
    pick = [longest] + [i for i in rng.permutation(len(done))
                        if i != longest][:chk["served_requests"] - 1]
    g = np.concatenate([served_gaps(tree, c, *done[i]) for i in pick])
    facts.update(requests=len(pick), tokens=int(g.size),
                 widest_gap=float(g.max()), mean_gap=float(g.mean()),
                 flipped_share=float((g > 0).mean()))
    for name in ("widest_gap", "mean_gap"):
        ok &= check_line(name, facts[name], lim[name],
                         facts[name] <= lim[name])
    say("compared", **facts)
    return ok, facts


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    c, s = cell.config, cell.config["serve"]
    cfg = program_config(c, c["weights_dtype"])
    import jax
    gen = traffic.ServeBursts(cell.traffic, seed, c["vocab_size"])
    t_in = time.perf_counter()
    with harness.Watch() as setup_watch:
        params = jax.block_until_ready(
            weights_lfm2.make_lfm2(c, seed, cfg.dtype))
        t_weights = time.perf_counter()
        warm = serve_burst(params, cfg, s, *gen.warmup())
    if failed_requests(warm):
        raise RuntimeError("the warm-up burst did not finish its requests")
    del warm
    # What the process holds by now (modules, traced programs: ~265 k
    # objects, 80-125 ms a full collection) goes to the permanent
    # generation: the collection each burst starts with scans only what
    # the window made. Three of them stood between the window's start
    # and its fourth burst, which then started 0.1-0.4 s before
    # ``seconds`` were up, and without them 0.6-0.9 s (PERF.md,
    # question 14).
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    say("setup", setup_s=setup_s, reach_chip_s=t_in - t_start,
        weights_s=t_weights - t_in, warm_burst_s=setup_watch.wall_s
        - (t_weights - t_in), compile_s=setup_watch.compile_s,
        cache_hits=setup_watch.hits, cache_misses=setup_watch.misses)

    logdir = os.path.join(cell.root, ".bench_trace", cell.name)
    with harness.Watch() as window_watch:
        t0 = time.perf_counter()
        tracer = _Tracer(logdir) if trace else None
        bursts = serve_window(params, cfg, s, gen, seconds, tracer)
        window_s = time.perf_counter() - t0
    gc.unfreeze()
    if tracer:
        tracer.stop()
    peak = harness.memory_peak_bytes()

    attempted = sum(len(b.prompts) for b in bursts)
    failed = sum(failed_requests(b) for b in bursts)
    tokens = sum(sum(b.log.count) for b in bursts)
    ttft = [t for b in bursts for t in b.log.ttft_s() if t is not None]
    tpot = [t for b in bursts for t in b.log.tpot_s()]
    m = [b.outs.metrics for b in bursts]
    say("window", window_s=window_s, bursts=len(bursts), requests=attempted,
        failed=failed, tokens=tokens, ttft_samples=len(ttft),
        tpot_samples=len(tpot),
        tpot_p95_ms=1e3 * harness.percentile(tpot, 0.95),
        compiles=window_watch.misses,
        programs_loaded=window_watch.hits, load_s=window_watch.compile_s,
        programs_traced=[x.programs_traced for x in m],
        burst_s=[round(b.seconds, 3) for b in bursts],
        preemptions=sum(x.preemptions for x in m),
        requeues=sum(x.requeues for x in m),
        rejections=sum(x.rejections for x in m),
        prefix_hits=sum(x.prefix_hits for x in m),
        conv_tail_restores=sum(x.conv_tail_restores for x in m),
        pages_hwm=max(x.pages_hwm for x in m), n_pages=s["n_pages"],
        paged_operator=m[0].paged_operator, paged_ffn=m[0].paged_ffn,
        paged_kv_write=m[0].paged_kv_write,
        paged_decode_attend=m[0].paged_decode_attend,
        moe_live_expert_share=[round(x.moe_live_expert_share, 4) for x in m],
        moe_load_max_over_mean=[round(x.moe_load_max_over_mean, 3)
                                for x in m],
        moe_pairs_a_layer_step=[round(x.moe_assignments
                                      / max(x.moe_layer_steps, 1), 1)
                                for x in m])

    ok = check_line("failed_requests", failed, 0, failed == 0)
    turned = sum(x.requeues + x.rejections + x.preemptions for x in m)
    ok &= check_line("requeues_rejections_preemptions", turned, 0,
                     turned == 0)
    if not cell.traffic["prefixes"]:    # nothing shared: a hit is a fault
        hits = sum(x.prefix_hits + x.conv_tail_restores for x in m)
        ok &= check_line("prefix_hits_and_tail_restores_with_nothing_shared",
                         hits, 0, hits == 0)
    t_ref = time.perf_counter()
    right, _ = compare(params, c, bursts, seed)
    ok &= right
    say("reference", seconds=time.perf_counter() - t_ref)

    end_to_end = {
        "serve_tok_s": tokens / window_s,
        "ttft_p95_ms": 1e3 * harness.percentile(ttft, 0.95),
        "tpot_p95_ms": 1e3 * harness.percentile(tpot, 0.95),
        "setup_s": setup_s,
    }
    return {"correct": ok, "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": peak,
            "bursts": bursts, "window_s": window_s,
            "window_watch": window_watch, "trace_dir": logdir,
            "traced": ((tracer.t0, tracer.t1, tracer.pauses)
                       if tracer and tracer.state == "done" else None),
            "config": c, "traffic": cell.traffic}
