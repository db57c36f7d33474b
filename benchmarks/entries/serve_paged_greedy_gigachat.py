"""Driver for GigaChat3 (``deepseek_v3``) configurations served through
``serving.serve_paged_greedy(..., family=gigachat)``: bursts served to
completion through the paged cache (a LATENT page pool: one ``[c_kv |
k_rope]`` row a token a layer, read by all the heads), timed by the
benchmark's own ``on_token`` clock, and compared with the plain
reference (``reference/gigachat.py``) once the window has closed.

The window, the tracer and the request checks are the Jamba driver's
and the GPT-2 driver's (imported, nothing of them edited: the traced
piece is the LAST REFILL of burst 0, a suffix prefill behind a radix
hit, and the decode chunk after it); what is this family's own is the
program config, the weights, the call (``family=``) and what ``correct``
compares: the first layer's latent pages of cached prompts
(``latent_page_rms``), and the served tokens' reference gaps (the widest
and the mean) over requests of BOTH kinds, cold prefills and suffix
prefills behind a hit, so that prefill, suffix prefill and the absorbed
decode through the cache are all held to the reference's full forward.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks import flops_gigachat, harness, traffic, weights_gigachat
from benchmarks.entries.serve_paged_greedy import Burst, failed_requests
from benchmarks.entries.serve_paged_greedy_jamba import _RefillTracer
from benchmarks.harness import check_line, say


def program_config(c: dict, dtype: str):
    """The program's own config object for a GigaChat3 configuration
    file; ``dtype`` is what the entry computes in."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import gigachat
    first, count, width = weights_gigachat.held(c)
    rs = c["rope_scaling"]
    return gigachat.GigaChatConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_layers=c["num_hidden_layers"],
        first_k_dense=c["first_k_dense_replace"], n_experts=width,
        top_k=c["num_experts_per_tok"], n_group=c["n_group"],
        topk_group=c["topk_group"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]), norm_eps=c["rms_norm_eps"],
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        max_seq=c["max_position_embeddings"], experts_first=first,
        experts_held=None if count == width else count,
        moe_block=c.get("serve", {}).get("moe_block", 2048),
        dtype=jnp.dtype(dtype))


def serve_burst(params, cfg, s, prompts, n_new, tick=None) -> Burst:
    """One burst through ``serve_paged_greedy`` with the configuration's
    ``serve`` arguments ``s``; the result keeps the call's ``PagedKV``
    for the comparison (whoever keeps the burst drops it before the
    next call)."""
    from mpi_acx_tpu.models import gigachat, serving
    gc.collect()
    log = harness.TokenLog(len(prompts), time.perf_counter())

    def on_token(rid, tok):
        log.on_token(rid, tok)
        if tick is not None:
            tick(log, rid)

    outs = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=s["n_slots"],
        max_len=s["max_len"], family=gigachat, chunk=s["chunk"],
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
    from benchmarks.reference import gigachat as ref
    # ONE shape for every request: the reference is a program a shape
    T = c["serve"]["max_len"]
    padded = jnp.asarray(np.pad(seq, (0, T - len(seq))).astype(np.int32))
    n_served = len(seq) - n_prompt
    rows = jnp.zeros((min(T, -(-c["check"]["served_rows"] // 64) * 64),),
                     jnp.int8)
    assert n_served <= rows.shape[0], (n_served, rows.shape)
    first = min(n_prompt - 1, T - rows.shape[0])
    skip = n_prompt - 1 - first
    got = np.asarray(ref.logits_from(
        tree, padded, first, rows, plan=weights_gigachat.plan(c),
        hp=ref.hyper(c)))[skip:][:n_served]
    return got.max(-1) - got[np.arange(n_served), seq[n_prompt:]]


def cached_state(burst: Burst, c: dict, rng) -> list:
    """What the burst's call left in its prefix cache, for up to
    ``check.kv_prompts`` different documents that it still holds
    ``check.kv_pages`` whole pages of, read through the calls the serve
    loop itself makes on a prefix hit (``prefix.match``,
    ``gather_history``): [(tokens [n_tok], rows [L, row_dim, n_tok])]."""
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
        rows, none = pkv.gather_history(pages)
        assert none is None, "a latent pool has no V to gather"
        out.append((head[:n_tok], np.asarray(rows, np.float32)[:, 0]))
        if len(out) == chk["kv_prompts"]:
            break
    return out


def state_rms(tree, c, cached: list) -> dict:
    """Relative RMS error of the FIRST layer's cached rows ``[c_kv |
    k_rope]`` against the reference's (``reference.gigachat.states``):
    nothing but the cache's own precision and two matmuls' rounding
    separates them there; deeper layers carry the bf16 residual
    stream's."""
    import jax.numpy as jnp
    from benchmarks.reference import gigachat as ref
    sums = np.zeros(2)
    for tokens, rows in cached:
        want = np.asarray(ref.states(
            tree, jnp.asarray(tokens), plan=weights_gigachat.plan(c),
            hp=ref.hyper(c), upto=1)[0], np.float64)        # [T, row_dim]
        sums += (np.square(rows[0].T - want).sum(), np.square(want).sum())
    return {"latent_page_rms": float(np.sqrt(sums[0] / sums[1]))}


def finished_by_kind(bursts) -> tuple:
    """((tokens, prompt length) of every request the window finished
    whose prompt was prefilled whole, the same for those prefilled
    behind a radix hit): from the serve call's own span record."""
    cold, hit = [], []
    for b in bursts:
        pages = {sp.ids["rid"]: sp.ids.get("hit_pages", 0)
                 for sp in b.outs.metrics.spans
                 if sp.name == "refill.prefill"}
        for rid, p in enumerate(b.prompts):
            if isinstance(b.outs[rid], np.ndarray):
                (hit if pages.get(rid) else cold).append(
                    (b.outs[rid], len(p)))
    return cold, hit


def compare(tree, c, bursts, seed, cached=None) -> tuple:
    """(correct, facts), each number printed beside its limit.

    ``latent_page_rms``: the first layer's latent pages of the window's
    last call against the reference's rows: the number a lower cache
    precision fails. ``widest_gap``: over ``check.served_requests``
    finished requests drawn from the seed, ``check.served_cold`` of them
    prefilled whole and the others behind a hit, the widest gap by
    which a served token's reference logit lies below the reference's
    best: the number a wrong token fails; ``mean_gap``, the same gaps'
    mean: the number part of the mathematics left out fails. ``cached``
    replaces what is read from the last call's cache (a control's
    rounding)."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0xC4EC])
    lim, chk = c["limits"], c["check"]
    if cached is None:
        cached = cached_state(bursts[-1], c, rng)
    bursts[-1].outs.paged_state = None
    gc.collect()
    if not cached:
        return check_line("latent_pages_compared", 0, ">0", False), {}
    facts = state_rms(tree, c, cached)
    ok = check_line("latent_page_rms", facts["latent_page_rms"],
                    lim["latent_page_rms"],
                    facts["latent_page_rms"] <= lim["latent_page_rms"])
    cold, hit = finished_by_kind(bursts)
    n_cold = min(chk["served_cold"], len(cold))
    pick = ([cold[i] for i in rng.permutation(len(cold))[:n_cold]]
            + [hit[i] for i in rng.permutation(len(hit))
               [:chk["served_requests"] - n_cold]])
    ok &= check_line("served_cold_and_hit_compared",
                     [n_cold, len(pick) - n_cold],
                     [chk["served_cold"],
                      chk["served_requests"] - chk["served_cold"]],
                     n_cold == chk["served_cold"]
                     and len(pick) == chk["served_requests"])
    if not pick:
        return False, facts
    g = np.concatenate([served_gaps(tree, c, *r) for r in pick])
    facts.update(requests=len(pick), cold=n_cold, tokens=int(g.size),
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
            weights_gigachat.make_gigachat(c, seed, cfg.dtype))
        t_weights = time.perf_counter()
        warm = serve_burst(params, cfg, s, *gen.warmup())
    if failed_requests(warm):
        raise RuntimeError("the warm-up burst did not finish its requests")
    warm_m = warm.outs.metrics
    del warm
    # (the LFM2 driver's note: what the process holds by now goes to the
    # permanent generation, so that a burst's collection scans only
    # what the window made)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    say("setup", setup_s=setup_s, reach_chip_s=t_in - t_start,
        weights_s=t_weights - t_in, warm_burst_s=setup_watch.wall_s
        - (t_weights - t_in), compile_s=setup_watch.compile_s,
        cache_hits=setup_watch.hits, cache_misses=setup_watch.misses,
        warm_programs_traced=warm_m.programs_traced,
        warm_phase_s={k: round(v, 3) for k, v in warm_m.phase_s.items()})

    logdir = os.path.join(cell.root, ".bench_trace", cell.name)
    with harness.Watch() as window_watch:
        t0 = time.perf_counter()
        tracer = _RefillTracer(logdir) if trace else None
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
    phases = {k: [round(x.phase_s.get(k, 0.0), 3) for x in m]
              for k in ("chunk.step", "refill.prefill", "refill.scatter",
                        "refill.seat")}
    prompt_tokens = sum(len(p) for b in bursts for p in b.prompts)
    say("window", window_s=window_s, bursts=len(bursts), requests=attempted,
        failed=failed, tokens=tokens, ttft_samples=len(ttft),
        tpot_samples=len(tpot),
        # tpot_p95_ms is noted, not reported: two bursts behind a p95
        # swing with one machine stop by more than half the metric's
        # bound (PERF.md, question 15)
        serve_tok_s=tokens / window_s,
        tpot_p95_ms=1e3 * harness.percentile(tpot, 0.95),
        step_decode_ms=[round(1e3 * x.itl_p50_s, 3) for x in m],
        compiles=window_watch.misses,
        programs_loaded=window_watch.hits, load_s=window_watch.compile_s,
        programs_traced=[x.programs_traced for x in m],
        burst_s=[round(b.seconds, 3) for b in bursts], phase_s=phases,
        chunks=[x.phase_n.get("chunk.step", 0) for x in m],
        prefills=[x.prefills for x in m],
        preemptions=sum(x.preemptions for x in m),
        requeues=sum(x.requeues for x in m),
        rejections=sum(x.rejections for x in m),
        prefix_hits=[x.prefix_hits for x in m],
        prefix_token_share=round(
            sum(x.prefix_pages_reused for x in m) * s["page_tokens"]
            / prompt_tokens, 4),
        pages_hwm=max(x.pages_hwm for x in m), n_pages=s["n_pages"],
        kv_bytes_token=m[0].kv_bytes_token,
        paged_operator=m[0].paged_operator, paged_ffn=m[0].paged_ffn,
        paged_kv_write=m[0].paged_kv_write,
        paged_decode_attend=m[0].paged_decode_attend,
        attend_live_share=[round(x.attend_live_share, 4) for x in m],
        attend_dead_share=[round(x.attend_dead_share, 4) for x in m],
        kv_page_rewrites_per_token=[
            round(x.kv_page_rewrites / max(x.kv_tokens_staged, 1), 4)
            for x in m],
        moe_pairs_routed=[x.moe_assignments for x in m],
        moe_pairs_held=[x.moe_pairs_held for x in m],
        moe_experts_live=[x.moe_experts_live for x in m],
        moe_group_hits=[x.moe_group_hits for x in m],
        moe_layer_steps=[x.moe_layer_steps for x in m],
        expert_bytes=flops_gigachat.expert_bytes(c),
        latent_row_bytes=flops_gigachat.latent_row_bytes(c))

    ok = check_line("failed_requests", failed, 0, failed == 0)
    turned = sum(x.requeues + x.rejections + x.preemptions for x in m)
    ok &= check_line("requeues_rejections_preemptions", turned, 0,
                     turned == 0)
    # (nothing compiles inside the window: a re-trace there would be
    # timed as serving)
    ok &= check_line("compiles", window_watch.misses, 0,
                     window_watch.misses == 0)
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
