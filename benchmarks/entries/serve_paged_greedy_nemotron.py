"""Driver for Nemotron-H (``nemotron_h``) configurations served through
``serving.serve_paged_greedy(..., family=nemotron_h)``: bursts served to
completion through the paged cache (GQA pages of the ``*`` layers beside
the Mamba-2 layers' fixed state and the snapshot store), timed by the
benchmark's own ``on_token`` clock, and compared with the plain reference
(``reference/nemotron_h.py``) once the window has closed.

The window, the tracer and the request checks are the GPT-2, Jamba and
GigaChat drivers' (imported, nothing of them edited: the traced piece is
the LAST REFILL of burst 0, a suffix prefill behind a restored snapshot,
and the decode chunk after it, so that ``%ssd_scan``, ``%ssd_update`` and
the latent experts' grouped matmuls are all inside); what is this
family's own is the program config, the weights, the call (``family=``,
``n_snapshots=``) and what ``correct`` compares: the first ``*`` layer's
pages of a cached system prompt (``kv_page_rms``), the first Mamba-2
layer's state and conv window AS A HIT RESTORES THEM, at the system
prompt's last page, carried there in float32 over 32 chunks
(``ssm_state_rms``, ``conv_tail_rms``), and the served tokens' reference
gaps (the widest and the mean) over requests of BOTH kinds, cold
prefills and suffix prefills behind a snapshot hit; and that snapshots
were restored at all where prompts are shared.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks import flops_nemotron, harness, traffic, weights_nemotron
from benchmarks.entries.serve_paged_greedy import Burst, failed_requests
from benchmarks.entries.serve_paged_greedy_gigachat import finished_by_kind
from benchmarks.entries.serve_paged_greedy_jamba import _RefillTracer
from benchmarks.harness import check_line, say


def program_config(c: dict, dtype: str):
    """The program's own config object for a Nemotron-H configuration
    file; ``dtype`` is what the entry computes in."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import nemotron_h
    first, count, width = weights_nemotron.held(c)
    if (c["n_group"], c["topk_group"]) != (1, 1):
        raise ValueError("nemotron_h routes over ONE group of experts")
    s = c.get("serve", {})
    return nemotron_h.NemotronHConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        pattern=c["hybrid_override_pattern"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mamba_heads=c["mamba_num_heads"], mamba_head_dim=c["mamba_head_dim"],
        ssm_state=c["ssm_state_size"], n_groups=c["n_groups"],
        conv_kernel=c["conv_kernel"], chunk_size=c["chunk_size"],
        n_experts=width, top_k=c["num_experts_per_tok"],
        moe_latent=c["moe_latent_size"], moe_d_ff=c["moe_intermediate_size"],
        shared_d_ff=c["moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        norm_topk_prob=bool(c["norm_topk_prob"]),
        time_step_min=c["time_step_min"], time_step_max=c["time_step_max"],
        time_step_floor=c["time_step_floor"],
        norm_eps=c["layer_norm_epsilon"],
        max_seq=c["max_position_embeddings"], experts_first=first,
        experts_held=None if count == width else count,
        moe_block=s.get("moe_block", 1024),
        snapshot_every=s.get("snapshot_every", 4), dtype=jnp.dtype(dtype))


def serve_burst(params, cfg, s, prompts, n_new, tick=None) -> Burst:
    """One burst through ``serve_paged_greedy`` with the configuration's
    ``serve`` arguments ``s``; the result keeps the call's ``PagedKV``
    for the comparison (whoever keeps the burst drops it before the
    next call)."""
    from mpi_acx_tpu.models import nemotron_h, serving
    gc.collect()
    log = harness.TokenLog(len(prompts), time.perf_counter())

    def on_token(rid, tok):
        log.on_token(rid, tok)
        if tick is not None:
            tick(log, rid)

    outs = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=s["n_slots"],
        max_len=s["max_len"], family=nemotron_h, chunk=s["chunk"],
        kv_int8=s["kv_int8"], page_tokens=s["page_tokens"],
        n_pages=s["n_pages"], prefix_cache=s["prefix_cache"],
        n_snapshots=s["n_snapshots"], on_token=on_token,
        max_request_retries=0, return_paged_state=True)
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
    from benchmarks.reference import nemotron_h as ref
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
        tree, padded, first, rows, plan=weights_nemotron.plan(c),
        hp=ref.hyper(c)))[skip:][:n_served]
    return got.max(-1) - got[np.arange(n_served), seq[n_prompt:]]


def cached_state(burst: Burst, c: dict, rng) -> list:
    """What the burst's call left in its prefix cache, for up to
    ``check.kv_prompts`` different system prompts that it still holds
    ``check.kv_pages`` whole pages of, read through the calls the serve
    loop itself makes on a prefix hit (``prefix.match``, which is cut
    back to a page that holds a snapshot, ``gather_history``,
    ``restore_tail``): [(tokens [n_tok], k, v [L_attn, Hkv, Dh, n_tok],
    h [L_mamba, H, P, N], window [L_mamba, taps, conv_dim])]."""
    pkv, chk = burst.outs.paged_state, c["check"]
    n_tok = chk["kv_pages"] * c["serve"]["page_tokens"]
    taps = c["conv_kernel"] - 1
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
        snap = pkv.restore_tail(pages[-1])
        window = np.asarray(snap["conv"], np.float32)
        out.append((head[:n_tok], np.asarray(k, np.float32),
                    np.asarray(v, np.float32),
                    np.asarray(snap["ssm"], np.float32),
                    window.reshape(window.shape[0], taps, -1)))
        if len(out) == chk["kv_prompts"]:
            break
    return out


def state_rms(tree, c, cached: list) -> dict:
    """Relative RMS error, against the reference's
    (``reference.nemotron_h.states``): of the FIRST ``*`` layer's cached
    keys and values together (published layer 7, behind four Mamba-2 and
    three expert layers in bfloat16), of the FIRST Mamba-2 layer's state
    after the snapshot's last token (``ssm_state_rms``: the number a
    state carried in a lower precision fails, and a snapshot restored
    from the wrong row; EVERY HEAD COUNTS THE SAME, the root of the mean
    over the heads of each head's squared relative error: pooled over
    the layer the few heads whose ``dt`` was last large hold most of the
    energy and all of the error is their inputs' rounding, which buries
    a state rounded after every token) and of its conv window there
    (``conv_tail_rms``)."""
    import jax.numpy as jnp
    from benchmarks.reference import nemotron_h as ref
    plan = weights_nemotron.plan(c)
    kinds = [e[0] for e in plan]
    upto = 1 + max(kinds.index("*"), kinds.index("M"))
    sums, heads = np.zeros(4), []
    for tokens, k, v, h, window in cached:
        T = len(tokens)
        taps = window.shape[1]
        rk, rv, ru, rh = (np.asarray(a, np.float64) for a in ref.states(
            tree, jnp.asarray(tokens), plan=plan, hp=ref.hyper(c), upto=upto,
            h_at=(T - 1,)))
        # cache layout [Hkv, Dh, T] -> the reference's [T, Hkv, Dh]
        for got, want in ((k[0], rk[0]), (v[0], rv[0])):
            sums[0] += np.square(got.transpose(2, 0, 1) - want).sum()
            sums[1] += np.square(want).sum()
        heads.append(np.square(h[0] - rh[0, 0]).sum((1, 2))
                     / np.square(rh[0, 0]).sum((1, 2)))
        want = ru[0, T - taps:]
        sums[2] += np.square(window[0] - want).sum()
        sums[3] += np.square(want).sum()
    return {"kv_page_rms": float(np.sqrt(sums[0] / sums[1])),
            "ssm_state_rms": float(np.sqrt(np.mean(heads))),
            "conv_tail_rms": float(np.sqrt(sums[2] / sums[3]))}


def compare(tree, c, bursts, seed, cached=None) -> tuple:
    """(correct, facts), each number printed beside its limit.

    ``kv_page_rms``, ``ssm_state_rms`` and ``conv_tail_rms``: the pages
    and the restored snapshot of the window's last call against the
    reference's keys, values, state and conv inputs, in the first layer
    of each kind: the numbers a lower cache or state precision and a
    wrong snapshot row fail. ``widest_gap``: over
    ``check.served_requests`` finished requests drawn from the seed,
    ``check.served_cold`` of them prefilled whole and the others behind
    a snapshot hit, the widest gap by which a served token's reference
    logit lies below the reference's best: the number a wrong token
    fails; ``mean_gap``, the same gaps' mean: the number part of the
    mathematics left out fails. ``cached`` replaces what is read from
    the last call's cache (a control's)."""
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
    for name in ("kv_page_rms", "ssm_state_rms", "conv_tail_rms"):
        ok &= check_line(name, facts[name], lim[name],
                         facts[name] <= lim[name])
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
            weights_nemotron.make_nemotron(c, seed, cfg.dtype))
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
        # tpot_p95_ms is noted, not reported: a few bursts behind a p95
        # swing with one machine stop by more than half the metric's
        # bound (PERF.md, questions 14-15)
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
        state_snapshot_restores=[x.state_snapshot_restores for x in m],
        state_snapshot_seats=[x.state_snapshot_seats for x in m],
        state_snapshots_taken=[x.state_snapshots_taken for x in m],
        state_snapshot_rows_hwm=max(x.state_snapshot_rows_hwm for x in m),
        state_snapshot_evictions=sum(x.state_snapshot_evictions for x in m),
        n_snapshots=s["n_snapshots"],
        state_bytes_slot=m[0].state_bytes_slot,
        state_slot_steps=[x.state_slot_steps for x in m],
        state_bytes_moved=[x.state_bytes_moved for x in m],
        pages_hwm=max(x.pages_hwm for x in m), n_pages=s["n_pages"],
        kv_bytes_token=m[0].kv_bytes_token,
        paged_operator=m[0].paged_operator, paged_ffn=m[0].paged_ffn,
        paged_kv_write=m[0].paged_kv_write,
        paged_decode_attend=m[0].paged_decode_attend,
        attend_live_share=[round(x.attend_live_share, 4) for x in m],
        attend_dead_share=[round(x.attend_dead_share, 4) for x in m],
        moe_pairs_routed=[x.moe_assignments for x in m],
        moe_pairs_held=[x.moe_pairs_held for x in m],
        moe_pairs_dead=[x.moe_pairs_dead for x in m],
        moe_experts_live=[x.moe_experts_live for x in m],
        moe_layer_steps=[x.moe_layer_steps for x in m],
        moe_latent_rows=[x.moe_latent_rows for x in m],
        moe_row_dim=m[0].moe_row_dim,
        expert_bytes=flops_nemotron.expert_bytes(c),
        ssd_update_a_call=flops_nemotron.ssd_update_work(c, s["n_slots"]),
        ssd_scan_a_512_bucket=flops_nemotron.ssd_scan_work(c, 512, 1))

    ok = check_line("failed_requests", failed, 0, failed == 0)
    turned = sum(x.requeues + x.rejections + x.preemptions for x in m)
    ok &= check_line("requeues_rejections_preemptions", turned, 0,
                     turned == 0)
    # (nothing compiles inside the window: a re-trace there would be
    # timed as serving)
    ok &= check_line("compiles", window_watch.misses, 0,
                     window_watch.misses == 0)
    seats = sum(x.state_snapshot_seats for x in m)
    if cell.traffic["prefixes"]:    # shared prompts: snapshots are restored
        ok &= check_line("state_snapshot_seats", seats, ">0", seats > 0)
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
