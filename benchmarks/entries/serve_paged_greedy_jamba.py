"""Driver for Jamba configurations served through
``serving.serve_paged_greedy(..., family=jamba)``: bursts served to
completion through the paged cache (multi-query pages beside the Mamba
layers' fixed state), timed by the benchmark's own ``on_token`` clock,
and compared with the plain reference (``reference/jamba.py``) once the
window has closed.

The window, the request checks and the result are the LFM2 driver's
(``entries/serve_paged_greedy.py``, imported, nothing of it edited);
what is this family's own is the program config, the weights, the call
(``family=``, ``n_snapshots=``), the traced piece (the LAST REFILL of
burst 0 and the decode chunk after it, so that both of the family's
kernels are inside) and what ``correct`` compares: the first attention
layer's pages, the first Mamba layer's snapshot (scan state and conv
window) at a 512-token boundary, the served tokens' reference gaps (the
widest and the mean), and that nothing was restored where nothing is
shared.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks import flops_jamba, harness, traffic, weights_jamba
from benchmarks.entries.serve_paged_greedy import (Burst, _Tracer,
                                                   failed_requests, finished)
from benchmarks.harness import check_line, say


def program_config(c: dict, dtype: str):
    """The program's own config object for a Jamba configuration file;
    ``dtype`` is what the entry computes in."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import jamba
    return jamba.JambaConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        attn_layer_period=c["attn_layer_period"],
        attn_layer_offset=c["attn_layer_offset"],
        mamba_expand=c["mamba_expand"], mamba_d_state=c["mamba_d_state"],
        mamba_d_conv=c["mamba_d_conv"], mamba_dt_rank=c["mamba_dt_rank"],
        norm_eps=c["rms_norm_eps"], max_seq=c["max_position_embeddings"],
        snapshot_every=c["serve"]["snapshot_every"], dtype=jnp.dtype(dtype))


class _RefillTracer(_Tracer):
    """Puts ONE refill and the decode chunk after it into the profiler's
    trace: it starts the profiler at the first token of the
    SECOND-TO-LAST request of the window's first burst to be seated (the
    next device work is the last request's prefill, scatter and seat)
    and stops it at the first decode token after that last request is
    seated. Chunks that ran between the two refills are inside too; the
    readers count them from the ``on_token`` record."""

    def tick(self, log: harness.TokenLog, rid: int) -> None:
        called = time.perf_counter()
        if self.state == "idle":
            if log.count[rid] == 1 and log.first.count(None) == 1:
                self.span = harness.start_trace(self.logdir)
                self.state, self.t0 = "on", time.perf_counter()
                self.pauses.append((called, self.t0))
        elif (self.state == "on" and log.count[rid] > 1
              and None not in log.first):
            self.stop()


def serve_burst(params, cfg, s, prompts, n_new, tick=None) -> Burst:
    """One burst through ``serve_paged_greedy`` with the configuration's
    ``serve`` arguments ``s``; the result keeps the call's ``PagedKV``
    for the comparison (whoever keeps the burst drops it before the
    next call)."""
    from mpi_acx_tpu.models import jamba, serving
    gc.collect()
    log = harness.TokenLog(len(prompts), time.perf_counter())

    def on_token(rid, tok):
        log.on_token(rid, tok)
        if tick is not None:
            tick(log, rid)

    outs = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=s["n_slots"],
        max_len=s["max_len"], family=jamba, chunk=s["chunk"],
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
    from benchmarks.reference import jamba as ref
    # ONE shape for every request (the reference's program is 28 layers
    # unrolled: a compile a shape costs more than the longer sequence)
    T = c["serve"]["max_len"]
    padded = jnp.asarray(np.pad(seq, (0, T - len(seq))).astype(np.int32))
    n_served = len(seq) - n_prompt
    rows = jnp.zeros((min(T, -(-c["check"]["served_rows"] // 64) * 64),),
                     jnp.int8)
    assert n_served <= rows.shape[0], (n_served, rows.shape)
    first = min(n_prompt - 1, T - rows.shape[0])
    skip = n_prompt - 1 - first
    got = np.asarray(ref.logits_from(
        tree, padded, first, rows, plan=weights_jamba.plan(c),
        hp=ref.hyper(c)))[skip:][:n_served]
    return got.max(-1) - got[np.arange(n_served), seq[n_prompt:]]


def cached_state(burst: Burst, c: dict, rng) -> list:
    """What the burst's call left in its prefix cache, for up to
    ``check.kv_prompts`` prompts that it still holds ``check.kv_pages``
    whole pages of (the depth of the first snapshot), read through the
    calls the serve loop itself makes on a prefix hit (``prefix.match``,
    which is cut back to a page that holds a snapshot, ``gather_history``,
    ``restore_tail``): [(tokens [n_tok], k, v [L_attn, Hkv, Dh, n_tok],
    h [L_mamba, N, C], window [L_mamba, taps, C])]."""
    pkv, chk = burst.outs.paged_state, c["check"]
    n_tok = chk["kv_pages"] * c["serve"]["page_tokens"]
    taps = c["mamba_d_conv"] - 1
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
    (``reference.jamba.states``): of the FIRST attention layer's cached
    keys and values together (published layer 7, behind seven Mamba
    layers in bfloat16), of the FIRST Mamba layer's scan state after
    the snapshot's last token (``ssm_state_rms``: the number a scan
    state carried in a lower precision fails) and of its conv window
    there (``conv_tail_rms``)."""
    import jax.numpy as jnp
    from benchmarks.reference import jamba as ref
    plan = weights_jamba.plan(c)
    kinds = [e[0] for e in plan]
    upto = 1 + max(kinds.index("attention"), kinds.index("mamba"))
    sums = np.zeros(6)
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
        want = rh[0, 0].T                       # the reference's [C, N]
        sums[2] += np.square(h[0] - want).sum()
        sums[3] += np.square(want).sum()
        want = ru[0, T - taps:]
        sums[4] += np.square(window[0] - want).sum()
        sums[5] += np.square(want).sum()
    return {"kv_page_rms": float(np.sqrt(sums[0] / sums[1])),
            "ssm_state_rms": float(np.sqrt(sums[2] / sums[3])),
            "conv_tail_rms": float(np.sqrt(sums[4] / sums[5]))}


def compare(tree, c, bursts, seed, cached=None) -> tuple:
    """(correct, facts), each number printed beside its limit.

    ``kv_page_rms``, ``ssm_state_rms`` and ``conv_tail_rms``: the pages
    and the first snapshot of the window's last call against the
    reference's keys, values, scan state and conv inputs, in the first
    layer of each kind: the numbers a lower cache or state precision
    fails. ``widest_gap``: over a sample of the finished requests drawn
    from the seed, the longest among them, the widest gap by which a
    served token's reference logit lies below the reference's best: the
    number a wrong token fails; ``mean_gap``, the same gaps' mean: the
    number part of the mathematics left out fails. ``cached`` replaces
    what is read from the last call's cache (a control's rounding)."""
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
            weights_jamba.make_jamba(c, seed, cfg.dtype))
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
    update = flops_jamba.ssm_update_work(c, s["n_slots"])
    say("window", window_s=window_s, bursts=len(bursts), requests=attempted,
        failed=failed, tokens=tokens, ttft_samples=len(ttft),
        tpot_samples=len(tpot),
        # noted, not reported: four bursts or fewer behind a p95 swing
        # with one machine stop by more than half the metric's bound
        # (PERF.md, PR 31 and PR 33)
        tpot_p95_ms=1e3 * harness.percentile(tpot, 0.95),
        step_decode_ms=[round(1e3 * x.itl_p50_s, 3) for x in m],
        compiles=window_watch.misses,
        programs_loaded=window_watch.hits, load_s=window_watch.compile_s,
        programs_traced=[x.programs_traced for x in m],
        burst_s=[round(b.seconds, 3) for b in bursts], phase_s=phases,
        chunks=[x.phase_n.get("chunk.step", 0) for x in m],
        preemptions=sum(x.preemptions for x in m),
        requeues=sum(x.requeues for x in m),
        rejections=sum(x.rejections for x in m),
        prefix_hits=sum(x.prefix_hits for x in m),
        conv_tail_restores=sum(x.conv_tail_restores for x in m),
        pages_hwm=max(x.pages_hwm for x in m), n_pages=s["n_pages"],
        state_bytes_slot=m[0].state_bytes_slot,
        state_snapshots_taken=[x.state_snapshots_taken for x in m],
        state_snapshot_rows_hwm=max(x.state_snapshot_rows_hwm for x in m),
        state_snapshot_evictions=sum(x.state_snapshot_evictions for x in m),
        n_snapshots=s["n_snapshots"],
        paged_operator=m[0].paged_operator, paged_ffn=m[0].paged_ffn,
        paged_kv_write=m[0].paged_kv_write,
        paged_decode_attend=m[0].paged_decode_attend,
        attend_live_share=[round(x.attend_live_share, 4) for x in m],
        ssm_update_a_call=update,
        ssm_scan_a_512_bucket=flops_jamba.ssm_scan_work(c, 512, 1))

    ok = check_line("failed_requests", failed, 0, failed == 0)
    turned = sum(x.requeues + x.rejections + x.preemptions for x in m)
    ok &= check_line("requeues_rejections_preemptions", turned, 0,
                     turned == 0)
    if not cell.traffic["prefixes"]:    # nothing shared: a hit is a fault
        hits = sum(x.prefix_hits + x.conv_tail_restores for x in m)
        ok &= check_line("prefix_hits_and_restores_with_nothing_shared",
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
