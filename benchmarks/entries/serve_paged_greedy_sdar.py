"""Driver for SDAR-MoE configurations served through
``serving.serve_paged_greedy(..., family=sdar)``: bursts served to
completion through the paged cache by diffusion over blocks (a slot's
step denoises a block of positions and yields a block, not a token),
timed by the benchmark's own ``on_token`` clock, and compared with the
plain reference (``reference/sdar.py``) once the window has closed.

The burst record and the request checks are the GPT-2 driver's
(``entries/serve_paged_greedy.py``, imported, nothing of it edited);
what is this family's own is the program config, the weights, the call
(``family=``), the tracer (a request's first token arrives with a chunk,
not with its prefill) and what ``correct`` compares, at the published
widths, of what the timed path produced: the pages of the first and the
last layer that finished requests left behind against the reference's
K/V of the FINISHED sequence (the first layer for the cache's precision,
the last over the generated blocks for what a block's forwards leave), and, for blocks of those requests as they
stood at the denoising step that committed a token, how far the served
token's reference logit lies below the reference's best and how far the
committed position's reference confidence lies below the best masked
position's. Logits and confidences, never tokens.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks import harness, traffic, weights_sdar
from benchmarks.entries.serve_paged_greedy import Burst, failed_requests
from benchmarks.harness import check_line, say
# (the family itself: a program without it fails here, at once)
from mpi_acx_tpu.models import sdar, serving


def program_config(c: dict, dtype: str, **over):
    """The program's own config object for an SDAR-MoE configuration
    file; ``dtype`` is what the entry computes in."""
    import jax.numpy as jnp
    g = c["generation"]
    return sdar.SdarConfig(**dict(dict(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        moe_d_ff=c["moe_intermediate_size"], n_experts=c["num_experts"],
        top_k=c["num_experts_per_tok"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        norm_eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
        max_seq=c["max_position_embeddings"],
        block_length=g["block_length"],
        denoising_steps=g["denoising_steps"],
        mask_token_id=g["mask_token_id"], dtype=jnp.dtype(dtype)), **over))


def chunk_deliveries(log: harness.TokenLog, gap_s: float = 0.02,
                     pauses=()) -> list:
    """``TokenLog.deliveries`` for a family whose EVERY token, a
    request's first among them, arrives with a decode chunk: the i-th
    delivery is the i-th chunk of the call. [(time of its first token,
    [(request, index), ...])]"""
    out = []
    for t, rid, idx in log.events:
        paused = (sum(max(0.0, min(t, e) - max(out[-1][2], s))
                      for s, e in pauses) if out else 0.0)
        if not out or t - out[-1][2] - paused > gap_s:
            out.append([t, [], t])
        out[-1][1].append((rid, idx))
        out[-1][2] = t
    return [(t0, toks) for t0, toks, _ in out]


def traced_chunks(run, field: str):
    """``ServingMetrics.<field>[i]`` of the chunks delivered inside the
    traced window of ``run`` (the per-layer readers' argument), or None
    when a chunk's counters cannot be found."""
    t0, t1, pauses = run["traced"]
    out = []
    for b in run["bursts"]:
        by_chunk = getattr(b.outs.metrics, field, None)
        for i, (when, _) in enumerate(chunk_deliveries(b.log,
                                                       pauses=pauses)):
            if t0 < when <= t1:
                if not by_chunk or i >= len(by_chunk):
                    return None
                out.append(by_chunk[i])
    return out


def forwards(c: dict, chunks: int) -> int:
    """Forwards of ``chunks`` decode chunks of configuration ``c``:
    blocks x (denoising steps + 1)."""
    g = c["generation"]
    return (chunks * c["serve"]["chunk"] // g["block_length"]
            * (g["denoising_steps"] + 1))


class BlockTracer:
    """Puts exactly ONE decode chunk into the profiler's trace, from
    inside ``on_token``, as the GPT-2 driver's ``_Tracer`` does, for a
    family whose first tokens arrive with a chunk's delivery: it starts
    the profiler at the first token of the LAST request of the window's
    first burst to get one (every request is seated by then: no prefill
    follows, the next device work is a decode chunk) and stops it at the
    first token of the NEXT delivery (a tick more than ``GAP_S`` after
    the one before: the tokens of one delivery come microseconds
    apart)."""
    GAP_S = 0.02

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.state, self.t0, self.t1, self.span = "idle", 0.0, 0.0, None
        self.pauses, self.last = [], 0.0

    def tick(self, log: harness.TokenLog, rid: int) -> None:
        called = time.perf_counter()
        if (self.state == "idle" and log.count[rid] == 1
                and None not in log.first):
            self.span = harness.start_trace(self.logdir)
            self.state, self.t0 = "on", time.perf_counter()
            self.pauses.append((called, self.t0))
        elif self.state == "on" and called - self.last > self.GAP_S:
            self.stop()
        self.last = time.perf_counter()

    def stop(self) -> None:
        if self.state == "on":
            self.t1 = time.perf_counter()
            harness.stop_trace(self.span)
            self.pauses.append((self.t1, time.perf_counter()))
            self.state = "done"


def serve_burst(params, cfg, s, prompts, n_new, tick=None, **over) -> Burst:
    """One burst through ``serve_paged_greedy`` with the configuration's
    ``serve`` arguments ``s``; the result keeps the call's ``PagedKV``
    for the comparison (whoever keeps the burst drops it before the
    next call)."""
    gc.collect()
    log = harness.TokenLog(len(prompts), time.perf_counter())

    def on_token(rid, tok):
        log.on_token(rid, tok)
        if tick is not None:
            tick(log, rid)

    outs = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, **dict(dict(
            n_slots=s["n_slots"], max_len=s["max_len"], family=sdar,
            chunk=s["chunk"], kv_int8=s["kv_int8"],
            page_tokens=s["page_tokens"], n_pages=s["n_pages"],
            prefix_cache=s["prefix_cache"], on_token=on_token,
            max_request_retries=0, return_paged_state=True), **over))
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


def left_behind(burst: Burst, c: dict, rng) -> list:
    """What up to ``check.served_requests`` finished requests of the
    burst (the longest and others drawn from the seed) left in the page
    pool, read through the call the serve loop itself makes on a prefix
    hit (``gather_history``) from the pages each request held when it
    retired and that nobody has been handed since
    (``PagedKV.left_behind``): [(the finished sequence [T]: the prompt
    and every position its blocks computed, a last block's excess
    included; prompt length; ``at`` [T]: the denoising step that
    committed each position, -1 for the prompt's; k, v [L, Hkv, Dh,
    T])]."""
    pkv, chk = burst.outs.paged_state, c["check"]
    W = c["generation"]["block_length"]
    per = {r.rid: r for r in burst.outs.metrics.per_request}
    kept = [rid for rid in range(len(burst.prompts))
            if rid in per and isinstance(burst.outs[rid], np.ndarray)
            and pkv.left_behind(rid) is not None]
    if not kept:
        return []
    longest = max(kept, key=lambda rid: len(burst.outs[rid]))
    pick = [longest] + [rid for rid in rng.permutation(kept)
                        if rid != longest][:chk["served_requests"] - 1]
    out = []
    for rid in pick:
        prompt, log = burst.prompts[rid], per[rid].block_log
        body = len(prompt) - len(prompt) % W
        seq = np.concatenate([prompt[:body],
                              np.asarray([t for t, _ in log], np.int32)])
        at = np.concatenate([np.full((body,), -1),
                             np.asarray([a for _, a in log])])
        pages, pos = pkv.left_behind(rid)
        assert len(seq) <= pos and len(seq) % W == 0, (rid, len(seq), pos)
        # (always the table row's length: one gather program)
        row = (pages + pages[-1:] * pkv.max_pages)[:pkv.max_pages]
        k, v = (np.asarray(a, np.float32)[..., :len(seq)]
                for a in pkv.gather_history(row))
        out.append((seq.astype(np.int32), len(prompt), at, k, v))
    return out


def block_states(seq, n_prompt: int, at, c: dict, rng) -> list:
    """Up to ``check.block_states`` (block, denoising step) pairs of one
    finished sequence, drawn from the seed among the steps that
    committed a token: [(block's first position, step)]."""
    W = c["generation"]["block_length"]
    pairs = sorted({(p - p % W, int(at[p]))
                    for p in range(n_prompt - n_prompt % W, len(seq))
                    if at[p] >= 0})
    order = rng.permutation(len(pairs))[:c["check"]["block_states"]]
    return [pairs[i] for i in sorted(order)]


def reference_gaps(tree, c: dict, seq, n_prompt: int, at, states) -> tuple:
    """ONE reference pass over the finished sequence and, behind it, a
    copy of each block of ``states`` as it stood at its step (positions
    committed EARLIER hold their tokens, the others the mask token; the
    copy sees the finished blocks before it and itself, at the block's
    own positions). Returns (token gaps, order gaps, k, v [2, T, Hkv,
    Dh] of the first and the last layer): for each position that step
    committed, how far the served token's reference logit lies below the
    reference's best there, and how far the position's reference
    confidence (log of its best token's probability) lies below the best
    still masked position's."""
    import jax.numpy as jnp
    from benchmarks.reference import sdar as ref
    g, chk = c["generation"], c["check"]
    W, T = g["block_length"], len(seq)
    base = c["serve"]["max_len"]
    rows = base + chk["block_states"] * W
    tokens = np.zeros((rows,), np.int32)
    positions = np.zeros((rows,), np.int32)
    mask = np.eye(rows, dtype=bool)         # padding sees itself alone
    tokens[:T], positions[:T] = seq, np.arange(T)
    mask[:T, :T] = ref.block_mask(T, W)
    for e, (first, step) in enumerate(states):
        r = base + e * W
        held = at[first:first + W] < step           # committed by then
        tokens[r:r + W] = np.where(held, seq[first:first + W],
                                   g["mask_token_id"])
        positions[r:r + W] = first + np.arange(W)
        mask[r:r + W, :first] = True
        mask[r:r + W, r:r + W] = True
    n_layer = c["num_hidden_layers"]
    logits, k, v = ref.forward_with(
        tree, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(mask),
        jnp.arange(base, rows), hp=ref.hyper(c), layers=(0, n_layer - 1))
    logits = np.asarray(logits, np.float64).reshape(-1, W, logits.shape[-1])
    top = logits.max(-1)
    conf = -np.log(np.exp(logits - top[..., None]).sum(-1))     # [E, W]
    gaps, order = [], []
    for e, (first, step) in enumerate(states):
        steps = at[first:first + W]
        masked = steps >= step
        for w in np.flatnonzero(steps == step):
            gaps.append(top[e, w] - logits[e, w, seq[first + w]])
            order.append(conf[e][masked].max() - conf[e, w])
    return (np.asarray(gaps), np.asarray(order),
            np.asarray(k, np.float64)[:, :T], np.asarray(v, np.float64)[:, :T])


def kv_rms(cached: list, refs: list, c: dict) -> dict:
    """Relative RMS error of the cached keys and values together
    (``cached``: :func:`left_behind`) against the reference's (``refs``:
    :func:`reference_gaps` of each): ``kv_page_rms`` in the FIRST layer,
    every position (nothing but the cache's own precision and one
    matmul's rounding separates the two there); ``kv_deep_rms`` in the
    LAST layer over the positions of the generated blocks, from the
    prompt's last whole block on (what a block's forwards leave: behind
    every layer's attend and experts); ``kv_last_rms``, a fact with no
    limit, the last layer over every position."""
    W = c["generation"]["block_length"]
    sums = np.zeros((3, 2))
    for (_, n_prompt, _, k, v), (_, _, rk, rv) in zip(cached, refs):
        last, body = k.shape[0] - 1, n_prompt - n_prompt % W
        for got, want in ((k[[0, last]], rk), (v[[0, last]], rv)):
            # cache layout [2, Hkv, Dh, T] -> the reference's [2, T, Hkv, Dh]
            err = np.square(got.transpose(0, 3, 1, 2) - want)
            want = np.square(want)
            for n, (layer, at) in enumerate(((0, 0), (1, body), (1, 0))):
                sums[n] += err[layer, at:].sum(), want[layer, at:].sum()
    return dict(zip(("kv_page_rms", "kv_deep_rms", "kv_last_rms"),
                    (float(x) for x in np.sqrt(sums[:, 0] / sums[:, 1]))))


def compare(tree, c, bursts, seed, keep=None) -> tuple:
    """(correct, facts), each number printed beside its limit.

    ``kv_page_rms`` / ``kv_deep_rms`` (:func:`kv_rms`): relative RMS
    error of the cached keys and values that a sample of the last call's
    finished requests left behind, against the reference's K/V of the
    finished sequence under the block-causal mask: in the first layer,
    prompt pages and the pages of generated blocks alike (the number a
    lower cache precision fails), and in the last layer over the
    generated blocks (the number K/V stored from a forward that still
    held a mask fail, and a wrong mask).
    ``widest_gap`` / ``mean_gap``: over blocks of those requests as they
    stood at a step that committed a token, how far the served token's
    reference logit lies below the reference's best: the numbers a wrong
    mask inside the block or part of the mathematics left out fail.
    ``order_gap``: the mean of how far the committed position's
    reference confidence lies below the best still masked position's:
    the number a wrong commit order fails. ``keep`` (a dict) is handed
    what was read from the pool and the reference's side of it
    (``cached``, ``refs``: the control's 8-bit rounding reads both
    again)."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0x5DA2])
    lim = c["limits"]
    cached = left_behind(bursts[-1], c, rng)
    bursts[-1].outs.paged_state = None
    gc.collect()
    if not cached:
        return check_line("kv_pages_compared", 0, ">0", False), {}
    refs, n_states = [], 0
    for seq, n_prompt, at, _, _ in cached:
        states = block_states(seq, n_prompt, at, c, rng)
        n_states += len(states)
        refs.append(reference_gaps(tree, c, seq, n_prompt, at, states))
    if keep is not None:
        keep.update(cached=cached, refs=refs)
    gaps = np.concatenate([r[0] for r in refs])
    order = np.concatenate([r[1] for r in refs])
    facts = dict(
        requests=len(cached), block_states=n_states, tokens=int(gaps.size),
        positions=int(sum(len(x[0]) for x in cached)),
        generated=int(sum(len(x[0]) - x[1] for x in cached)),
        **kv_rms(cached, refs, c),
        widest_gap=float(gaps.max()), mean_gap=float(gaps.mean()),
        order_gap=float(order.mean()),
        flipped_share=float((gaps > 0).mean()),
        reordered_share=float((order > 0).mean()))
    ok = True
    for name in ("kv_page_rms", "kv_deep_rms", "widest_gap", "mean_gap",
                 "order_gap"):
        ok &= check_line(name, facts[name], lim[name],
                         facts[name] <= lim[name])
    say("compared", **facts)
    return ok, facts


def window_facts(bursts) -> dict:
    """The program's own block counters over the window's bursts."""
    m = [b.outs.metrics for b in bursts]
    steps = sum(x.decode_slot_steps for x in m)
    forwards = sum(x.forwards_denoise + x.forwards_store for x in m)
    return dict(
        block_length=m[0].block_length, denoise_steps=m[0].denoise_steps,
        chunks=sum(x.steps for x in m),
        forwards_denoise=sum(x.forwards_denoise for x in m),
        forwards_store=sum(x.forwards_store for x in m),
        positions=steps, delivered=sum(x.decode_tokens for x in m),
        kept=sum(x.block_positions_kept for x in m),
        dead=sum(x.block_positions_dead for x in m),
        store_forward_share=(sum(x.forwards_store for x in m)
                             / max(forwards, 1)),
        token_share=sum(x.decode_tokens for x in m) / max(steps, 1))


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    c, s = cell.config, cell.config["serve"]
    cfg = program_config(c, c["weights_dtype"])
    import jax
    gen = traffic.ServeBursts(cell.traffic, seed, c["vocab_size"])
    t_in = time.perf_counter()
    with harness.Watch() as setup_watch:
        params = jax.block_until_ready(
            weights_sdar.make_sdar(c, seed, cfg.dtype))
        t_weights = time.perf_counter()
        warm = serve_burst(params, cfg, s, *gen.warmup())
    if failed_requests(warm):
        raise RuntimeError("the warm-up burst did not finish its requests")
    del warm
    # (what the process holds by now goes to the permanent generation:
    # the collection each burst starts with scans only what the window
    # made; the LFM2 entry's note)
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
        tracer = BlockTracer(logdir) if trace else None
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
        pages_hwm=max(x.pages_hwm for x in m), n_pages=s["n_pages"],
        paged_operator=m[0].paged_operator, paged_ffn=m[0].paged_ffn,
        paged_kv_write=m[0].paged_kv_write,
        paged_decode_attend=m[0].paged_decode_attend,
        moe_live_expert_share=[round(x.moe_live_expert_share, 4) for x in m],
        moe_pairs_a_layer_forward=[round(x.moe_assignments
                                         / max(x.moe_layer_steps, 1), 1)
                                   for x in m],
        stalls=sum(x.stalls for x in m),
        stall_s=round(sum(x.stall_s for x in m), 3),
        phase_s={k: round(sum(x.phase_s.get(k, 0.0) for x in m), 3)
                 for k in sorted(m[0].phase_s)},
        **window_facts(bursts))

    ok = check_line("failed_requests", failed, 0, failed == 0)
    turned = sum(x.requeues + x.rejections + x.preemptions for x in m)
    ok &= check_line("requeues_rejections_preemptions", turned, 0,
                     turned == 0)
    if not cell.traffic["prefixes"]:    # nothing shared: a hit is a fault
        hits = sum(x.prefix_hits for x in m)
        ok &= check_line("prefix_hits_with_nothing_shared", hits, 0,
                         hits == 0)
    t_ref = time.perf_counter()
    right, _ = compare(params, c, bursts, seed)
    ok &= right
    say("reference", seconds=time.perf_counter() - t_ref)

    end_to_end = {
        "serve_tok_s": tokens / window_s,
        "ttft_p95_ms": 1e3 * harness.percentile(ttft, 0.95),
        "setup_s": setup_s,
    }
    return {"correct": ok, "attempted": attempted, "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": peak,
            "bursts": bursts, "window_s": window_s,
            "window_watch": window_watch, "trace_dir": logdir,
            "traced": ((tracer.t0, tracer.t1, tracer.pauses)
                       if tracer and tracer.state == "done" else None),
            "config": c, "traffic": cell.traffic}
