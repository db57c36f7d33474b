"""Driver for configurations whose entry is ``serving.serve_paged_greedy``:
bursts served to completion through the paged cache, timed by the
benchmark's own ``on_token`` clock, and compared with the plain
reference once the window has closed.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import time

import numpy as np

from benchmarks import harness, traffic, weights
from benchmarks.harness import check_line, say

# The limits `correct` holds a run to are the configuration's own
# (``configs/<name>.json``: ``limits``, beside the readings they were set
# from, and ``check``, how much is compared); a missing one is an error.


@dataclasses.dataclass
class Burst:
    prompts: list
    n_new: list
    log: harness.TokenLog
    outs: object                 # the program's ServedBatch
    seconds: float


class _Tracer:
    """Puts exactly ONE decode chunk into the profiler's trace, from
    inside ``on_token`` — the serve call blocks, so the callback is the
    only place the benchmark runs meanwhile. It starts the profiler at
    the first token of the LAST request of the window's first burst to
    be seated: no prefill follows, the next device work is a decode
    chunk; and stops it at that chunk's first token. A window by the
    clock would hold refill prefills too, and with them the device's
    trace buffer overflowed and dropped the chunk's tail (PERF.md)."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.state, self.t0, self.t1, self.span = "idle", 0.0, 0.0, None
        self.pauses = []

    def tick(self, log: harness.TokenLog, rid: int) -> None:
        called = time.perf_counter()
        if (self.state == "idle" and log.count[rid] == 1
                and None not in log.first):
            self.span = harness.start_trace(self.logdir)
            self.state, self.t0 = "on", time.perf_counter()
            self.pauses.append((called, self.t0))
        elif self.state == "on" and log.count[rid] > 1:
            self.stop()

    def stop(self) -> None:
        if self.state == "on":
            self.t1 = time.perf_counter()
            harness.stop_trace(self.span)
            self.pauses.append((self.t1, time.perf_counter()))
            self.state = "done"


def serve_burst(params, cfg, s, prompts, n_new, tick=None) -> Burst:
    """One burst through ``serve_paged_greedy`` with the configuration's
    ``serve`` arguments ``s``. The result keeps the call's ``PagedKV``
    (``outs.paged_state``) for the comparison; whoever keeps the burst
    drops it before the next call, the pool being most of the chip."""
    from mpi_acx_tpu.models import serving
    # The program's PagedKV sits in a reference cycle once a prefix hit
    # has built its gather program, so the last call's 9 GB pool lives
    # until the collector runs; a caller has to run it (PERF.md, PR 23).
    gc.collect()
    log = harness.TokenLog(len(prompts), time.perf_counter())

    def on_token(rid, tok):
        log.on_token(rid, tok)
        if tick is not None:
            tick(log, rid)

    outs = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=s["n_slots"],
        max_len=s["max_len"], chunk=s["chunk"], kv_int8=s["kv_int8"],
        page_tokens=s["page_tokens"], n_pages=s["n_pages"],
        prefix_cache=s["prefix_cache"], on_token=on_token,
        max_request_retries=0, return_paged_state=True)
    return Burst(prompts, n_new, log, outs,
                 time.perf_counter() - log.t_handed)


def serve_window(params, cfg, s, gen, seconds, tracer=None) -> list:
    """Burst after burst until ``seconds`` have passed; the run ends at
    the end of the burst in which they passed. Only the last burst
    keeps its page pool."""
    bursts, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if bursts:
            bursts[-1].outs.paged_state = None
        bursts.append(serve_burst(params, cfg, s, *gen.burst(),
                                  tick=tracer.tick if tracer else None))
    return bursts


def failed_requests(b: Burst) -> int:
    """Requests of a burst that were rejected, came out with the wrong
    length or without their prompt, or streamed another token count."""
    bad = 0
    for rid, (p, n) in enumerate(zip(b.prompts, b.n_new)):
        out = b.outs[rid]
        ok = (isinstance(out, np.ndarray) and out.shape == (len(p) + n,)
              and (out[:len(p)] == p).all() and b.log.count[rid] == n)
        bad += not ok
    return bad


def served_gaps(tree, c, seq: np.ndarray, n_prompt: int) -> np.ndarray:
    """Per served token of one finished request: how far its reference
    logit lies below the reference's best (0: the reference's own
    choice)."""
    import jax.numpy as jnp
    from benchmarks.reference import gpt2
    T = min(-(-len(seq) // 256) * 256, c["n_positions"])
    padded = jnp.asarray(np.pad(seq, (0, T - len(seq))).astype(np.int32))
    n_served = len(seq) - n_prompt
    rows = jnp.zeros((-(-n_served // 128) * 128,), jnp.int8)
    first = min(n_prompt - 1, T - rows.shape[0])
    skip = n_prompt - 1 - first
    ref = np.asarray(gpt2.logits_from(
        tree, padded, first, rows, n_head=c["n_head"],
        eps=c["layer_norm_epsilon"]))[skip:][:n_served]
    return ref.max(-1) - ref[np.arange(n_served), seq[n_prompt:]]


def finished(bursts) -> list:
    """(tokens, prompt length) of every request the window finished."""
    return [(b.outs[rid], len(p)) for b in bursts
            for rid, p in enumerate(b.prompts)
            if isinstance(b.outs[rid], np.ndarray)]


def page_errors(tree, c, burst: Burst, rng) -> np.ndarray | None:
    """How far the K/V pages that the burst's call left in its prefix
    cache lie from the reference's keys and values: [n_layer, 4] sums of
    squares (``reference.gpt2.kv_error``) over the first
    ``check.kv_pages`` pages of ``check.kv_prompts`` prompts drawn by
    ``rng`` among those the cache still holds so many pages of. Read
    through the calls the serve loop itself makes on a prefix hit
    (``prefix.match``, ``gather_history``). None: nothing to read."""
    import jax.numpy as jnp
    from benchmarks.reference import gpt2
    pkv, chk = burst.outs.paged_state, c["check"]
    n_tok = chk["kv_pages"] * c["serve"]["page_tokens"]
    total, seen, left = None, set(), chk["kv_prompts"]
    for rid in rng.permutation(len(burst.prompts)):
        head = burst.prompts[rid][:n_tok + 1]
        if len(head) <= n_tok or head[:n_tok].tobytes() in seen:
            continue
        pages = pkv.prefix.match(head)
        if len(pages) < chk["kv_pages"]:
            continue
        seen.add(head[:n_tok].tobytes())
        got_k, got_v = pkv.gather_history(pages)
        sums = np.asarray(gpt2.kv_error(
            tree, jnp.asarray(head[:n_tok]), got_k, got_v,
            n_head=c["n_head"], eps=c["layer_norm_epsilon"]), np.float64)
        total = sums if total is None else total + sums
        left -= 1
        if not left:
            break
    return total


def page_rms(sums: np.ndarray) -> np.ndarray:
    """[n_layer] relative RMS error of a layer's cached keys and values
    together, from :func:`page_errors`' sums."""
    return np.sqrt((sums[:, 0] + sums[:, 2]) / (sums[:, 1] + sums[:, 3]))


def compare(tree, c, bursts, seed) -> tuple:
    """(correct, facts), each number printed beside its limit.

    ``kv_page_rms``: the pages of the window's last call against the
    reference's keys and values, in the FIRST layer, where nothing but
    the page's own precision and one matmul's rounding separates them
    (deeper layers carry the bf16 residual stream's rounding, which
    buries the pages'): the number a lower page precision fails.
    ``widest_gap``: over a sample of the finished requests drawn from
    the seed, the longest among them, the widest gap by which a served
    token's reference logit lies below the reference's best: the number
    a wrong token fails."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0xC4EC])
    lim, chk = c["limits"], c["check"]
    sums = page_errors(tree, c, bursts[-1], rng)
    bursts[-1].outs.paged_state = None
    gc.collect()
    if sums is None:
        return check_line("kv_pages_compared", 0, ">0", False), {}
    layers = page_rms(sums)
    facts = {"kv_page_rms": float(layers[0]),
             "kv_page_rms_layers": [round(float(x), 6) for x in layers]}
    ok = check_line("kv_page_rms", facts["kv_page_rms"], lim["kv_page_rms"],
                    facts["kv_page_rms"] <= lim["kv_page_rms"])

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
    ok &= check_line("widest_gap", facts["widest_gap"], lim["widest_gap"],
                     facts["widest_gap"] <= lim["widest_gap"])
    say("compared", **facts)
    return ok, facts


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    c, s = cell.config, cell.config["serve"]
    cfg = harness.gpt2_program_config(c, c["weights_dtype"])
    import jax
    gen = traffic.ServeBursts(cell.traffic, seed, c["vocab_size"])
    t_in = time.perf_counter()
    with harness.Watch() as setup_watch:
        params = jax.block_until_ready(weights.make_gpt2(c, seed, cfg.dtype))
        t_weights = time.perf_counter()
        warm = serve_burst(params, cfg, s, *gen.warmup())
    if failed_requests(warm):
        raise RuntimeError("the warm-up burst did not finish its requests")
    del warm
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
        tpot_samples=len(tpot), compiles=window_watch.misses,
        programs_loaded=window_watch.hits,
        load_s=window_watch.compile_s,
        preemptions=sum(x.preemptions for x in m),
        requeues=sum(x.requeues for x in m),
        rejections=sum(x.rejections for x in m),
        prefix_hits=sum(x.prefix_hits for x in m),
        pages_hwm=max(x.pages_hwm for x in m), n_pages=s["n_pages"])

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
