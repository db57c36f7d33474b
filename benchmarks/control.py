#!/usr/bin/env python3
"""Read the numbers `correct` is decided on, for the sound program and
for its controls, over several seeds in ONE process (set-up is long).
This is how every limit in ``configs/*.json`` was set (PERF.md section 2)
and how to read them again; the benchmark's own runs never call it.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 [--seconds 12] [--logits 8]

Serving, per seed: a window of the cell's own traffic through the timed
path as the configuration states it (sound), and the same window with
the program's own lower precision switched on, ``kv_int8=True``
(control): each with every number `correct` compares. ``--logits N``
adds, for both, the statistic ISSUE 23 named: the relative RMS error of
the logits of N decode steps over all slots, taken through the step
functions the serve loop jits (``transformer.prefill``, ``PagedKV``,
``kvpage.paged_decode_step``), against the reference's.
Training, per seed: the program's three checked steps against the
float32 reference (sound), and the reference computed in int8 against
itself in float32 (control).
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

from benchmarks import harness, traffic, weights  # noqa: E402


def step_logits(params, cfg, s, reqs, steps: int) -> np.ndarray:
    """[steps, slots, vocab] logits of ``steps`` decode steps through the
    paged cache: ``reqs`` [(tokens, prompt length)], one per slot, each
    prefilled with its prompt and seated ON the prompt's last token, so
    that the first step decodes that token again through the cache and
    the next ones the tokens it was served (as ``chip_smoke.py`` does)."""
    import jax
    import jax.numpy as jnp
    from mpi_acx_tpu.models import kvpage, serving
    from mpi_acx_tpu.models import transformer as tfm
    pt, q = s["page_tokens"], s["kv_int8"]
    pkv = kvpage.PagedKV(cfg, tfm, s["n_slots"], s["max_len"], pt,
                         s["n_pages"], kv_int8=q)
    prefill = jax.jit(lambda p, t, li: tfm.prefill(
        p, cfg, t, t.shape[1], kv_int8=q, last_index=li))
    for b, (seq, n) in enumerate(reqs):
        padded = np.zeros((1, min(serving._bucket(n), s["max_len"])),
                          np.int32)
        padded[0, :n] = seq[:n]
        _, one = prefill(params, jnp.asarray(padded), n - 1)
        pages = pkv.alloc_evicting(kvpage.pages_needed(n + steps, pt))
        pkv.scatter_prompt({k: v for k, v in one.items() if k != "pos"},
                           pages[:kvpage.pages_needed(n, pt)])
        pkv.seat(b, [], pages, n - 1)
    step = jax.jit(lambda p, st, t: kvpage.paged_decode_step(
        p, cfg, st, t, pt), donate_argnums=(1,))
    state, out = pkv.device_state(), []
    for j in range(steps):
        tok = jnp.asarray([seq[n - 1 + j] for seq, n in reqs], jnp.int32)
        logits, state = step(params, state, tok)
        out.append(np.asarray(logits, np.float32))
    return np.stack(out)


def logits_rms(params, cfg, c, s, reqs, steps: int) -> dict:
    """Relative RMS error of :func:`step_logits` against the reference
    (each row centred over the vocabulary, which no token depends on)."""
    import jax.numpy as jnp
    from benchmarks.reference import gpt2
    got = step_logits(params, cfg, s, reqs, steps)
    err = ref2 = 0.0
    for b, (seq, n) in enumerate(reqs):
        T = min(-(-len(seq) // 256) * 256, c["n_positions"])
        padded = jnp.asarray(np.pad(seq, (0, T - len(seq))).astype(np.int32))
        want = np.asarray(gpt2.logits_from(
            params, padded, n - 1, jnp.zeros((steps,), jnp.int8),
            n_head=c["n_head"], eps=c["layer_norm_epsilon"]))
        d = got[:, b] - want
        d -= d.mean(-1, keepdims=True)
        want = want - want.mean(-1, keepdims=True)
        err += float(np.square(d, dtype=np.float64).sum())
        ref2 += float(np.square(want, dtype=np.float64).sum())
    return {"logits": int(got.size), "logit_rel_rms": (err / ref2) ** 0.5}


def serve(cell, seeds, seconds, logit_steps):
    from benchmarks.entries import serve_paged_greedy as e
    c = cell.config
    cfg = harness.gpt2_program_config(c, c["weights_dtype"])
    legs = (("sound", c["serve"]),
            ("kv_int8", dict(c["serve"], kv_int8=True)))
    for seed in seeds:
        params = weights.make_gpt2(c, seed, cfg.dtype)
        for leg, s in legs:
            gen = traffic.ServeBursts(cell.traffic, seed, c["vocab_size"])
            e.serve_burst(params, cfg, s, *gen.warmup())
            bursts = e.serve_window(params, cfg, s, gen, seconds)
            m = [b.outs.metrics for b in bursts]
            reqs = e.finished(bursts[-1:])[:s["n_slots"]]
            t0 = time.perf_counter()
            _, facts = e.compare(params, c, bursts, seed)
            ref_s = time.perf_counter() - t0
            if logit_steps:
                facts.update(logits_rms(params, cfg, c, s, reqs, logit_steps))
            harness.say("control", workload=cell.name, seed=seed, leg=leg,
                        failed=sum(e.failed_requests(b) for b in bursts),
                        bursts=len(bursts), reference_seconds=ref_s,
                        pages_hwm=max(x.pages_hwm for x in m),
                        preemptions=sum(x.preemptions for x in m), **facts)
            del bursts
        del params


def train(cell, seeds):
    from benchmarks.entries import train_step_optax as e
    c, t = cell.config, cell.traffic

    def gaps(got, want, diff):
        return {"loss_gap": max(abs(a - b) for a, b in zip(got[0], want[0])),
                "first_grad_norm_gap": e.worst_leaf_gap(got[1], want[1]),
                "param_change_norm_gap": e.worst_leaf_gap(got[2], want[2]),
                "first_grad_diff": e.worst_leaf_diff(diff, want[1]),
                "first_grad_diff_median_leaf": e.worst_leaf_diff(
                    diff, want[1], of=np.median)}

    for seed in seeds:
        trainer = e.Trainer(c, t, seed)
        batches, got, grad = trainer.checked_steps(seed)
        del trainer
        t0 = time.perf_counter()
        want = e.reference_steps(c, seed, batches, first_grad=grad,
                                 keep_grad=True)
        ref_s = time.perf_counter() - t0
        low = e.reference_steps(c, seed, batches, precision="int8",
                                first_grad=want[4])
        half = e.reference_steps(c, seed, [b[:len(b) // 2] for b in batches])
        harness.say("control", workload=cell.name, seed=seed,
                    reference_seconds=ref_s, losses=got[0],
                    sound=gaps(got, want, want[3]),
                    reference_int8=gaps(low, want, low[3]),
                    half_batch_loss_gap=max(
                        abs(a - b) for a, b in zip(half[0], want[0])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--logits", type=int, default=0)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    harness.require_chips(cell.cell["chips"])
    from mpi_acx_tpu import backend
    backend.enable_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",")]
    if cell.config["entry"] == "serve_paged_greedy":
        serve(cell, seeds, a.seconds, a.logits)
    else:
        train(cell, seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
