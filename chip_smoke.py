#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on a chip.

Drives the main path once on a TPU, through the entry points a user
calls, at the full width of GPT-2 125M (``transformer.gpt2_small()``,
bf16 weights made from ``--seed``), and checks what comes out by the
repo's own means. It measures nothing: the seconds it prints are set-up
facts (how long compiles and runs took here), never a rate.

    python chip_smoke.py            # one chip: the four phases below
    python chip_smoke.py --chips 4  # four chips: the multi-chip paths only

One chip:
  serve_paged  serving.serve_paged_greedy at its defaults (128-token
               pages, radix prefix cache on), bf16 pool and int8 pool,
               twice (the second call traces no program), against the
               dense reference configuration on the same chip; then the
               same loop over a small LFM2-MoE preset (conv state beside
               GQA pages, routed experts): what the step was built from,
               the share of experts a step reads, tails restored on hits
  serve_fixed  serving.serve_greedy at max_len 1024 (auto -> the Pallas
               decode kernel), and disagg.serve_disagg_greedy in loopback
               (native runtime, per-layer Pready/Parrived, int8 wire)
  trigger      tests/tpu_onchip_worker.py under build/acxrun -np 2: rank 0
               on the chip fires io_callback triggers and a compiled
               Pallas flag kernel, rank 1 on the CPU receives
  train        train.make_train_step on a one-device mesh, three steps at
               B=8 S=512 and one at S=1024 (flash attention's backward);
               a ``tp`` axis of one runs no ring: ``attn_direct_calls``;
               on the flash path (S=1024) the remat layer keeps the
               kernel's ``o`` and ``lse``: ``flash_residuals_named``
Four chips (``--chips 4``):
  tp_serve     make_tp_server_fns at tp=4 under serve_greedy, against the
               one-device serve in the same process
  train_mesh   make_train_step on dp1 x pp2 x tp2 (ring attention inside:
               ``attn_ring_calls``), against the one-device step

A chip belongs to one process, so this parent never imports JAX: it
builds the native library (``make lib tools`` — build/ is not tracked),
runs each phase as a child in turn, and takes the device facts for its
last line from the children's reports. Every child fails unless JAX's
default device is a TPU; any phase that fails makes the exit code
non-zero. All children share one persistent compile cache
(mpi_acx_tpu.backend.enable_compile_cache).

Every stdout line is one JSON object; the last is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

ONE_CHIP = ("serve_paged", "serve_fixed", "trigger", "train")
FOUR_CHIPS = ("tp_serve", "train_mesh")
CHILD_TIMEOUT_S = 900
# bf16 parity of kernel vs dense attention (tests/test_flash_decode.py).
LOGIT_TOL = 4e-2


@dataclasses.dataclass(frozen=True)
class Size:
    """What a phase runs at. FULL is the contract's size; TINY exists so
    the phase functions can be rehearsed on the CPU (tests)."""

    tiny: bool
    n_slots: int
    max_len: int
    chunk: int
    page_tokens: int | None       # None -> the serve loop's default (128)
    prompt_lens: tuple            # cold prompts, spread over the buckets
    shared_prefix: int            # tokens three more prompts share
    shared_suffixes: tuple
    new_tokens: tuple             # (lo, hi) per request
    train_b: int
    train_s: int
    train_s_long: int


FULL = Size(tiny=False, n_slots=8, max_len=1024, chunk=32, page_tokens=None,
            prompt_lens=(160, 230, 250, 400, 420, 500, 650, 700, 768),
            shared_prefix=256, shared_suffixes=(140, 70, 100),
            new_tokens=(32, 64), train_b=8, train_s=512, train_s_long=1024)
TINY = Size(tiny=True, n_slots=2, max_len=64, chunk=4, page_tokens=16,
            prompt_lens=(5, 19), shared_prefix=16,
            shared_suffixes=(3, 6, 7), new_tokens=(3, 6), train_b=4,
            train_s=16, train_s_long=32)


def emit(**row):
    print(json.dumps(row), flush=True)


def _require(ok, what="a check failed"):
    """A check that survives ``python -O`` (an assert does not)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


# --------------------------------------------------------------------------
# Child side (everything below imports JAX lazily)


def _model(size: Size, seed: int, **cfg_over):
    """(cfg, bf16 params): GPT-2 125M at full width and depth, or the
    test config at TINY."""
    import jax

    from mpi_acx_tpu.models import transformer as tfm
    cfg = (tfm.tiny_config(vocab=128, d_model=64, n_heads=4, n_layers=2,
                           d_ff=128, max_seq=128) if size.tiny
           else tfm.gpt2_small())
    cfg = dataclasses.replace(cfg, **cfg_over)
    params = tfm.cast_params(tfm.init_params(jax.random.key(seed), cfg))
    return cfg, params


def _reference(cfg):
    """The dense reference configuration: no Pallas kernel anywhere."""
    return dataclasses.replace(cfg, decode_flash=False, use_flash=False)


def _requests(size: Size, vocab: int, seed: int):
    """(prompts, n_new): cold prompts over the prefill buckets, then
    prompts that share one full-page prefix (radix hits)."""
    import numpy as np
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, vocab, size=n).astype(np.int32)

    prompts = [toks(n) for n in size.prompt_lens]
    prefix = toks(size.shared_prefix)
    prompts += [np.concatenate([prefix, toks(n)])
                for n in size.shared_suffixes]
    lo, hi = size.new_tokens
    return prompts, [int(rng.integers(lo, hi + 1)) for _ in prompts]


class _Watch:
    """Wall, compile seconds and persistent-cache traffic of a block —
    from JAX's own monitoring events, so compile and run time come
    apart without running anything twice."""

    _live = []

    @classmethod
    def install(cls):
        import jax

        def on_event(event, **_):
            for w in cls._live:
                if event.endswith("/cache_hits"):
                    w.hits += 1
                elif event.endswith("/cache_misses"):
                    w.misses += 1

        def on_duration(event, secs, **_):
            if event.endswith("/backend_compile_duration"):
                for w in cls._live:
                    w.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def __enter__(self):
        self.hits = self.misses = 0
        self.compile_s = 0.0
        self._t0 = time.perf_counter()
        _Watch._live.append(self)
        return self

    def __exit__(self, *exc):
        _Watch._live.remove(self)
        self.wall_s = time.perf_counter() - self._t0

    def row(self):
        return {"wall_s": round(self.wall_s, 3),
                "compile_s": round(self.compile_s, 3),
                "run_s": round(self.wall_s - self.compile_s, 3),
                "cache_hits": self.hits, "cache_misses": self.misses}


def _has_kernel(jitted, *args) -> bool:
    """Is a Mosaic kernel in the compiled program of ``jitted(*args)``?"""
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def _code_agreement(got, want):
    """Share of int8 codes that agree to within one step."""
    import numpy as np
    d = np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32))
    return float((d <= 1).mean())


def _check_outputs(outs, prompts, n_new, metrics):
    import numpy as np
    _require(metrics.requeues == 0, f"requeues={metrics.requeues}")
    _require(metrics.rejections == 0, f"rejections={metrics.rejections}")
    for out, p, n in zip(outs, prompts, n_new):
        out = np.asarray(out)
        _require(out.shape == (len(p) + n,),
                 f"output of {out.shape}, prompt {len(p)} + {n} new")
        _require((out[:len(p)] == p).all(), "prompt not echoed")
    return int(sum(n_new))


def _mismatch_share(a_outs, b_outs, prompts):
    """Share of generated tokens on which two serves differ (bf16
    near-ties can flip an argmax, and everything after it)."""
    import numpy as np
    diff = total = 0
    for a, b, p in zip(a_outs, b_outs, prompts):
        a, b = np.asarray(a)[len(p):], np.asarray(b)[len(p):]
        diff += int((a != b).sum())
        total += a.size
    return round(diff / max(total, 1), 4)


def _mismatch_vs_dense(outs, ref, prompts):
    """Share of a kernel serve's tokens that its dense twin's are not,
    REQUIRED under a half: near-tie flips (and what follows a flip) read
    0.0-0.15 here; a path that turns non-finite serves token 0 from then
    on, every request's (0.93 in the latent leg before its walk masked
    the stage's unfilled rows: PERF.md, PR 40)."""
    share = _mismatch_share(outs, ref, prompts)
    _require(share < 0.5, f"token_mismatch_vs_dense={share}")
    return share


def _logit_check(got, want, what):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    _require(got.shape == want.shape, f"{what}: logits {got.shape}")
    _require(np.isfinite(got).all(), f"{what}: non-finite logits")
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    _require(err <= LOGIT_TOL,
             f"{what}: kernel-path logits off the dense reference by {err}")
    return round(err, 5)


def _serve_stats(m):
    return {"requeues": m.requeues, "rejections": m.rejections,
            "preemptions": m.preemptions, "prefills": m.prefills,
            "steps": m.steps}


def _rewrites_per_token(m):
    """Pages a layer's flushes read and wrote back for each token it
    stored: 1 / chunk to 2 / chunk with the chunk's stage (the write a
    token that it replaced read 1)."""
    return round(m.kv_page_rewrites / max(m.kv_tokens_staged, 1), 4)


def _record_row(first, again):
    """What two calls' span records hold (``ServingMetrics.spans``):
    the spans a call kept, the waits far above their like of the first
    call (it loads the programs: those ARE its stalls) and of the second
    (none on a quiet machine), and what the first call loaded, by
    program: seconds traced, lowered, loaded (self seconds)."""
    from mpi_acx_tpu import profiling
    _require(len(again.spans) == sum(again.phase_n.values())
             and all(s.t1 >= s.t0 > 0 for s in again.spans),
             f"spans={len(again.spans)}, phase_n={again.phase_n}")
    loaded = [e for s in first.spans for e in s.programs]
    return {"spans": len(again.spans),
            "stalls": [first.stalls, again.stalls],
            "stall_ms": [round(1e3 * first.stall_s, 3),
                         round(1e3 * again.stall_s, 3)],
            "programs_loaded": [
                sum(e.kind == "load" for e in loaded),
                sum(e.kind == "load" for s in again.spans
                    for e in s.programs)],
            "programs_loaded_s": round(profiling.program_seconds(loaded), 3),
            "programs_trace_lower_load_s": {
                name: [round(row.get(k, 0.0), 3)
                       for k in ("trace", "lower", "load")]
                for name, row in profiling.programs_by_name(loaded).items()}}


def _seated_batch(size: Size, vocab: int, seed: int):
    """One prompt per slot, lengths spread over one bucket, for the
    first-step logit comparison."""
    import numpy as np

    from mpi_acx_tpu.models.serving import _bucket
    rng = np.random.default_rng(seed + 1)
    top = max(size.prompt_lens)
    lens = np.linspace(top // 8 + 1, top, size.n_slots).astype(int)
    bucket = min(_bucket(top), size.max_len)
    batch = np.zeros((size.n_slots, bucket), np.int32)
    for b, n in enumerate(lens):
        batch[b, :n] = rng.integers(0, vocab, size=n)
    return batch, lens.astype(np.int32)


def _first_step_logits(params, cfg, size: Size, seed: int, kv_int8: bool,
                       paged: bool):
    """Logits of the first decode step after a prefill of one prompt per
    slot, each slot at its own position — through the fixed-slot step
    (transformer.decode_step) or the paged one
    (kvpage.paged_decode_step). Returns (logits [B, vocab], whether a
    Mosaic kernel is in the compiled step)."""
    import jax
    import jax.numpy as jnp

    from mpi_acx_tpu.models import kvpage
    from mpi_acx_tpu.models import transformer as tfm
    batch, lens = _seated_batch(size, cfg.vocab, seed)
    tok = jnp.asarray(batch[range(len(lens)), lens - 1])
    if not paged:
        _, cache = jax.jit(lambda p, t: tfm.prefill(
            p, cfg, t, size.max_len, kv_int8=kv_int8))(
                params, jnp.asarray(batch))
        # Re-decode each slot's real last token at its real position.
        cache["pos"] = jnp.asarray(lens - 1)
        step = jax.jit(lambda p, c, t: tfm.decode_step(p, cfg, c, t))
        return (step(params, cache, tok)[0],
                _has_kernel(step, params, cache, tok))
    pt = size.page_tokens or kvpage.default_page_tokens(size.max_len)
    pkv = kvpage.PagedKV(cfg, tfm, size.n_slots, size.max_len, pt,
                         size.n_slots * size.max_len // pt,
                         kv_int8=kv_int8)
    prefill = jax.jit(lambda p, t: tfm.prefill(
        p, cfg, t, batch.shape[1], kv_int8=kv_int8))
    for b, n in enumerate(lens):
        _, one = prefill(params, jnp.asarray(batch[b:b + 1]))
        pages = pkv.alloc_evicting(kvpage.pages_needed(int(n) + 1, pt))
        pkv.scatter_prompt({k: v for k, v in one.items() if k != "pos"},
                           pages[:kvpage.pages_needed(int(n), pt)])
        pkv.seat(b, [], pages, int(n) - 1)
    state = pkv.device_state()
    step = jax.jit(lambda p, s, t: kvpage.paged_decode_step(
        p, cfg, s, t, pt))
    return (step(params, state, tok)[0],
            _has_kernel(step, params, state, tok))


def _attend_check(size, seed, cfg, params, kv_int8, paged, require_kernel):
    """First-step logits, kernel configuration against the dense
    reference on the same device."""
    got, kernel = _first_step_logits(params, cfg, size, seed, kv_int8,
                                     paged)
    want, ref_kernel = _first_step_logits(params, _reference(cfg), size,
                                          seed, kv_int8, paged)
    _require(not ref_kernel, "the dense reference compiled a Pallas kernel")
    if require_kernel:
        _require(kernel, "the default configuration's decode step holds "
                 "no Pallas kernel")
    what = f"{'paged' if paged else 'fixed'}/{'int8' if kv_int8 else 'bf16'}"
    path = {(True, True): "pallas paged_flash_decode_attend",
            (True, False): "pallas flash_decode_attend",
            (False, True): "dense paged_gather_attend",
            (False, False): "dense dense_decode_attend"}[kernel, paged]
    return {"decode_kernel_in_step": kernel, "attend_path": path,
            "first_step_logit_err": _logit_check(got, want, what)}


def phase_serve_paged(size: Size = FULL, seed: int = 0,
                      require_kernel: bool = True):
    from mpi_acx_tpu.models import serving
    cfg, params = _model(size, seed)
    prompts, n_new = _requests(size, cfg.vocab, seed)
    for kv_int8 in (False, True):
        name = f"serve_paged/{'int8' if kv_int8 else 'bf16'}"
        kw = dict(n_slots=size.n_slots, max_len=size.max_len,
                  chunk=size.chunk, kv_int8=kv_int8,
                  page_tokens=size.page_tokens, prefix_cache=True,
                  max_request_retries=0)
        with _Watch() as w:
            outs = serving.serve_paged_greedy(params, cfg, prompts, n_new,
                                              **kw)
        tokens = _check_outputs(outs, prompts, n_new, outs.metrics)
        _require(outs.metrics.prefix_hits >= 2,
                 f"prefix_hits={outs.metrics.prefix_hits}")
        # The same call again: the process has its programs, so nothing
        # is traced, and the tokens are the first call's.
        again = serving.serve_paged_greedy(params, cfg, prompts, n_new,
                                           **kw)
        traced = [outs.metrics.programs_traced,
                  again.metrics.programs_traced]
        _require(traced[0] > 0 and traced[1] == 0
                 and _mismatch_share(again, outs, prompts) == 0,
                 f"second serve call: programs_traced={traced}")
        ref = serving.serve_paged_greedy(params, _reference(cfg), prompts,
                                         n_new, **kw)
        _check_outputs(ref, prompts, n_new, ref.metrics)
        row = _attend_check(size, seed, cfg, params, kv_int8, True,
                            require_kernel)
        emit(phase=name, ok=True, tokens=tokens, **w.row(),
             **_serve_stats(outs.metrics),
             prefix_hits=outs.metrics.prefix_hits,
             pages_hwm=outs.metrics.pages_hwm,
             kv_write_path=outs.metrics.paged_kv_write,
             kv_page_rewrites_per_token=_rewrites_per_token(outs.metrics),
             attend_built=outs.metrics.paged_decode_attend,
             attend_live_page_share=round(
                 outs.metrics.attend_live_share, 4),
             attend_pages_walked=outs.metrics.attend_pages_walked,
             attend_dead_share=round(outs.metrics.attend_dead_share, 4),
             programs_traced=traced,
             token_mismatch_vs_dense=_mismatch_vs_dense(outs, ref, prompts),
             paged_operator=outs.metrics.paged_operator,
             paged_ffn=outs.metrics.paged_ffn,
             **row, **_record_row(outs.metrics, again.metrics))
    _serve_paged_lfm2(size, seed)
    _serve_paged_jamba(size, seed)
    _serve_paged_gigachat(size, seed)
    _serve_paged_nemotron(size, seed)
    _serve_paged_sdar(size, seed)
    return True


def _moe_mask_row(m, top_k: int, n_slots: int) -> dict:
    """How the expert layers' mask engaged in a serve call (the chunk
    routes no pair of a slot-step that can deliver no token): the pairs
    it left out beside those computed add up to every slot's."""
    _require(m.moe_pairs_dead > 0 and m.moe_assignments + m.moe_pairs_dead
             == top_k * n_slots * m.moe_layer_steps,
             f"moe_pairs_dead={m.moe_pairs_dead}, "
             f"moe_assignments={m.moe_assignments}, "
             f"moe_layer_steps={m.moe_layer_steps}")
    return dict(moe_pairs_dead=m.moe_pairs_dead,
                moe_dead_share=round(m.moe_pairs_dead / (
                    m.moe_pairs_dead + m.moe_assignments), 4))


def _serve_paged_lfm2(size: Size, seed: int):
    """The same serve loop over a family of two operator kinds and two
    FFN kinds (models/lfm2.py, a small preset: heads of 64 so that the
    chip's kernels tile, 8 experts top 2): what the step program was
    built from, what its routed FFN had to read, and that a radix hit
    restores the conv layers' tails. Tokens against the dense
    configuration of the same family."""
    import jax

    from mpi_acx_tpu.models import lfm2, serving
    over = {} if size.tiny else dict(vocab=512, d_model=256, d_ff=512,
                                     moe_d_ff=256)
    cfg = lfm2.tiny_lfm2(max_seq=2 * size.max_len, **over)
    params = lfm2.cast_params(lfm2.init_params(jax.random.key(seed), cfg))
    prompts, n_new = _requests(size, cfg.vocab, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, family=lfm2,
              chunk=size.chunk, page_tokens=size.page_tokens,
              prefix_cache=True, max_request_retries=0)
    with _Watch() as w:
        outs = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    m = outs.metrics
    tokens = _check_outputs(outs, prompts, n_new, m)
    _require(m.prefix_hits >= 2 and m.conv_tail_restores >= 2,
             f"prefix_hits={m.prefix_hits}, "
             f"conv_tail_restores={m.conv_tail_restores}")
    _require(0 < m.moe_live_expert_share <= 1 and m.moe_layer_steps > 0,
             f"moe_live_expert_share={m.moe_live_expert_share}")
    again = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    traced = [m.programs_traced, again.metrics.programs_traced]
    _require(traced[0] > 0 and traced[1] == 0
             and _mismatch_share(again, outs, prompts) == 0,
             f"second serve call (lfm2): programs_traced={traced}")
    ref = serving.serve_paged_greedy(params, _reference(cfg), prompts, n_new,
                                     **kw)
    _check_outputs(ref, prompts, n_new, ref.metrics)
    emit(phase="serve_paged/lfm2", ok=True, tokens=tokens, **w.row(),
         **_serve_stats(m), prefix_hits=m.prefix_hits,
         conv_tail_restores=m.conv_tail_restores,
         paged_operator=m.paged_operator, paged_ffn=m.paged_ffn,
         moe_live_expert_share=round(m.moe_live_expert_share, 4),
         moe_load_max_over_mean=round(m.moe_load_max_over_mean, 3),
         **_moe_mask_row(m, cfg.top_k, size.n_slots),
         kv_write_path=m.paged_kv_write,
         kv_page_rewrites_per_token=_rewrites_per_token(m),
         attend_built=m.paged_decode_attend,
         attend_dead_share=round(m.attend_dead_share, 4),
         programs_traced=traced,
         token_mismatch_vs_dense=_mismatch_vs_dense(outs, ref, prompts),
         **_record_row(m, again.metrics))


def _serve_paged_jamba(size: Size, seed: int):
    """The same serve loop over a family whose state is a matrix a
    channel (models/jamba.py, a small preset: heads of 128 and 512
    channels so that the chip's kernels tile): Mamba layers through
    ``ssm_scan`` and ``ssm_update`` beside multi-query pages, a snapshot
    store of its own size, and that a radix hit restores a snapshot.
    Tokens against the plain-JAX configuration of the same family."""
    import jax

    from mpi_acx_tpu.models import jamba, serving
    pt = size.page_tokens or 128
    over = {} if size.tiny else dict(vocab=512, d_model=256, n_heads=2,
                                     d_ff=512, mamba_d_state=16,
                                     mamba_dt_rank=16)
    cfg = jamba.tiny_jamba(max_seq=2 * size.max_len,
                           snapshot_every=size.shared_prefix // pt, **over)
    params = jamba.cast_params(jamba.init_params(jax.random.key(seed), cfg))
    prompts, n_new = _requests(size, cfg.vocab, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, family=jamba,
              chunk=size.chunk, page_tokens=size.page_tokens,
              prefix_cache=True, max_request_retries=0, n_snapshots=4)
    with _Watch() as w:
        outs = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    m = outs.metrics
    tokens = _check_outputs(outs, prompts, n_new, m)
    _require(m.prefix_hits >= 2 and m.conv_tail_restores >= 2,
             f"prefix_hits={m.prefix_hits}, "
             f"conv_tail_restores={m.conv_tail_restores}")
    _require(0 < m.state_snapshot_rows_hwm <= 4
             and m.state_snapshots_taken >= m.state_snapshot_rows_hwm
             + m.state_snapshot_evictions,
             f"state_snapshot_rows_hwm={m.state_snapshot_rows_hwm}, "
             f"state_snapshots_taken={m.state_snapshots_taken}")
    again = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    traced = [m.programs_traced, again.metrics.programs_traced]
    _require(traced[0] > 0 and traced[1] == 0
             and _mismatch_share(again, outs, prompts) == 0,
             f"second serve call (jamba): programs_traced={traced}")
    ref = serving.serve_paged_greedy(
        params, dataclasses.replace(_reference(cfg), ssm_kernel=False),
        prompts, n_new, **kw)
    _check_outputs(ref, prompts, n_new, ref.metrics)
    emit(phase="serve_paged/jamba", ok=True, tokens=tokens, **w.row(),
         **_serve_stats(m), prefix_hits=m.prefix_hits,
         conv_tail_restores=m.conv_tail_restores,
         paged_operator=m.paged_operator, paged_ffn=m.paged_ffn,
         state_bytes_slot=m.state_bytes_slot,
         state_snapshots_taken=m.state_snapshots_taken,
         state_snapshot_rows_hwm=m.state_snapshot_rows_hwm,
         state_snapshot_evictions=m.state_snapshot_evictions,
         kv_write_path=m.paged_kv_write,
         kv_page_rewrites_per_token=_rewrites_per_token(m),
         attend_built=m.paged_decode_attend,
         attend_dead_share=round(m.attend_dead_share, 4),
         programs_traced=traced,
         token_mismatch_vs_dense=_mismatch_vs_dense(outs, ref, prompts),
         **_record_row(m, again.metrics))


def _serve_paged_gigachat(size: Size, seed: int):
    """The same serve loop over a LATENT page pool (models/gigachat.py, a
    small preset: a 512 + 64 wide row read by 4 heads, so that the chip's
    kernels tile; 8 experts in 2 groups of which this chip holds group
    1, beside a shared expert): the absorbed decode attend over the one
    pool, suffix prefills that up-project hit pages, and what of the
    routing the held share computes. Tokens against the dense
    configuration of the same family."""
    import jax

    from mpi_acx_tpu.models import gigachat, serving
    over = {} if size.tiny else dict(
        vocab=512, d_model=256, q_lora_rank=128, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=192, d_ff=512,
        moe_d_ff=256, moe_block=256)
    cfg = gigachat.tiny_gigachat(max_seq=2 * size.max_len, experts_first=4,
                                 experts_held=4, **over)
    params = gigachat.cast_params(
        gigachat.init_params(jax.random.key(seed), cfg))
    prompts, n_new = _requests(size, cfg.vocab, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, family=gigachat,
              chunk=size.chunk, page_tokens=size.page_tokens,
              prefix_cache=True, max_request_retries=0)
    with _Watch() as w:
        outs = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    m = outs.metrics
    tokens = _check_outputs(outs, prompts, n_new, m)
    _require(m.prefix_hits >= 2 and m.prefix_pages_reused >= 2,
             f"prefix_hits={m.prefix_hits}")
    _require(0 < m.moe_pairs_held < m.moe_assignments
             and m.moe_pairs_held == 2 * m.moe_group_hits
             and 0 < m.moe_live_expert_share <= 1,
             f"moe_pairs_held={m.moe_pairs_held} of {m.moe_assignments}, "
             f"moe_group_hits={m.moe_group_hits}")
    _require(m.kv_bytes_token == 3 * 2 * cfg.row_dim,
             f"kv_bytes_token={m.kv_bytes_token}")
    again = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    traced = [m.programs_traced, again.metrics.programs_traced]
    _require(traced[0] > 0 and traced[1] == 0
             and _mismatch_share(again, outs, prompts) == 0,
             f"second serve call (gigachat): programs_traced={traced}")
    ref = serving.serve_paged_greedy(params, _reference(cfg), prompts, n_new,
                                     **kw)
    _check_outputs(ref, prompts, n_new, ref.metrics)
    mismatch = _mismatch_vs_dense(outs, ref, prompts)
    emit(phase="serve_paged/gigachat", ok=True, tokens=tokens, **w.row(),
         **_serve_stats(m), prefix_hits=m.prefix_hits,
         prefix_pages_reused=m.prefix_pages_reused,
         paged_operator=m.paged_operator, paged_ffn=m.paged_ffn,
         kv_bytes_token=m.kv_bytes_token,
         moe_pairs_routed=m.moe_assignments,
         moe_pairs_held=m.moe_pairs_held, moe_group_hits=m.moe_group_hits,
         moe_experts_live=m.moe_experts_live,
         moe_live_expert_share=round(m.moe_live_expert_share, 4),
         **_moe_mask_row(m, cfg.top_k, size.n_slots),
         kv_write_path=m.paged_kv_write,
         kv_page_rewrites_per_token=_rewrites_per_token(m),
         attend_built=m.paged_decode_attend,
         attend_pages_walked=m.attend_pages_walked,
         attend_dead_share=round(m.attend_dead_share, 4),
         programs_traced=traced,
         token_mismatch_vs_dense=mismatch,
         **_record_row(m, again.metrics))


def _serve_paged_nemotron(size: Size, seed: int):
    """The same serve loop over a family whose layers are ONE mixer each
    (models/nemotron_h.py, the published period MEMEMEM*EME at a small
    width, the Mamba-2 heads at their published geometry, 128 x 64 x 128
    in 8 groups, so that the chip's kernels tile as in the cell): Mamba-2
    layers through ``ssd_scan`` and ``ssd_update``, GQA pages of the one
    ``*`` layer, latent experts of two matrices of which this chip holds
    half, and that a radix hit restores a snapshot. Tokens against the
    plain-JAX configuration of the same family."""
    import jax

    from mpi_acx_tpu.models import nemotron_h, serving
    pt = size.page_tokens or 128
    over = {} if size.tiny else dict(
        vocab=512, d_model=256, n_heads=4, n_kv_heads=2, head_dim=128,
        mamba_heads=128, mamba_head_dim=64, ssm_state=128, n_groups=8,
        chunk_size=128, moe_latent=128, moe_d_ff=256, shared_d_ff=512,
        moe_block=256)
    cfg = nemotron_h.tiny_nemotron(
        max_seq=2 * size.max_len, experts_first=4, experts_held=4,
        snapshot_every=size.shared_prefix // pt, **over)
    params = nemotron_h.cast_params(
        nemotron_h.init_params(jax.random.key(seed), cfg))
    prompts, n_new = _requests(size, cfg.vocab, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, family=nemotron_h,
              chunk=size.chunk, page_tokens=size.page_tokens,
              prefix_cache=True, max_request_retries=0, n_snapshots=4)
    with _Watch() as w:
        outs = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    m = outs.metrics
    tokens = _check_outputs(outs, prompts, n_new, m)
    _require(m.prefix_hits >= 2 and m.state_snapshot_seats >= 2
             and m.state_snapshot_restores >= m.state_snapshot_seats,
             f"prefix_hits={m.prefix_hits}, "
             f"state_snapshot_seats={m.state_snapshot_seats}")
    _require(0 < m.moe_pairs_held < m.moe_assignments
             and m.moe_latent_rows == m.moe_pairs_held
             and m.moe_row_dim == cfg.moe_latent,
             f"moe_pairs_held={m.moe_pairs_held} of {m.moe_assignments}, "
             f"moe_latent_rows={m.moe_latent_rows}")
    _require(m.state_slot_steps == m.decode_tokens
             and m.state_bytes_moved == 2 * m.state_slot_steps
             * m.state_bytes_slot,
             f"state_slot_steps={m.state_slot_steps}")
    # every slot-step of the chunks either delivers or was left alone
    _require(m.state_steps_dead > 0 and m.state_slot_steps
             + m.state_steps_dead == m.decode_slot_steps,
             f"state_steps_dead={m.state_steps_dead}, "
             f"state_slot_steps={m.state_slot_steps}, "
             f"decode_slot_steps={m.decode_slot_steps}")
    again = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    traced = [m.programs_traced, again.metrics.programs_traced]
    _require(traced[0] > 0 and traced[1] == 0
             and _mismatch_share(again, outs, prompts) == 0,
             f"second serve call (nemotron): programs_traced={traced}")
    ref = serving.serve_paged_greedy(
        params, dataclasses.replace(_reference(cfg), ssm_kernel=False),
        prompts, n_new, **kw)
    _check_outputs(ref, prompts, n_new, ref.metrics)
    emit(phase="serve_paged/nemotron", ok=True, tokens=tokens, **w.row(),
         **_serve_stats(m), prefix_hits=m.prefix_hits,
         state_snapshot_restores=m.state_snapshot_restores,
         state_snapshot_seats=m.state_snapshot_seats,
         paged_operator=m.paged_operator, paged_ffn=m.paged_ffn,
         state_bytes_slot=m.state_bytes_slot,
         state_slot_steps=m.state_slot_steps,
         state_steps_dead=m.state_steps_dead,
         state_dead_share=round(m.state_dead_share, 4),
         state_bytes_moved=m.state_bytes_moved,
         state_snapshots_taken=m.state_snapshots_taken,
         state_snapshot_rows_hwm=m.state_snapshot_rows_hwm,
         moe_pairs_routed=m.moe_assignments,
         moe_pairs_held=m.moe_pairs_held,
         moe_latent_rows=m.moe_latent_rows, moe_row_dim=m.moe_row_dim,
         moe_experts_live=m.moe_experts_live,
         **_moe_mask_row(m, cfg.top_k, size.n_slots),
         kv_write_path=m.paged_kv_write,
         kv_page_rewrites_per_token=_rewrites_per_token(m),
         attend_built=m.paged_decode_attend,
         attend_dead_share=round(m.attend_dead_share, 4),
         programs_traced=traced,
         token_mismatch_vs_dense=_mismatch_vs_dense(outs, ref, prompts),
         **_record_row(m, again.metrics))


def _serve_paged_sdar(size: Size, seed: int):
    """The same serve loop over a family that generates by diffusion
    over blocks (models/sdar.py, a small preset: heads of 64 so that the
    chip's kernels tile, 8 experts top 2, blocks of 4 positions denoised
    in 4 forwards and stored by a fifth): a slot-step yields a block,
    not a token; the attend is the token step's call with a block's rows
    folded into one position's heads; the prefill (cold: dense and flash
    buckets; behind a radix hit) is block-causal and hands out no token.
    Tokens against the dense configuration of the same family."""
    import jax

    from mpi_acx_tpu.models import sdar, serving
    over = {} if size.tiny else dict(vocab=512, d_model=256, head_dim=64,
                                     moe_d_ff=256, mask_token_id=511)
    cfg = sdar.tiny_sdar(max_seq=2 * size.max_len, **over)
    params = sdar.cast_params(sdar.init_params(jax.random.key(seed), cfg))
    prompts, n_new = _requests(size, cfg.vocab - 1, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, family=sdar,
              chunk=size.chunk, page_tokens=size.page_tokens,
              prefix_cache=True, max_request_retries=0)
    with _Watch() as w:
        outs = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    m = outs.metrics
    tokens = _check_outputs(outs, prompts, n_new, m)
    W, steps = cfg.block_length, cfg.denoising_steps
    _require(m.prefix_hits >= 2, f"prefix_hits={m.prefix_hits}")
    _require(m.block_length == W and m.forwards_store * steps
             == m.forwards_denoise > 0
             and m.decode_tokens == tokens
             and m.decode_slot_steps == (tokens + m.block_positions_kept
                                         + m.block_positions_dead),
             f"forwards={m.forwards_denoise}+{m.forwards_store}, "
             f"positions={m.decode_slot_steps}, delivered={m.decode_tokens}, "
             f"kept={m.block_positions_kept}, dead={m.block_positions_dead}")
    _require(m.moe_pairs_dead > 0 and m.moe_assignments + m.moe_pairs_dead
             == cfg.top_k * size.n_slots * W * m.moe_layer_steps,
             f"moe_pairs_dead={m.moe_pairs_dead}, "
             f"moe_assignments={m.moe_assignments}, "
             f"moe_layer_steps={m.moe_layer_steps}")
    again = serving.serve_paged_greedy(params, cfg, prompts, n_new, **kw)
    traced = [m.programs_traced, again.metrics.programs_traced]
    _require(traced[0] > 0 and traced[1] == 0
             and _mismatch_share(again, outs, prompts) == 0,
             f"second serve call (sdar): programs_traced={traced}")
    ref = serving.serve_paged_greedy(params, _reference(cfg), prompts, n_new,
                                     **kw)
    _check_outputs(ref, prompts, n_new, ref.metrics)
    emit(phase="serve_paged/sdar", ok=True, tokens=tokens, **w.row(),
         **_serve_stats(m), prefix_hits=m.prefix_hits,
         paged_operator=m.paged_operator, paged_ffn=m.paged_ffn,
         block_length=W, denoise_steps=steps,
         forwards_denoise=m.forwards_denoise,
         forwards_store=m.forwards_store,
         block_token_share=round(m.step_utilization, 4),
         block_positions_kept=m.block_positions_kept,
         block_positions_dead=m.block_positions_dead,
         moe_live_expert_share=round(m.moe_live_expert_share, 4),
         moe_dead_share=round(m.moe_pairs_dead / (
             m.moe_pairs_dead + m.moe_assignments), 4),
         kv_write_path=m.paged_kv_write,
         attend_built=m.paged_decode_attend,
         attend_dead_share=round(m.attend_dead_share, 4),
         programs_traced=traced,
         token_mismatch_vs_dense=_mismatch_vs_dense(outs, ref, prompts),
         **_record_row(m, again.metrics))


def _handoff_prefill_parity(params, cfg, size: Size, seed: int):
    """The hand-off's layer-by-layer prompt pass (one program per layer,
    so that layer l's K/V can ship while l+1 computes) against the
    scanned prefill of the monolithic server, on the longest prompt of
    the batch: last-token logits within tolerance, and the share of
    int8 codes within one step (printed; bytes landed in the wrong
    layout would agree in a few percent). Bit-equal on the CPU
    (tests/test_disagg.py); on the chip they are separately compiled
    programs whose bf16 activations drift apart layer by layer —
    98.9% of the codes of the worst layer agreed in the first run."""
    import jax
    import jax.numpy as jnp

    from mpi_acx_tpu.models import disagg
    from mpi_acx_tpu.models import transformer as tfm
    batch, lens = _seated_batch(size, cfg.vocab, seed)
    tokens, last = jnp.asarray(batch[-1:]), int(lens[-1]) - 1
    want_logits, want = jax.jit(lambda p, t, li: tfm.prefill(
        p, cfg, t, t.shape[1], kv_int8=True, last_index=li))(
            params, tokens, last)
    embed_fn, layer_fn, head_fn, quant_fn = \
        disagg.make_layerwise_prefill_fns(params, cfg)
    x, agree = embed_fn(tokens), 1.0
    for layer in range(cfg.n_layers):
        x, k, v = layer_fn(x, layer)
        kq, _, vq, _ = quant_fn(k, v)
        agree = min(agree, _code_agreement(kq, want["k"][layer]),
                    _code_agreement(vq, want["v"][layer]))
    _require(agree >= 0.95, f"hand-off prefill: int8 codes agree {agree}")
    return {"prefill_logit_err": _logit_check(head_fn(x, last), want_logits,
                                              "hand-off prefill"),
            "prefill_code_agreement": round(agree, 5)}


def phase_serve_fixed(size: Size = FULL, seed: int = 0,
                      require_kernel: bool = True):
    import numpy as np

    from mpi_acx_tpu import backend
    from mpi_acx_tpu.models import disagg, serving
    from mpi_acx_tpu.models import transformer as tfm
    cfg, params = _model(size, seed)
    prompts, n_new = _requests(size, cfg.vocab, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, chunk=size.chunk,
              max_request_retries=0)
    mono = None
    for kv_int8 in (False, True):
        fns = serving.make_server_fns(params, cfg, tfm, chunk=size.chunk,
                                      kv_int8=kv_int8)
        with _Watch() as w:
            outs = serving.serve_greedy(params, cfg, prompts, n_new,
                                        kv_int8=kv_int8, server_fns=fns,
                                        **kw)
        tokens = _check_outputs(outs, prompts, n_new, outs.metrics)
        row = _attend_check(size, seed, cfg, params, kv_int8, False,
                            require_kernel)
        emit(phase=f"serve_fixed/{'int8' if kv_int8 else 'bf16'}", ok=True,
             tokens=tokens, **w.row(), **_serve_stats(outs.metrics), **row)
        mono = outs
    # The hand-off in loopback: one process plays prefill and decode over
    # a real partitioned channel of the native runtime. On the CPU its
    # output is the monolithic int8 serve's, token for token
    # (tests/test_disagg.py); on the chip the two prompt passes are
    # different compiled programs, so the codes agree to rounding and a
    # near-tie can flip a token — most requests still come out equal,
    # and a hand-off that landed the wrong bytes would break them all.
    with _Watch() as w:
        dis = disagg.serve_disagg_greedy(params, cfg, prompts, n_new,
                                         server_fns=fns, **kw)
    tokens = _check_outputs(dis, prompts, n_new, dis.metrics)
    _require(len(dis.metrics.handoffs) == len(prompts)
             and all(h.layers == cfg.n_layers and h.overlap
                     for h in dis.metrics.handoffs),
             "a hand-off did not ship every layer with overlap")
    equal = sum(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(mono, dis))
    need = len(prompts) // 2 if backend.on_tpu() else len(prompts)
    _require(equal >= need, f"{equal} of {len(prompts)} hand-off requests "
             "equal serve_greedy(kv_int8=True)")
    emit(phase="serve_fixed/disagg_loopback", ok=True, tokens=tokens,
         **w.row(), **_serve_stats(dis.metrics),
         handoffs=len(dis.metrics.handoffs), wire="int8 codes + f32 scales",
         requests_equal_serve_greedy_int8=f"{equal}/{len(prompts)}",
         token_mismatch_vs_serve_greedy_int8=_mismatch_share(dis, mono,
                                                             prompts),
         **_handoff_prefill_parity(params, cfg, size, seed))
    return True


def _train_data(cfg, n_micro, mb, S, seed):
    import jax
    import jax.numpy as jnp
    tokens = jax.random.randint(jax.random.key(seed + 2), (n_micro, mb, S),
                                0, cfg.vocab)
    return tokens, jnp.roll(tokens, -1, axis=-1)


def _attn_calls():
    """ring_attention.attention_calls_traced() under the names a train
    phase prints: a program's attention calls are all direct (a ``tp``
    axis of one) or all a ring, so over a phase one of the two stands
    still. Beside them ``flash_residuals_named``: the direct flash
    calls whose forward rule named its ``o`` and ``lse`` for the remat
    layer's policy to keep (none: the backward runs the kernel again)."""
    from mpi_acx_tpu.ops.attention import flash_residuals_named_traced
    from mpi_acx_tpu.parallel.ring_attention import attention_calls_traced
    calls = {f"attn_{k}_calls": n
             for k, n in attention_calls_traced().items()}
    calls["flash_residuals_named"] = flash_residuals_named_traced()
    return calls


def _train_run(cfg, mesh, params, tokens, targets, steps, lr=0.1):
    """``steps`` SGD steps of make_train_step on ``mesh``; returns
    (losses, max |delta embed|, the parameters after the last step).
    ``remat=True``: without it the step's temporaries at B=8 S=512 are
    15.9 GB in the compiler's own account — the chip has 16."""
    import jax.numpy as jnp

    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.train import make_train_step
    step, n_stages = make_train_step(cfg, mesh, n_micro=tokens.shape[0],
                                     lr=lr, remat=True)
    staged = tfm.stage_slice(params, n_stages)
    cur, losses = staged, []
    for _ in range(steps):
        loss, cur = step(cur, tokens, targets)
        losses.append(float(loss))
    moved = float(jnp.abs(cur["embed"] - staged["embed"]).max())
    return losses, moved, cur


def phase_train(size: Size = FULL, seed: int = 0, require_kernel=True):
    import jax
    import numpy as np

    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.ring_attention import FLASH_MIN_SHARD
    cfg, _ = _model(size, seed)
    params = tfm.init_params(jax.random.key(seed), cfg)     # f32 masters
    mesh = mesh_from_devices({"dp": 1, "pp": 1, "tp": 1}, jax.devices()[:1])
    for S, steps, mb in ((size.train_s, 3, size.train_b // 2),
                         (size.train_s_long, 1, max(size.train_b // 4, 1))):
        tokens, targets = _train_data(cfg, 2, mb, S, seed)
        before = _attn_calls()
        with _Watch() as w:
            losses, moved, _ = _train_run(cfg, mesh, params, tokens,
                                          targets, steps)
        calls = {k: n - before[k] for k, n in _attn_calls().items()}
        want = float(jax.jit(lambda p, t, y: tfm.loss_fn(p, cfg, t, y))(
            params, tokens.reshape(-1, S), targets.reshape(-1, S)))
        _require(np.isfinite(losses).all()
                 and all(b < a for a, b in zip(losses, losses[1:])),
                 f"losses {losses} are not finite and falling")
        _require(abs(losses[0] - want) <= 2e-2 * abs(want),
                 f"first loss {losses[0]} against loss_fn's {want}")
        _require(moved > 0, "the step did not move the parameters")
        _require(calls["attn_direct_calls"] > 0
                 and calls["attn_ring_calls"] == 0,
                 f"a one-device mesh traced a ring: {calls}")
        if require_kernel and S >= FLASH_MIN_SHARD:
            _require(calls["flash_residuals_named"] > 0,
                     f"the flash path at S={S} named no residual: {calls}")
        emit(phase=f"train/S{S}", ok=True, batch=2 * mb, seq=S, steps=steps,
             losses=[round(x, 4) for x in losses],
             loss_fn_reference=round(want, 4), **calls, **w.row())
    return True


def _device_bytes():
    import jax
    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


def phase_tp_serve(size: Size = FULL, seed: int = 0, require_kernel=True):
    """Tensor-parallel continuous batching over four devices (as
    examples/serve_continuous.py --tp does), against the one-device
    serve in the same process."""
    import jax

    from mpi_acx_tpu.models import serving
    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns
    _require(len(jax.devices()) >= 4, f"four devices, not {jax.devices()}")
    cfg, params = _model(size, seed)
    prompts, n_new = _requests(size, cfg.vocab, seed)
    kw = dict(n_slots=size.n_slots, max_len=size.max_len, chunk=size.chunk,
              family=tfm, max_request_retries=0)
    solo = serving.serve_greedy(params, cfg, prompts, n_new, **kw)
    _check_outputs(solo, prompts, n_new, solo.metrics)
    mesh = mesh_from_devices({"tp": 4}, jax.devices()[:4])
    with _Watch() as w:
        fns = make_tp_server_fns(params, cfg, mesh, chunk=size.chunk)
        outs = serving.serve_greedy(params, cfg, prompts, n_new,
                                    server_fns=fns, **kw)
    tokens = _check_outputs(outs, prompts, n_new, outs.metrics)
    # Where the bytes are: the weights as the server holds them, and a
    # prefilled cache as its prefill program hands it to the slots.
    import jax.numpy as jnp
    wqkv = fns[0].sharded_params["layers"]["wqkv"]
    _, one = fns[0](jnp.zeros((1, 8), jnp.int32), 7)
    shard_shapes = {
        "wqkv": [list(s.data.shape) for s in wqkv.addressable_shards],
        "kv_cache_k": [list(s.data.shape)
                       for s in one["k"].addressable_shards]}
    for arr in (wqkv, one["k"]):
        _require(len({s.device for s in arr.addressable_shards}) == 4,
                 "a sharded array sits on fewer than four devices")
    emit(phase="tp_serve/tp4", ok=True, tokens=tokens, **w.row(),
         **_serve_stats(outs.metrics),
         token_mismatch_vs_one_device=_mismatch_share(outs, solo, prompts),
         device_bytes_in_use=_device_bytes(), shard_shapes=shard_shapes)
    return True


def phase_train_mesh(size: Size = FULL, seed: int = 0, require_kernel=True):
    """One train step on dp1 x pp2 x tp2 (ring attention inside the
    sequence-parallel block), against the one-device step."""
    import jax
    import numpy as np

    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    _require(len(jax.devices()) >= 4, f"four devices, not {jax.devices()}")
    cfg, _ = _model(size, seed)
    params = tfm.init_params(jax.random.key(seed), cfg)
    tokens, targets = _train_data(cfg, 2, size.train_b // 2, size.train_s,
                                  seed)
    one_mesh = mesh_from_devices({"dp": 1, "pp": 1, "tp": 1},
                                 jax.devices()[:1])
    (want,), _, _ = _train_run(cfg, one_mesh, params, tokens, targets, 1)
    mesh = mesh_from_devices({"dp": 1, "pp": 2, "tp": 2}, jax.devices()[:4])
    before = _attn_calls()
    with _Watch() as w:
        (loss,), moved, new = _train_run(cfg, mesh, params, tokens, targets,
                                         1)
    calls = {k: n - before[k] for k, n in _attn_calls().items()}
    _require(calls["attn_ring_calls"] > 0
             and calls["attn_direct_calls"] == 0,
             f"a tp axis of two traced a direct call: {calls}")
    _require(np.isfinite(loss) and abs(loss - want) <= 2e-2 * abs(want),
             f"mesh loss {loss} against the one-device step's {want}")
    _require(moved > 0, "the step did not move the parameters")
    w1 = new["layers"]["w1"]        # [pp, L/pp, d, ff]: staged, ff over tp
    _require(len({s.device for s in w1.addressable_shards}) == 4,
             "the stepped parameters sit on fewer than four devices")
    emit(phase="train_mesh/dp1_pp2_tp2", ok=True, loss=round(loss, 4),
         one_device_loss=round(want, 4), **calls, **w.row(),
         device_bytes_in_use=_device_bytes(),
         shard_shapes={"w1": [list(s.data.shape)
                              for s in w1.addressable_shards]})
    return True


PHASES = {"serve_paged": phase_serve_paged, "serve_fixed": phase_serve_fixed,
          "train": phase_train, "tp_serve": phase_tp_serve,
          "train_mesh": phase_train_mesh}


def child(phase: str, seed: int) -> int:
    """Run one phase in this process, which owns the chip(s)."""
    import jax

    from mpi_acx_tpu import backend
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" or not backend.on_tpu():
        emit(phase=phase, ok=False, device=device,
             error="JAX found no TPU; chip_smoke.py runs on the chip only")
        return 1
    cache_dir = backend.enable_compile_cache()
    before = backend.cache_entries(cache_dir)
    _Watch.install()
    with _Watch() as w:
        PHASES[phase](FULL, seed)
    emit(phase=phase, ok=True, device=device, cache_dir=cache_dir,
         cache_entries_before=before,
         cache_entries_after=backend.cache_entries(cache_dir), **w.row())
    return 0


# --------------------------------------------------------------------------
# Parent side (never imports JAX)


def _run(cmd, env=None, timeout=CHILD_TIMEOUT_S, show=True):
    """Run a command in its own process group, pass its stdout lines
    through (``show``), and leave nothing of it behind. Returns
    (rc, lines)."""
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)

    def kill_group():
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    try:
        out, _ = proc.communicate(timeout=timeout)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        kill_group()
        out, _ = proc.communicate()
        rc = 124
    finally:
        kill_group()                # stragglers (acxrun's ranks)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    if show:
        for ln in lines:
            print(ln, flush=True)
    return rc, lines


def build_native():
    """build/ is ignored by git, and runtime.lib() loads whatever
    libtpuacx.so it finds there: build from the tracked sources first."""
    t0 = time.perf_counter()
    try:
        r = subprocess.run(
            ["make", "-C", REPO, f"-j{os.cpu_count() or 1}", "lib", "tools"],
            capture_output=True, text=True, timeout=600)
    except FileNotFoundError as e:
        raise SystemExit(f"chip_smoke: cannot build the native runtime: {e}")
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-4000:] + r.stderr[-4000:])
        raise SystemExit("chip_smoke: `make lib tools` failed (are make "
                         "and g++ installed, and is this the repo root?)")
    emit(phase="build", ok=True, build_s=round(time.perf_counter() - t0, 2))


def run_trigger():
    """The trigger plane: rank 0 owns the chip, rank 1 pins the CPU."""
    env = dict(os.environ, ACX_RANK0_PLATFORM="tpu")
    t0 = time.perf_counter()
    rc, lines = _run([os.path.join(REPO, "build", "acxrun"), "-np", "2",
                      "-timeout", "420", sys.executable,
                      os.path.join(REPO, "tests", "tpu_onchip_worker.py")],
                     env=env, timeout=480, show=False)
    text = "\n".join(lines)
    on_chip, ranks_ok = "ONCHIP_OK tpu" in text, text.count("ONCHIP_OK")
    ok = rc == 0 and on_chip and ranks_ok == 2
    emit(phase="trigger", ok=ok, rc=rc, rank0_on_chip=on_chip,
         ranks_ok=ranks_ok, worker_said=text[-400:],
         wall_s=round(time.perf_counter() - t0, 2))
    return ok, None


def run_phase(phase: str, seed: int):
    """Returns (ok, device facts the child reported or None)."""
    if phase == "trigger":
        return run_trigger()
    rc, lines = _run([sys.executable, os.path.abspath(__file__), "--phase",
                      phase, "--seed", str(seed)])
    report = {}
    try:
        report = json.loads(lines[-1]) if lines else {}
    except ValueError:
        pass
    ok = rc == 0 and report.get("phase") == phase and report.get("ok") is True
    if not ok and report.get("ok") is not False:
        emit(phase=phase, ok=False, rc=rc,
             error="the phase's process died without a report")
    return ok, report.get("device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)     # child mode
    args = ap.parse_args(argv)
    if args.phase:
        return child(args.phase, args.seed)

    build_native()
    device, failed = None, []
    for phase in (ONE_CHIP if args.chips == 1 else FOUR_CHIPS):
        ok, dev = run_phase(phase, args.seed)
        device = device or dev
        if not ok:
            failed.append(phase)
            if dev is not None and dev.get("platform") != "tpu":
                break                       # no chip: nothing else can pass
    if device is None or device.get("platform") != "tpu":
        failed = failed or ["no device report"]
    elif device["count"] < args.chips:
        failed.append(f"{device['count']} device(s), --chips {args.chips}")
    if failed:
        emit(ok=False, failed=failed, device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
