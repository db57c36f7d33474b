"""Speculative decoding: a small draft model proposes, the target model
verifies k tokens in ONE forward pass.

Autoregressive decode is latency-bound — every token costs a full pass
whose time is dominated by streaming the target's weights. Speculative
decoding amortizes that stream: the cheap draft model proposes k-1
tokens with sequential cached steps, then the target scores the pending
token plus all proposals in a single k-wide cached window pass (one
weight stream for up to k emitted tokens). The wall-clock win requires
the weight-streaming-bound regime (a real-size target on HBM) and a
draft the target usually agrees with; the mechanism — R window passes
instead of n_new sequential steps — is asserted directly in the tests
(6.0x fewer target passes at full acceptance, k=6). Greedy acceptance
keeps the output equal to the target-only greedy decode up to
floating-point argmax ties: the window pass and sequential decode use
differently-ordered contractions (~1e-8 apart in f32), so a position
whose top-2 logits tie within that noise — or within bf16 rounding
under bf16 compute — can break the equality; the draft can never
otherwise change which tokens appear, only how fast
(tests/test_speculative.py asserts token equality against
transformer.generate for arbitrary draft/target pairs in f32).

TPU-first construction: the whole loop is one jitted ``lax.while_loop``
with static shapes — a fixed-k draft scan, a fixed-width target window
pass, and a token buffer sized S + n_new + k for the final-round
overshoot. Cache rollback is free by design: both KV caches keep their
stale entries for rejected positions, which are always overwritten by
the pass that next occupies those positions before any query can attend
to them (queries at position p attend only to entries <= p, and every
position is re-written in order).

The reference has no serving stack at all (SURVEY.md §0); this sits on
the same decode substrate as the other families
(decoding.decode_layer_scan, grouped_decode_attend).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.ops.wquant import wread

from mpi_acx_tpu.models import llama as lm
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.models.decoding import (decode_layer_scan,
                                          grouped_decode_attend)


def _window_pass_llama(params, cfg, cache, tokens):
    """Llama counterpart of :func:`_window_pass`: RoPE at the window's
    absolute positions and grouped-query attention against the
    un-repeated GQA cache (the W-token generalization of
    decoding.grouped_decode_attend)."""
    W = tokens.shape[1]
    pos = cache["pos"]
    max_len = cache["k"].shape[-1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = pos + jnp.arange(W)

    def qkv_fn(lp, x, _pos):
        return lm._qkv(cfg, lp, x, positions)

    def attend_fn(lp, x, q, kc, vc, _pos):
        o = grouped_decode_attend(q, kc, vc, pos, max_len, n_rep,
                                  flash=cfg.decode_flash)
        return lm._mlp(cfg, lp, x + o @ wread(lp, "wo", x.dtype))

    x, ks, vs = decode_layer_scan(params["layers"], x, cache["k"],
                                  cache["v"], pos, qkv_fn, attend_fn)
    x = lm.rmsnorm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["unembed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits, {"k": ks, "v": vs, "pos": pos + W}


def _family_ops(cfg):
    """(prefill, decode_step, window_pass) for a config's model family."""
    from mpi_acx_tpu.models.moe_transformer import (MoeTransformerConfig,
                                                    _moe_ffn)

    if isinstance(cfg, lm.LlamaConfig):
        return lm.prefill, lm.decode_step, _window_pass_llama
    if isinstance(cfg, MoeTransformerConfig):
        # The MoE family rides the GPT-2 scaffold with its routed FFN
        # plugged into every pass (same hook as prefill/decode_step).
        return (functools.partial(tfm.prefill, ffn=_moe_ffn),
                functools.partial(tfm.decode_step, ffn=_moe_ffn),
                functools.partial(_window_pass, ffn=_moe_ffn))
    if isinstance(cfg, tfm.TransformerConfig):
        return tfm.prefill, tfm.decode_step, _window_pass
    raise TypeError(
        f"speculative decoding supports the GPT-2, Llama, and "
        f"MoE-transformer families; got {type(cfg).__name__}")


def _window_pass(params, cfg, cache, tokens, ffn=None):
    """Process a W-token window against the cache: tokens [1, W] occupy
    positions pos..pos+W-1; returns (logits [1, W, vocab] f32, cache with
    pos advanced by W). Row w attends cache entries <= pos+w (the entries
    for this window are written before the attention reads them).
    ``ffn(cfg, lp, x) -> x`` overrides the feed-forward half, exactly as
    on tfm.prefill/decode_step — the MoE family plugs in its routed FFN.
    """
    ffn = ffn or tfm._mlp
    W = tokens.shape[1]
    pos = cache["pos"]
    max_len = cache["k"].shape[-1]
    x = (params["embed"][tokens]
         + lax.dynamic_slice_in_dim(params["pos"], pos, W, 0)[None]
         ).astype(cfg.dtype)

    def qkv_fn(lp, x, pos):
        return tfm._qkv(cfg, lp, x)                    # [1, W, H, Dh]

    def attend_fn(lp, x, q, kc, vc, pos):
        o = grouped_decode_attend(q, kc, vc, pos, max_len, n_rep=1,
                                  flash=cfg.decode_flash)
        return ffn(cfg, lp, x + o @ wread(lp, "wo", x.dtype))

    x, ks, vs = decode_layer_scan(params["layers"], x, cache["k"],
                                  cache["v"], pos, qkv_fn, attend_fn)
    x = tfm.layernorm(x, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits, {"k": ks, "v": vs, "pos": pos + W}

def _make_run(draft_cfg, cfg, S, n_new, k, pick0, draft_pick, decide,
              ops=None):
    """The ONE speculative round skeleton (prefill, draft scan with the
    k-th cache-seat step, window pass, buffer/cache bookkeeping,
    while_loop) shared by the greedy and stochastic variants, which
    differ only through three hooks:

    pick0(logits [1,V], key) -> pending [1]      (first token)
    draft_pick(lg [1,V], key) -> nxt [1]         (proposal choice)
    decide(props [k-1], q_logits [k-1,V], p_logits [k,V], key)
        -> (emit [k], m, pending [1])            (accept + finalize)

    ``ops`` overrides the model-family operations as a tuple
    ``(t_prefill, t_window, d_prefill, d_decode)`` with the family
    signatures (params first) — the tensor-parallel speculative builder
    injects per-shard TP variants here; None selects the single-device
    family ops by config type. Returns the RAW traceable ``run`` —
    callers jit it themselves (or embed it in an outer shard_map/jit).

    Cache invariants (identical for both variants): the draft runs k
    steps so full-acceptance rounds leave no unwritten cache seat; stale
    entries sit at >= the rolled-back pos and are rewritten before any
    query can see them; buffer garbage past slot m is overwritten next
    round or trimmed by the final ``buf[:, :S + n_new]``.
    """
    cap = S + n_new + k                      # overshoot slack, last round
    assert cap <= cfg.max_seq and cap <= draft_cfg.max_seq, (
        cap, cfg.max_seq, draft_cfg.max_seq)
    if ops is None:
        t_prefill, _t_decode, t_window = _family_ops(cfg)
        d_prefill, d_decode, _ = _family_ops(draft_cfg)
    else:
        t_prefill, t_window, d_prefill, d_decode = ops

    def run(draft_params, params, prompt, key):
        t_logits, t_cache = t_prefill(params, cfg, prompt, cap,
                                      last_only=True)
        _, d_cache = d_prefill(draft_params, draft_cfg, prompt, cap,
                               last_only=True)
        key, k0 = jax.random.split(key)
        pending = pick0(t_logits[:, -1], k0).astype(prompt.dtype)

        buf = jnp.zeros((1, cap), prompt.dtype)
        buf = lax.dynamic_update_slice(buf, prompt, (0, 0))
        buf = lax.dynamic_update_slice(buf, pending[:, None], (0, S))

        # State: (n_emitted_after_prompt, pending token, caches, buf,
        # key, rounds, accepted). By the decode convention the pending
        # token occupies cache pos = S + n - 1 and is in no cache yet.
        def cond(state):
            n, *_ = state
            return n < n_new

        def body(state):
            n, pending, d_cache, t_cache, buf, key, rounds, acc = state
            key, kd, kdec = jax.random.split(key, 3)

            def dstep(carry, skey):
                cache, tok = carry
                lg, cache = d_decode(draft_params, draft_cfg, cache, tok)
                nxt = draft_pick(lg, skey).astype(tok.dtype)
                return (cache, nxt), (nxt, lg[0])

            (d_cache, _), (props_all, q_logits) = lax.scan(
                dstep, (d_cache, pending), jax.random.split(kd, k))
            props = props_all[:k - 1, 0]                   # [k-1]

            window = jnp.concatenate([pending, props])[None]   # [1, k]
            t_logits, t_cache = t_window(params, cfg, t_cache, window)

            emit, m, pending = decide(props, q_logits[:k - 1],
                                      t_logits[0], kdec)
            emit = emit.astype(prompt.dtype)
            pending = pending.astype(prompt.dtype)
            # Entries of emit past slot m are garbage the next round
            # overwrites before the final trim can expose them.
            buf = lax.dynamic_update_slice(buf, emit[None], (0, S + n))

            n = n + m + 1
            # Roll both caches to the new pending position S + n - 1.
            newpos = jnp.asarray(S, jnp.int32) + n - 1
            d_cache = dict(d_cache, pos=newpos)
            t_cache = dict(t_cache, pos=newpos)
            return (n, pending, d_cache, t_cache, buf, key, rounds + 1,
                    acc + m)

        state = (jnp.asarray(1, jnp.int32), pending, d_cache, t_cache,
                 buf, key, jnp.asarray(0, jnp.int32),
                 jnp.asarray(0, jnp.int32))
        n, pending, d_cache, t_cache, buf, key, rounds, acc = \
            lax.while_loop(cond, body, state)
        return buf[:, :S + n_new], rounds, acc

    return run


def _greedy_hooks(k: int):
    """(pick0, draft_pick, decide) for GREEDY speculation: argmax
    proposals, the longest prefix matching the target's argmax chain
    accepted, the target's argmax as the bonus/correction. The hooks
    ignore their key arguments."""
    def pick0(logits, key):
        return jnp.argmax(logits, -1)

    def draft_pick(lg, key):
        return jnp.argmax(lg, -1)

    def decide(props, q_logits, t_logits, key):
        targets = jnp.argmax(t_logits, -1).astype(props.dtype)   # [k]
        matches = props == targets[:k - 1]
        m = jnp.argmin(jnp.concatenate([matches, jnp.zeros((1,), bool)]))
        m = m.astype(jnp.int32)
        # Emitted tokens are exactly targets[0..m] (accepted proposals
        # equal the target chain; targets[m] is the bonus/correction).
        return targets, m, targets[m][None]

    return pick0, draft_pick, decide


@functools.lru_cache(maxsize=64)
def _build(draft_cfg, cfg, S: int, n_new: int, k: int):
    """Compiled GREEDY speculative loop (hooks: _greedy_hooks). One
    compiled program per (configs, shapes) — the configs are frozen
    dataclasses, so they key the lru_cache and repeat calls are
    trace-free. The public wrapper passes a dummy key."""
    run = _make_run(draft_cfg, cfg, S, n_new, k, *_greedy_hooks(k))
    return jax.jit(run)


def _sample_hooks(k: int, temperature: float):
    """(pick0, draft_pick, decide) for STOCHASTIC speculation (the
    Leviathan/Chen accept/resample algorithm): proposals are SAMPLED
    from the draft at ``temperature``, each accepted with probability
    min(1, p(x)/q(x)) under the target's distribution p and the
    draft's q; on rejection the token is resampled from
    normalize(max(p - q, 0)). Every emitted token is therefore
    distributed EXACTLY as target-only sampling at the same temperature
    (the algorithm's defining guarantee — tests/test_speculative.py
    checks the two-token joint distribution against exact
    teacher-forced target probabilities)."""
    assert temperature > 0.0, temperature
    inv_t = 1.0 / temperature

    def pick0(logits, key):
        return jax.random.categorical(key, logits * inv_t, axis=-1)

    def draft_pick(lg, key):
        return jax.random.categorical(key, lg * inv_t, axis=-1)

    def decide(props, q_logits, t_logits, key):
        ka, kr = jax.random.split(key)
        q = jax.nn.softmax(q_logits * inv_t, -1)       # [k-1, V]
        p = jax.nn.softmax(t_logits * inv_t, -1)       # [k, V]
        # Accept x_i with prob min(1, p_i(x_i)/q_i(x_i)).
        idx = props.astype(jnp.int32)
        p_x = jnp.take_along_axis(p[:k - 1], idx[:, None], 1)[:, 0]
        q_x = jnp.take_along_axis(q, idx[:, None], 1)[:, 0]
        u = jax.random.uniform(ka, (k - 1,))
        accept = u * q_x < p_x                         # [k-1]
        m = jnp.argmin(jnp.concatenate([accept, jnp.zeros((1,), bool)]))
        m = m.astype(jnp.int32)                        # accepted count
        # Final token: on rejection at slot m, resample from the
        # residual (p_m - q_m)^+; at full acceptance, a free sample
        # from p_{k-1}.
        p_m = p[m]
        q_m = q[jnp.minimum(m, k - 2)]
        residual = jnp.where(m < k - 1,
                             jnp.maximum(p_m - q_m, 0.0), p_m)
        # All-zero residual (p <= q everywhere, numerically) falls back
        # to p_m — distribution-correct when p == q.
        residual = jnp.where(residual.sum() > 0, residual, p_m)
        y = jax.random.categorical(kr, jnp.log(residual + 1e-30))
        emit = jnp.concatenate([props, jnp.zeros((1,), props.dtype)])
        emit = lax.dynamic_update_slice(
            emit, y[None].astype(props.dtype), (m,))
        return emit, m, y[None]

    return pick0, draft_pick, decide


@functools.lru_cache(maxsize=64)
def _build_sample(draft_cfg, cfg, S: int, n_new: int, k: int,
                  temperature: float):
    """Compiled STOCHASTIC speculative loop (hooks: _sample_hooks);
    cached per (configs, shapes, temperature) like :func:`_build`."""
    run = _make_run(draft_cfg, cfg, S, n_new, k,
                    *_sample_hooks(k, temperature))
    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _build_batched(draft_cfg, cfg, S: int, n_new: int, k: int,
                   temperature):
    """Batched speculative loop as ``vmap`` of the single-sequence
    program (temperature None = greedy, else stochastic).

    Rows advance INDEPENDENTLY: each row is the complete B=1
    ``lax.while_loop`` round loop, and JAX's while_loop batching rule
    lifts the batch to ONE loop that runs while any row is active,
    select-guarding every row's carry by its own predicate — a finished
    row's buffer, caches, and stats stop changing while the stragglers
    run on. Per-row cache positions, buffer offsets, and acceptance
    counts fall out of the same rule (the scalar ``pos`` becomes a [B]
    vector, the dynamic updates become scatters). This is the TPU-first
    answer to per-row speculative state that CUDA serving stacks
    hand-schedule: the transform, not the kernel, carries the
    bookkeeping. Masked work on finished rows is the usual batched-
    speculation cost and is bounded by the slowest row's round count.
    """
    if temperature is None:
        run = _build(draft_cfg, cfg, S, n_new, k)
    else:
        run = _build_sample(draft_cfg, cfg, S, n_new, k, temperature)

    @jax.jit
    def runb(draft_params, params, prompts, keys):
        tokens, rounds, acc = jax.vmap(
            lambda row, kk: run(draft_params, params, row[None], kk)
        )(prompts, keys)
        return tokens[:, 0], rounds, acc

    return runb


def _check_moe_target(cfg):
    """An MoE TARGET must be in the drop-free capacity regime: the window
    pass routes k tokens as ONE dispatch group while plain decode routes
    1, so with tight capacity a popular expert could drop tokens in one
    pass and not the other — silently breaking the exactness guarantees.
    capacity_factor >= n_experts makes every group drop-free (each
    expert can seat every token). A MoE DRAFT needs no guard: it only
    shapes acceptance, never the emitted distribution."""
    from mpi_acx_tpu.models.moe_transformer import MoeTransformerConfig
    if isinstance(cfg, MoeTransformerConfig):
        assert cfg.capacity_factor >= cfg.n_experts, (
            f"MoE speculative target needs drop-free routing "
            f"(capacity_factor {cfg.capacity_factor} < n_experts "
            f"{cfg.n_experts}); see moe_transformer.decode_step")


def speculative_sample(
    draft_params, draft_cfg, params, cfg,
    prompt: jax.Array, n_new: int, key: jax.Array, k: int = 4,
    temperature: float = 1.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Stochastic speculative decode: same round structure as
    :func:`speculative_generate` but with SAMPLED proposals and the
    accept/resample rule, so every emitted token follows the target's
    sampling distribution at ``temperature`` exactly — the draft changes
    only latency, never the distribution. Returns ``(tokens, stats)``
    like the greedy variant; at B > 1 each row samples under its own
    fold of ``key`` and the stats are per-row (see
    :func:`speculative_generate`)."""
    B, S = prompt.shape
    assert k >= 2, k
    assert draft_cfg.vocab == cfg.vocab, (draft_cfg.vocab, cfg.vocab)
    _check_moe_target(cfg)
    if B == 1:
        run = _build_sample(draft_cfg, cfg, S, n_new, k,
                            float(temperature))
        tokens, rounds, acc = run(draft_params, params, prompt, key)
    else:
        runb = _build_batched(draft_cfg, cfg, S, n_new, k,
                              float(temperature))
        tokens, rounds, acc = runb(draft_params, params, prompt,
                                   jax.random.split(key, B))
    return tokens, {"rounds": rounds, "drafted_accepted": acc}


def speculative_generate(
    draft_params, draft_cfg, params, cfg,
    prompt: jax.Array, n_new: int, k: int = 4,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Greedy speculative decode.

    cfg/draft_cfg select the model family per config type (GPT-2
    TransformerConfig, LlamaConfig, or MoeTransformerConfig — an MoE
    target additionally requires drop-free capacity, see
    _check_moe_target; the families may be mixed freely, but the
    vocabularies must match — asserted). Returns ``(tokens
    [B, S + n_new], stats)`` where each row of tokens equals the target
    family's ``generate(params, cfg, prompt, n_new)`` on that row (up to
    fp argmax ties, see module docstring) and stats counts
    ``{"rounds": R, "drafted_accepted": A}`` — the target ran R window
    passes (vs n_new sequential steps for plain decode), and A of the
    R*(k-1) drafted tokens were accepted. At B == 1 both stats are
    scalars; at B > 1 they are per-row [B] vectors and rows advance
    independently through the vmap-lifted loop (see
    :func:`_build_batched`) — each row's output and stats are those of
    its own B=1 run, while wall-clock is bounded by the slowest row
    (finished rows ride along masked until the batch drains).

    Each round: the draft runs ``k-1`` cached greedy steps from the
    pending token; the target scores the pending token plus the k-1
    proposals in one k-wide window pass; the longest prefix of proposals
    matching the target's own argmax chain is emitted, plus the target's
    next token (the "bonus" — also the correction when a proposal is
    rejected). A round therefore emits 1..k tokens at the cost of ONE
    target pass + k-1 draft steps.

    The compiled loop is cached per (configs, prompt length, n_new, k),
    so repeat calls with the same shapes are trace-free.
    """
    B, S = prompt.shape
    assert k >= 2, k
    assert draft_cfg.vocab == cfg.vocab, (
        f"draft/target vocabularies differ ({draft_cfg.vocab} vs "
        f"{cfg.vocab}) — acceptance would be meaningless")
    _check_moe_target(cfg)
    if B == 1:
        run = _build(draft_cfg, cfg, S, n_new, k)
        tokens, rounds, acc = run(draft_params, params, prompt,
                                  jax.random.key(0))   # hooks ignore it
    else:
        runb = _build_batched(draft_cfg, cfg, S, n_new, k, None)
        tokens, rounds, acc = runb(
            draft_params, params, prompt,
            jax.random.split(jax.random.key(0), B))    # hooks ignore it
    return tokens, {"rounds": rounds, "drafted_accepted": acc}
