"""Shared decode scaffolds for the model families.

Both transformer.generate and llama.generate are this loop closed over
their own prefill/decode_step; keeping the scaffold in one place keeps
the max_seq position-clamp guard and the scan wiring from drifting.
``sample_generate`` is the stochastic sibling (temperature / top-k /
top-p nucleus), all inside one ``lax.scan`` — fixed shapes, one compile.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def to_cache_layout(x):
    """Token-major K/V as the model computes it ``[..., S, H, D]`` (or
    its kv_quant scales ``[..., S, H, 1]``) -> THE cache layout
    ``[..., H, D, S]`` (scales ``[..., H, 1, S]``): heads outside,
    features on sublanes, tokens on lanes. It is the layout in which a
    block of one slot's tokens is a whole number of (8..32, 128) TPU
    tiles for any head_dim that is a multiple of 32 — head_dim 64
    included, which as a minor dimension would leave half of every
    lane row empty — and the one XLA's TPU layout assignment picks for
    such arrays by itself, so the Pallas decode kernels
    (ops/flash_decode.py) read the cache with no relayout copy. Every
    cache, page pool and KV wire partition in the tree holds this
    layout; this function is the only crossing."""
    return jnp.moveaxis(x, -3, -1)


def new_kv_cache(n_layers, batch, n_kv_heads, head_dim, max_len, dtype,
                 kv_int8: bool = False):
    """Zeroed cache pytree in THE cache layout: {'k','v': [L, B, Hkv,
    Dh, max_len], 'pos': int32}; ``kv_int8`` stores int8 codes plus
    per-(position, head) f32 scales 'ks'/'vs' [L, B, Hkv, 1, max_len]
    (ops/kvquant.py). Every family's ``init_kv_cache`` is this."""
    shape = (n_layers, batch, n_kv_heads, head_dim, max_len)
    cache = {
        "k": jnp.zeros(shape, jnp.int8 if kv_int8 else dtype),
        "v": jnp.zeros(shape, jnp.int8 if kv_int8 else dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if kv_int8:
        cache["ks"] = jnp.zeros(shape[:3] + (1, max_len), jnp.float32)
        cache["vs"] = jnp.zeros(shape[:3] + (1, max_len), jnp.float32)
    return cache


def grouped_decode_attend(q, kc, vc, pos, max_len, n_rep, flash=None):
    """W-token grouped-query attention against an UN-REPEATED KV cache:
    q [B, W, Hq, D] occupying positions pos..pos+W-1, kc/vc
    [B, Hkv, D, max_len] with Hq = Hkv*n_rep -> o [B, W, Hq*D]. Query
    head g*n_rep + r reads K/V group g directly — no [B, L, Hq, D]
    materialization, preserving GQA's cache-bandwidth win; window row w
    attends cache entries <= pos+w. With n_rep=1 this IS plain
    multi-head attention and with W=1 the ordinary decode step, so every
    decode path — the three families' steps, the tensor-parallel loops,
    and the speculative window passes — shares this single definition of
    the scale/mask/softmax math.

    ``flash`` is the ``decode_flash`` config knob, dispatched through
    :func:`mpi_acx_tpu.ops.flash_decode.select_decode_attend` (the
    ``select_attention`` idiom): ``None`` -> auto (the length-aware
    Pallas decode kernel on TPU when max_len is big and 128-divisible,
    dense otherwise), ``True`` -> always the kernel (interpret mode off
    TPU, so CPU tests run the same code path), ``False`` -> the dense
    reference below."""
    from mpi_acx_tpu.ops.flash_decode import select_decode_attend

    return select_decode_attend(flash)(q, kc, vc, pos, max_len, n_rep)


def dense_decode_attend(q, kc, vc, pos, max_len, n_rep, scale=None):
    """Dense-einsum reference for :func:`grouped_decode_attend` — reads
    the whole [B, Hkv, D, max_len] cache every step (the flash kernel's
    parity ground truth; also the dispatch target below the kernel's
    crossover and on non-TPU backends).

    ``kc``/``vc`` may each be an ``(int8 codes, f32 scales [B, Hkv, 1,
    max_len])`` tuple (ops/kvquant.py codes in cache layout). The
    per-position scales are then applied to the SMALL tensors — K's to
    the logits, V's to the probabilities — never to the cache itself:
    the r05 chip A/B showed the obvious dequantize-then-attend path at
    0.73x the bf16 baseline because XLA materializes the dequantized
    cache tensor in HBM (int8 read + bf16 write + bf16 read — MORE
    traffic than the bf16 cache the codes were meant to halve). With
    the factoring, the full-cache operands stay int8 end-to-end.
    Algebraically identical: sum_d q_d*(K_kd*s_k) == (sum_d q_d*K_kd)
    * s_k, and the f32 logits/probs multiply is if anything MORE
    precise than rounding each dequantized element to bf16.

    ``scale`` replaces the scores' ``1 / sqrt(Dh)``; ``vc`` may be
    narrower than ``kc`` (a latent cache's values are its rows' heads),
    the result then ``[B, W, Hq * Dv]``."""
    ks = vs = None
    if isinstance(kc, tuple):
        kc, ks = kc
    if isinstance(vc, tuple):
        vc, vs = vc
    B, W = q.shape[:2]
    Hkv, Dh = kc.shape[1], kc.shape[2]
    qg = q.reshape(B, W, Hkv, n_rep, Dh)
    # Pre-scale q by 1/sqrt(Dh) (W*Hq*Dh elements) instead of dividing
    # the [B, g, r, W, max_len] f32 logits — same trick as _flash_kernel.
    qg = (qg.astype(jnp.float32)
          * (1.0 / Dh ** 0.5 if scale is None else scale)).astype(q.dtype)
    kin = kc if ks is None else kc.astype(q.dtype)  # int8 exact in bf16
    logits = jnp.einsum("bqgrd,bgdk->bgrqk", qg, kin).astype(jnp.float32)
    if ks is not None:
        # [B, Hkv, 1, max_len] -> [B, g, 1, 1, k] against bgrqk.
        logits = logits * ks[:, :, None]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        rows = pos + jnp.arange(W)[:, None]            # [W, 1]
        cols = jnp.arange(max_len)[None, :]            # [1, max_len]
        mask = (cols <= rows)[None, None, None]        # [1,1,1,W,max_len]
    else:
        # Per-slot positions (continuous-batching serving): slot b's
        # window row w attends cache entries <= pos[b] + w.
        rows = pos[:, None, None] + jnp.arange(W)[None, :, None]
        cols = jnp.arange(max_len)[None, None, :]
        mask = (cols <= rows)[:, None, None]       # [B,1,1,W,max_len]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(logits, axis=-1)
    if vs is not None:
        p = p * vs[:, :, None]
    p = p.astype(q.dtype)
    vin = vc if vs is None else vc.astype(q.dtype)
    return jnp.einsum("bgrqk,bgdk->bqgrd", p, vin).reshape(
        B, W, Hkv * n_rep * vin.shape[2])


def decode_layer_scan(layers, x, kc_all, vc_all, pos, qkv_fn, attend_fn,
                      ksc_all=None, vsc_all=None):
    """The carry-scan decode layer loop shared by every decode path
    (transformer/llama decode_step, the TP generation loop).

    The KV cache rides the scan's CARRY with ONE in-place
    dynamic_update_slice per layer. Passing it as scan xs/ys instead (the
    obvious structure) makes XLA re-materialize the whole
    [L, B, H, D, max_len] buffer every step — measured 1.9x slower
    end-to-end GPT-2 decode on v5e (the copies, not attention math,
    dominated).

    The caches are [L, B, Hkv, D, max_len] (:func:`to_cache_layout`).
    qkv_fn(lp, x, pos) -> (q, k [B,W,H,D], v), token-major as the model
    computes them; attend_fn(lp, x, q, kc_l, vc_l, pos) -> x consumes
    the layer's UPDATED cache slices. Returns
    (x, kc_all, vc_all). ``pos`` may be a scalar (every row at the same
    position — the generate paths) or [B] (each slot at its own
    position — continuous-batching serving, models/serving.py), in
    which case the cache writes vmap per slot.

    With ``ksc_all``/``vsc_all`` ([L, B, H, 1, max_len] f32) the cache
    is INT8 (ops/kvquant.py): the fresh K/V vectors are quantized on
    write, the scale buffers ride the carry beside the code buffers,
    and attend_fn receives ``(codes, scales)`` tuples that
    :func:`grouped_decode_attend` consumes without ever materializing
    a dequantized cache (scale-on-scores factoring — see its
    docstring for the r05 chip A/B that killed the dequant-first
    design). Returns (x, kc, vc, ksc, vsc) then.
    """
    from mpi_acx_tpu.ops.kvquant import kv_quant

    n_layers = jax.tree.leaves(layers)[0].shape[0]
    quant = ksc_all is not None
    pos = jnp.asarray(pos)
    slotwise = pos.ndim == 1   # per-slot positions (serving.py)

    def write(cache, fresh, i):
        """Land fresh [B, W, H, *] at this layer's write position(s):
        one slice write at scalar pos, a vmapped per-slot write when
        each slot sits at its own position."""
        fresh = to_cache_layout(fresh)                 # [B, H, *, W]
        if not slotwise:
            return lax.dynamic_update_slice(cache, fresh[None],
                                            (i, 0, 0, 0, pos))
        layer = lax.dynamic_index_in_dim(cache, i, 0, keepdims=False)
        layer = jax.vmap(
            lambda c, f, p: lax.dynamic_update_slice(c, f, (0, 0, p)))(
            layer, fresh, pos)
        return lax.dynamic_update_index_in_dim(cache, layer, i, 0)

    def body(carry, i):
        if quant:
            x, kc, vc, ksc, vsc = carry
        else:
            x, kc, vc = carry
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            layers)
        q, k, v = qkv_fn(lp, x, pos)
        if quant:
            k, ks = kv_quant(k)
            v, vs = kv_quant(v)
            ksc = write(ksc, ks, i)
            vsc = write(vsc, vs, i)
        kc = write(kc, k, i)
        vc = write(vc, v, i)
        kc_l = lax.dynamic_index_in_dim(kc, i, 0, keepdims=False)
        vc_l = lax.dynamic_index_in_dim(vc, i, 0, keepdims=False)
        if quant:
            kc_l = (kc_l, lax.dynamic_index_in_dim(ksc, i, 0,
                                                   keepdims=False))
            vc_l = (vc_l, lax.dynamic_index_in_dim(vsc, i, 0,
                                                   keepdims=False))
        x = attend_fn(lp, x, q, kc_l, vc_l, pos)
        if quant:
            return (x, kc, vc, ksc, vsc), None
        return (x, kc, vc), None

    if quant:
        (x, kc_all, vc_all, ksc_all, vsc_all), _ = lax.scan(
            body, (x, kc_all, vc_all, ksc_all, vsc_all),
            jnp.arange(n_layers))
        return x, kc_all, vc_all, ksc_all, vsc_all
    (x, kc_all, vc_all), _ = lax.scan(body, (x, kc_all, vc_all),
                                      jnp.arange(n_layers))
    return x, kc_all, vc_all


def pack_kv(k, v, kv_int8: bool):
    """Token-major K/V ``[..., S, H, D]`` as a prompt pass computes
    them -> the form that lands in a cache, a page pool or a KV wire
    partition: ``{'k','v': [..., H, D, S]}`` in cache layout, quantized
    to int8 codes plus ``'ks','vs'`` f32 scales ``[..., H, 1, S]`` when
    ``kv_int8``. The ONE definition, so neither the int8 form nor the
    layout can drift between the families and the serving planes."""
    from mpi_acx_tpu.ops.kvquant import kv_quant
    out = {}
    if kv_int8:
        k, ks = kv_quant(k)
        v, vs = kv_quant(v)
        out["ks"], out["vs"] = to_cache_layout(ks), to_cache_layout(vs)
    out["k"], out["v"] = to_cache_layout(k), to_cache_layout(v)
    return out


def fill_kv_cache(cache, ks, vs, pos):
    """Land the prefill K/V ([L, B, S, H, D], token-major, compute
    dtype) into a fresh cache from a family's ``init_kv_cache`` and set
    ``pos`` — quantizing when the cache is int8 ('ks' present)."""
    for key, val in pack_kv(ks, vs, "ks" in cache).items():
        cache[key] = lax.dynamic_update_slice(cache[key], val, (0,) * 5)
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    return cache


def run_decode_layers(layers, x, cache, qkv_fn, attend_fn):
    """:func:`decode_layer_scan` dispatched on the cache layout (bf16
    vs int8 — the ONE place 'ks' selects the quantized path), returning
    ``(x, updated cache)`` with ``pos`` advanced by the one decoded
    token."""
    pos = cache["pos"]
    if "ks" in cache:
        x, kc, vc, ksc, vsc = decode_layer_scan(
            layers, x, cache["k"], cache["v"], pos, qkv_fn, attend_fn,
            ksc_all=cache["ks"], vsc_all=cache["vs"])
        return x, {"k": kc, "v": vc, "ks": ksc, "vs": vsc,
                   "pos": pos + 1}
    x, kc, vc = decode_layer_scan(layers, x, cache["k"], cache["v"],
                                  pos, qkv_fn, attend_fn)
    return x, {"k": kc, "v": vc, "pos": pos + 1}


def greedy_generate(prefill_fn: Callable, decode_fn: Callable,
                    prompt, n_new: int, max_seq: int,
                    max_len: Optional[int] = None):
    """prompt [B, S] -> [B, S + n_new] by greedy argmax.

    prefill_fn(tokens, max_len, last_only) -> (logits [B, *, vocab], cache)
    decode_fn(cache, token [B]) -> (logits [B, vocab], cache)
    """
    B, S = prompt.shape
    if max_len is None:
        max_len = S + n_new
    assert S + n_new <= max_len, (S, n_new, max_len)
    # The position table/rope ceiling is hard: past it, position lookups
    # clamp silently and every token reuses the last row.
    assert S + n_new <= max_seq, (S, n_new, max_seq)
    logits, cache = prefill_fn(prompt, max_len, True)
    first = jnp.argmax(logits[:, -1], axis=-1).astype(prompt.dtype)

    def step(carry, _):
        cache, tok = carry
        logits, cache = decode_fn(cache, tok)
        nxt = jnp.argmax(logits, axis=-1).astype(tok.dtype)
        return (cache, nxt), tok

    (_, _), toks = lax.scan(step, (cache, first), None, length=n_new)
    return jnp.concatenate([prompt, jnp.moveaxis(toks, 0, 1)], axis=1)


def sample_logits(logits, key, temperature: float = 1.0,
                  top_k: Optional[int] = None, top_p: Optional[float] = None):
    """Sample token ids from [B, vocab] f32 logits.

    Filters compose in the standard order: top-k first, then top-p
    (nucleus) over the surviving mass, then a Gumbel draw at the given
    temperature. ``temperature=0`` degenerates to argmax. Static-shaped
    (masking, not gathering), so it jits and scans cleanly.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    neg = jnp.finfo(logits.dtype).min
    V = logits.shape[-1]
    want_k = top_k is not None and top_k < V
    want_p = top_p is not None and top_p < 1.0
    if want_k or want_p:
        # ONE descending sort serves both filters (a second full-vocab
        # sort per decode step would dominate the filter cost).
        srt = jnp.sort(logits, axis=-1)[:, ::-1]         # [B, V] desc
        if want_k:
            kth = srt[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, neg, logits)
            # Nucleus below operates on the top-k-FILTERED distribution
            # (sequential composition, the standard order).
            srt = jnp.where(jnp.arange(V)[None, :] >= top_k, neg, srt)
        if want_p:
            # Keep the smallest prefix of descending-prob tokens whose
            # mass reaches top_p; the top-1 token always survives.
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = (cum - probs < top_p).at[:, 0].set(True)
            thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
            logits = jnp.where(logits < thresh[:, None], neg, logits)
    # Gumbel-max draw == categorical sample over the filtered softmax.
    g = jax.random.gumbel(key, logits.shape, logits.dtype)
    return jnp.argmax(logits + g, axis=-1)


def sample_generate(prefill_fn: Callable, decode_fn: Callable,
                    prompt, n_new: int, max_seq: int, key,
                    temperature: float = 1.0, top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    max_len: Optional[int] = None):
    """prompt [B, S] -> [B, S + n_new] by stochastic sampling (temperature
    / top-k / top-p); same contract as :func:`greedy_generate` plus a PRNG
    key. One jittable program: the whole decode is a lax.scan."""
    B, S = prompt.shape
    if max_len is None:
        max_len = S + n_new
    assert S + n_new <= max_len, (S, n_new, max_len)
    assert S + n_new <= max_seq, (S, n_new, max_seq)
    logits, cache = prefill_fn(prompt, max_len, True)
    key, sub = jax.random.split(key)
    first = sample_logits(logits[:, -1].astype(jnp.float32), sub,
                          temperature, top_k, top_p).astype(prompt.dtype)

    def step(carry, _):
        cache, tok, key = carry
        logits, cache = decode_fn(cache, tok)
        key, sub = jax.random.split(key)
        nxt = sample_logits(logits.astype(jnp.float32), sub, temperature,
                            top_k, top_p).astype(tok.dtype)
        return (cache, nxt, key), tok

    (_, _, _), toks = lax.scan(step, (cache, first, key), None, length=n_new)
    return jnp.concatenate([prompt, jnp.moveaxis(toks, 0, 1)], axis=1)
