"""GigaChat3 Ultra (``deepseek_v3``: ai-sage GigaChat3.1-702B-A36B),
plainly: the forward pass in ``jax.numpy``.

float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, no absorption, one sequence at a time, and no
import from the program. It follows the published ``config.json`` and
HF's ``DeepseekV3*`` modules (``u`` is the RMS-normed input; no bias
anywhere; RMSNorm ``x / sqrt(mean(x^2) + eps) * g``):

* layer l: ``h = x + Attn(norm(x))``; ``out = h + FFN_l(norm(h))``; a
  final RMSNorm, then the (untied) head.
* attention (MLA): ``c_q = norm(W_DQ u)``; ``q = W_UQ c_q`` as ``H``
  heads of ``[q_nope | q_rope]``; ``[c | k_r] = W_DKV u``; ``c_kv =
  norm(c)``; ``k_rope = RoPE(k_r)``, one head shared by all; K and V are
  FORMED for every head, ``k_h = [W_UK_h c_kv | k_rope]``, ``v_h = W_UV_h
  c_kv``; causal ``softmax(q_h k_h^T * scale) v_h`` with ``scale = (nope
  + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
  an output projection. What the cache holds of a token is the row ``[c_kv
  | k_rope]``.
* RoPE: YaRN's inverse frequencies (the original ones ``theta^(-2i /
  rope)`` and the same ``/ factor``, blended by the linear ramp over the
  pair index i between ``floor(pair(beta_fast))`` and
  ``ceil(pair(beta_slow))``, ``pair(t) = rope * ln(original / (2 pi t)) /
  (2 ln theta)``); cos and sin times ``mscale / mscale_all_dim`` term's
  ratio (1 as published). **Pairing convention, stated:** the rope
  values are rotated in the pairs ``(2i, 2i + 1)`` (DeepSeek's
  checkpoints interleave) and the rotated pair is written to ``(i, rope /
  2 + i)``, as HF's ``apply_rotary_pos_emb`` for this model type does
  (de-interleave, then rotate-half): q and k share the permutation, so
  the scores are those of the interleaved form.
* dense FFN (``l < first_k_dense_replace``): ``W2 (silu(W1 u) * W3 u)``.
* expert FFN: ``s = sigmoid(W_g u)``; ``s' = s + b``; the experts lie in
  ``n_group`` groups of consecutive ones; a group's score is the sum of
  its two largest ``s'``; the ``topk_group`` best groups are kept; the
  ``top_k`` largest ``s'`` inside them are selected (of equal scores the
  lower index wins, groups and experts alike); ``p = s`` of the
  selected, ``/ (sum p + 1e-20)`` when ``norm_topk``, ``* scale``; ``y =
  shared(u) + sum_i p_i E_i(u)``, every expert and the shared one a
  SwiGLU. No capacity, no drop.

Departures from the published description, all of them:

* only the experts HELD are computed (``first`` and the leading axis of
  the expert leaves), as the program is told: what an absent expert
  would have added is left out, here as there; the shared expert is
  computed whole. The router keeps its published width;
* the vocabulary is whatever slice the tree's ``embed`` / ``head`` hold;
* the multi-token-prediction module is not loaded (the reference
  inference code does not load it either);
* dropped groups are taken OUT of the selection where HF fills their
  scores with 0.0: the same choice unless a kept group's ``top_k``-th
  best ``s'`` is negative;
* experts are taken ONE AT A TIME (a scan), each upcast alone, and the
  heads a few at a time, so that neither 10 GB of bfloat16 weights nor
  64 heads' 8192 x 8192 scores ever stand in float32 at once.

The weights are the benchmark's own (``weights_gigachat.make_gigachat``);
``plan`` (``weights_gigachat.plan``) says where each layer's leaves lie.
``hp`` is ``hyper(c)``: the numbers of the configuration file this file
reads, as a hashable tuple.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def hyper(c: dict) -> tuple:
    """The configuration's numbers this reference reads."""
    rs = c["rope_scaling"]
    return tuple(sorted(dict(
        n_head=c["num_attention_heads"], kv_rank=c["kv_lora_rank"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v=c["v_head_dim"], eps=c["rms_norm_eps"],
        theta=float(c["rope_theta"]), factor=float(rs["factor"]),
        original=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all=float(rs["mscale_all_dim"]),
        top_k=c["num_experts_per_tok"], n_group=c["n_group"],
        topk_group=c["topk_group"], norm_topk=bool(c["norm_topk_prob"]),
        scale=float(c["routed_scaling_factor"]),
        first=c.get("experts_held", {}).get("first", 0),
        heads_at_once=c.get("check", {}).get("reference_heads_at_once", 4),
    ).items()))


def _get(stacked, r, name):
    return stacked[name][r].astype(F32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(h) -> jnp.ndarray:
    """YaRN's [rope / 2] inverse frequencies (module docstring)."""
    dim, base = h["rope"], h["theta"]
    i = jnp.arange(dim // 2, dtype=F32)
    extra = base ** (-2.0 * i / dim)

    def pair(turns):
        return dim * math.log(h["original"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(pair(h["beta_fast"])), 0)
    high = min(math.ceil(pair(h["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / h["factor"] * ramp + extra * (1.0 - ramp)


def _rope(x, h):
    """x [T, heads, rope] at positions 0..T-1 (module docstring: pairs
    (2i, 2i + 1), written to (i, rope / 2 + i))."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq(h)[None]
    amp = _m(h["factor"], h["mscale"]) / _m(h["factor"], h["mscale_all"])
    cos, sin = amp * jnp.cos(ang)[:, None], amp * jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(u, lp, r, h):
    """(y [T, d], row [T, kv_rank + rope]: what the cache holds)."""
    T = u.shape[0]
    H, nope, rope, kr = h["n_head"], h["nope"], h["rope"], h["kv_rank"]
    cq = _rms(u @ _get(lp, r, "w_dq"), _get(lp, r, "q_norm"), h["eps"])
    q = (cq @ _get(lp, r, "w_uq")).reshape(T, H, nope + rope)
    ckr = u @ _get(lp, r, "w_dkv")
    c = _rms(ckr[:, :kr], _get(lp, r, "kv_norm"), h["eps"])
    k_rope = _rope(ckr[:, None, kr:], h)                        # [T, 1, rope]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], h)], -1)
    k = jnp.concatenate(
        [(c @ _get(lp, r, "w_uk")).reshape(T, H, nope),
         jnp.broadcast_to(k_rope, (T, H, rope))], -1)
    v = (c @ _get(lp, r, "w_uv")).reshape(T, H, h["v"])
    m = _m(h["factor"], h["mscale_all"])
    scale = (nope + rope) ** -0.5 * m * m
    mask = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        qh, kh, vh = qkv                                    # [T, *] a head
        s = (qh @ kh.T) * scale
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ vh

    o = lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q, k, v)),
                batch_size=h["heads_at_once"])              # [H, T, v]
    y = o.transpose(1, 0, 2).reshape(T, H * h["v"]) @ _get(lp, r, "w_o")
    return y, jnp.concatenate([c, k_rope[:, 0]], -1)


def _swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def route(u, gate, bias, h):
    """[T, E] weights: ``p`` at each token's chosen experts, 0 elsewhere."""
    T, E = u.shape[0], gate.shape[1]
    s = jax.nn.sigmoid(u @ gate)
    pick = (s + bias).reshape(T, h["n_group"], -1)
    two = -jnp.sort(-pick, axis=-1)[..., :2].sum(-1)            # [T, groups]
    best = jnp.argsort(-two, axis=-1, stable=True)[:, :h["topk_group"]]
    kept = jnp.zeros((T, h["n_group"]), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    pick = jnp.where(kept[:, :, None], pick, -jnp.inf).reshape(T, E)
    idx = jnp.argsort(-pick, axis=-1, stable=True)[:, :h["top_k"]]
    p = jnp.take_along_axis(s, idx, -1)
    if h["norm_topk"]:
        p = p / (jnp.sum(p, -1, keepdims=True) + 1e-20)
    p = p * h["scale"]
    return jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx].set(p)


def _moe(u, lp, r, h, shared=True, only=None):
    """shared(u) + the held experts' part (``shared`` False, ``only`` an
    expert's index here: the pieces a test adds up)."""
    comb = route(u, _get(lp, r, "gate"), _get(lp, r, "bias"), h)
    n_held = lp["w1"].shape[1]

    def one(acc, e):
        w1, w3, w2 = (lax.dynamic_index_in_dim(
            lax.index_in_dim(lp[n], r, 0, keepdims=False), e, 0,
            keepdims=False).astype(F32) for n in ("w1", "w3", "w2"))
        p = lax.dynamic_index_in_dim(comb, h["first"] + e, 1, keepdims=True)
        return acc + p * _swiglu(u, w1, w3, w2), None

    es = jnp.arange(n_held) if only is None else jnp.asarray([only])
    y = lax.scan(one, jnp.zeros_like(u), es)[0]
    if shared:
        y = y + _swiglu(u, *(_get(lp, r, n) for n in ("ws1", "ws3", "ws2")))
    return y


def _layers(tree, tokens, plan, h, upto=None):
    """(x [T, d] after ``upto`` layers (all), [row] of the layers
    passed)."""
    x = tree["embed"][tokens].astype(F32)
    rows = []
    for ffn, key, r in plan[:upto]:
        lp = tree[key]
        y, row = _attention(_rms(x, _get(lp, r, "attn_norm"), h["eps"]),
                            lp, r, h)
        rows.append(row)
        x = x + y
        u = _rms(x, _get(lp, r, "ffn_norm"), h["eps"])
        x = x + (_swiglu(u, *(_get(lp, r, n) for n in ("w1", "w3", "w2")))
                 if ffn == "dense" else _moe(u, lp, r, h))
    return x, rows


@functools.partial(jax.jit, static_argnames=("plan", "hp"))
def logits_from(tree, tokens, first, n_rows, *, plan, hp):
    """Next-token logits [n_rows.shape[0], vocab] of one sequence
    ``tokens`` [T] at positions ``first .. first + rows`` (``n_rows`` is
    a dummy array whose length is the static row count)."""
    h = dict(hp)
    with jax.default_matmul_precision("highest"):
        x = _layers(tree, tokens, plan, h)[0]
        x = _rms(x, tree["final_norm"].astype(F32), h["eps"])
        x = lax.dynamic_slice_in_dim(x, first, n_rows.shape[0], axis=0)
        return x @ tree["head"].astype(F32)


@functools.partial(jax.jit, static_argnames=("plan", "hp", "upto"))
def states(tree, tokens, *, plan, hp, upto=None):
    """What a latent cache holds of one sequence ``tokens`` [T], from
    the first ``upto`` layers (all): rows [layers, T, kv_rank + rope],
    ``[c_kv | k_rope]`` a token."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack(_layers(tree, tokens, plan, dict(hp), upto)[1])
