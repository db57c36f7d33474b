"""LFM2-MoE (``lfm2_moe``: LiquidAI LFM2-24B-A2B), plainly: the forward
pass in ``jax.numpy``.

float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, one sequence at a time, and no import from the
program. It follows the published ``config.json`` and HF's ``Lfm2Moe*``
modules (``u`` is the RMS-normed input; no bias anywhere; RMSNorm ``x /
sqrt(mean(x^2) + eps) * g``):

* layer l: ``h = x + Op_l(norm(x))``; ``out = h + FFN_l(norm(h))``;
  a final RMSNorm, then the head.
* attention (``full_attention``): q as ``n_head`` heads, k and v as
  ``n_kv`` heads of ``d / n_head``; q and k RMS-normed over the head's
  dims (one weight each) BEFORE RoPE (``theta``, over the whole head,
  rotate-half); causal ``softmax(q k^T / sqrt(head)) v``, a K/V head
  shared by ``n_head / n_kv`` query heads; an output projection.
* gated short conv (``conv``): ``[B | C | X] = W_in u``; ``z = B * X``;
  ``c_t = sum_j w[:, j] z_{t - (taps - 1) + j}`` (depthwise, causal,
  zeros before the sequence); ``y = W_out (C * c)``.
* dense FFN (``l < num_dense_layers``): ``W2 (silu(W1 u) * W3 u)``.
* routed FFN: ``s = sigmoid(W_g u)``; the ``top_k`` of ``s + b`` are
  selected (the bias only selects; of equal scores the lower index
  wins); ``p = s`` of the selected, ``/ (sum p + 1e-6)`` when
  ``norm_topk``, ``* scale``; ``y = sum_i p_i W2_i (silu(W1_i u) * W3_i
  u)``. No shared expert, no capacity, no drop.

Departures from the published description, all of them:

* the head is TIED to the embedding (the catalog row gives no
  ``tie_word_embeddings``; LFM2 ties);
* only the experts HELD are computed (``first`` and the leading axis of
  the expert leaves), as the program is told: what an absent expert
  would have added is left out, here as there. With every expert held,
  as in the benchmark's configuration, that is no departure;
* experts are taken ONE AT A TIME (a scan), each upcast alone, so that
  10 GB of bfloat16 weights never stand in float32 at once; each is
  applied to every token and weighted by ``p`` (exactly 0 for a token
  that did not choose it): the same sum at 16x the multiplications.

The weights are the benchmark's own (``weights_lfm2.make_lfm2``),
stacked by stretch; ``plan`` (``weights_lfm2.plan``) says where each
layer's leaves lie. ``hp`` is ``hyper(c)``: the numbers of the
configuration file this file reads, as a hashable tuple.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def hyper(c: dict) -> tuple:
    """The configuration's numbers this reference reads."""
    return tuple(sorted(dict(
        n_head=c["num_attention_heads"], n_kv=c["num_key_value_heads"],
        eps=c["norm_eps"], theta=float(c["rope_parameters"]["rope_theta"]),
        top_k=c["num_experts_per_tok"], norm_topk=bool(c["norm_topk_prob"]),
        scale=float(c["routed_scaling_factor"]),
        use_bias=bool(c["use_expert_bias"]),
        first=c.get("experts_held", {}).get("first", 0)).items()))


def _leaves(tree, entry):
    """One layer's leaves, still stacked, and its repeat."""
    _, _, key, place, r = entry
    stacked = tree[key] if place is None else tree[key][place]
    return stacked, r


def _get(stacked, r, name):
    return stacked[name][r].astype(F32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, rotate-half over the whole head."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)        # [D/2]
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]         # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]      # [T, 1, D]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(u, lp, r, h):
    """(y [T, d], k, v [T, n_kv, D]: keys as cached, normed and roped)."""
    T, d = u.shape
    D = d // h["n_head"]
    q = (u @ _get(lp, r, "wq")).reshape(T, h["n_head"], D)
    k = (u @ _get(lp, r, "wk")).reshape(T, h["n_kv"], D)
    v = (u @ _get(lp, r, "wv")).reshape(T, h["n_kv"], D)
    q = _rope(_rms(q, _get(lp, r, "q_norm"), h["eps"]), h["theta"])
    k = _rope(_rms(k, _get(lp, r, "k_norm"), h["eps"]), h["theta"])
    rep = h["n_head"] // h["n_kv"]              # query head i reads K/V i // rep
    s = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, rep, axis=1))
    s = s / jnp.sqrt(F32(D))
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, jnp.repeat(v, rep, axis=1))
    return o.reshape(T, d) @ _get(lp, r, "wo"), k, v


def _conv(u, lp, r):
    """(y [T, d], z [T, d] the gated input the convolution runs over)."""
    T, d = u.shape
    b, c, x = jnp.split(u @ _get(lp, r, "w_in"), 3, axis=-1)
    z = b * x
    w = _get(lp, r, "conv_w")                                   # [d, taps]
    taps = w.shape[1]
    zp = jnp.concatenate([jnp.zeros((taps - 1, d), F32), z], 0)
    conv = sum(w[:, j] * zp[j:j + T] for j in range(taps))
    return (c * conv) @ _get(lp, r, "w_out"), z


def _dense(u, lp, r):
    return (jax.nn.silu(u @ _get(lp, r, "w1")) * (u @ _get(lp, r, "w3"))) \
        @ _get(lp, r, "w2")


def route(u, gate, bias, h):
    """[T, E] weights: ``p`` at each token's chosen experts, 0 elsewhere."""
    s = jax.nn.sigmoid(u @ gate)
    pick = s + bias if h["use_bias"] else s
    idx = jnp.argsort(-pick, axis=-1, stable=True)[:, :h["top_k"]]
    p = jnp.take_along_axis(s, idx, -1)
    if h["norm_topk"]:
        p = p / (jnp.sum(p, -1, keepdims=True) + 1e-6)
    p = p * h["scale"]
    return jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None], idx].set(p)


def _moe(u, lp, r, h):
    comb = route(u, _get(lp, r, "gate"), _get(lp, r, "bias"), h)
    held = lp["w1"].shape[1]

    def one(acc, e):
        w1, w3, w2 = (lax.dynamic_index_in_dim(
            lax.index_in_dim(lp[n], r, 0, keepdims=False), e, 0,
            keepdims=False).astype(F32) for n in ("w1", "w3", "w2"))
        y = (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
        p = lax.dynamic_index_in_dim(comb, h["first"] + e, 1, keepdims=True)
        return acc + p * y, None

    return lax.scan(one, jnp.zeros_like(u), jnp.arange(held))[0]


def _layers(tree, tokens, plan, h, upto=None):
    """(x [T, d] after ``upto`` layers (all), [k], [v] of the attention
    layers passed, [z] of the conv layers passed)."""
    x = tree["embed"][tokens].astype(F32)
    ks, vs, zs = [], [], []
    for entry in plan[:upto]:
        lp, r = _leaves(tree, entry)
        u = _rms(x, _get(lp, r, "op_norm"), h["eps"])
        if entry[0] == "full_attention":
            y, k, v = _attention(u, lp, r, h)
            ks.append(k)
            vs.append(v)
        else:
            y, z = _conv(u, lp, r)
            zs.append(z)
        x = x + y
        u = _rms(x, _get(lp, r, "ffn_norm"), h["eps"])
        x = x + (_dense(u, lp, r) if entry[1] == "dense"
                 else _moe(u, lp, r, h))
    return x, ks, vs, zs


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
        return x @ tree["embed"].astype(F32).T


@functools.partial(jax.jit, static_argnames=("plan", "hp", "upto"))
def states(tree, tokens, *, plan, hp, upto=None):
    """What a cache holds of one sequence ``tokens`` [T], from the first
    ``upto`` layers (all): (k, v [attention layers, T, n_kv, D], the
    keys normed and roped as they are cached; z [conv layers, T, d],
    whose rows ``t - taps + 2 .. t`` are the conv state after token t)."""
    with jax.default_matmul_precision("highest"):
        _, ks, vs, zs = _layers(tree, tokens, plan, dict(hp), upto)
    stack = lambda a: jnp.stack(a) if a else None
    return stack(ks), stack(vs), stack(zs)
