"""Nemotron-H (``model_type`` ``nemotron_h``: NVIDIA-Nemotron-3-Super-
120B-A12B), plainly: the forward pass in ``jax.numpy``.

float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, one sequence at a time, the recurrence a plain
``lax.scan`` over the TOKENS (not the chunked form the program computes a
prefill in), the experts one after the other over every token, and no
import from the program. It follows the catalog row's ``config`` (source:
the published ``config.json``) and HF's ``modeling_nemotron_h.py``
(RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; no bias but the conv's and
``dt``'s):

* Block: ``x <- x + mixer(RMSNorm(x; eps 1e-5))``, ONE mixer a layer by
  ``hybrid_override_pattern`` (``M``, ``*`` or ``E``); a final RMSNorm,
  an untied head, no scaling.
* ``M`` (Mamba-2): ``[z | xBC | dt] = x W_in`` (4096 -> 8192 + 10240 +
  128, no bias); ``xBC <- silu(b + sum_j w[j] * xBC_{t - (taps - 1) +
  j})`` (depthwise, causal, zeros before the sequence, kernel 4), split
  ``x`` 8192 = 128 heads x 64, ``B``, ``C`` 8 groups x 128 (a group's
  ``B``/``C`` serve its 16 consecutive heads); ``dt <- softplus(dt +
  dt_bias)``; ``a = -exp(A_log)`` a head; per token and head ``h_t =
  exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``;
  ``y <- RMSNorm_grouped(y * silu(z); 8 groups of 1024) * w``; out ``8192
  -> 4096``.
* ``*``: ``q, k, v = x W_q, x W_k, x W_v`` (32 / 2 / 2 heads of 128, no
  bias), every query head on its K/V head, causal softmax at ``1 /
  sqrt(128)``, ``W_o``. NO positional encoding: the ``nemotron_h``
  attention applies none (``rope_theta`` and ``partial_rotary_factor``
  are keys its code does not read; the configuration lists this under
  ``assumed``).
* ``E``: ``s = sigmoid(x W_g)`` (512 wide); the top 22 of ``s + b``
  chosen (``n_group`` 1: no group limit; of equal scores the lower index),
  weights ``s`` of the chosen, normalised (``norm_topk_prob``), x 5.0
  (``routed_scaling_factor``); ``u = x W_dn`` (4096 -> 1024); ``r = sum_i
  w_i W2_i relu(W1_i u)^2`` (1024 -> 2688 -> 1024, no gate) over the
  chosen experts HELD here (``hp``'s ``first`` and the leading axis of
  ``w1``); ``out = r W_up (1024 -> 4096) + W2_s relu(W1_s x)^2`` (the
  shared expert, 4096 -> 5376 -> 4096).

Departures from the published model, all the configuration's (``reduced``,
``published``): the layers are the stage's, the experts a share of the
router's 512 (what absent experts would add is left out here as in the
program), the vocabulary its first rows, and no multi-token-prediction
module is loaded.

The weights are the benchmark's own (``weights_nemotron.make_nemotron``),
stacked by stretch, a layer (an expert) upcast at a time; ``plan``
(``weights_nemotron.plan``) says where each layer's leaves lie. ``hp`` is
``hyper(c)``: the numbers of the configuration file this file reads, as a
hashable tuple.
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
        head_dim=c["head_dim"], eps=c["layer_norm_epsilon"],
        m_heads=c["mamba_num_heads"], m_head_dim=c["mamba_head_dim"],
        d_state=c["ssm_state_size"], n_groups=c["n_groups"],
        top_k=c["num_experts_per_tok"],
        scale=float(c["routed_scaling_factor"]),
        norm_topk=bool(c["norm_topk_prob"]),
        first=c.get("experts_held", {}).get("first", 0),
        heads_at_once=c.get("check", {}).get("reference_heads_at_once", 4),
    ).items()))


def _leaves(tree, entry):
    """One layer's leaves, still stacked, and its repeat."""
    _, key, place, r = entry
    return (tree[key] if place is None else tree[key][place]), r


def _get(stacked, r, name):
    return stacked[name][r].astype(F32)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _attention(u, lp, r, h):
    """(y [T, d], k, v [T, n_kv, D] as cached: no position in them)."""
    T = u.shape[0]
    D, rep = h["head_dim"], h["n_head"] // h["n_kv"]
    q = (u @ _get(lp, r, "wq")).reshape(T, h["n_head"], D)
    k = (u @ _get(lp, r, "wk")).reshape(T, h["n_kv"], D)
    v = (u @ _get(lp, r, "wv")).reshape(T, h["n_kv"], D)
    mask = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):
        qh, kh, vh = qkv                                    # [T, D] a head
        s = (qh @ kh.T) / jnp.sqrt(F32(D))
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1) @ vh

    o = lax.map(head, (q.transpose(1, 0, 2),
                       jnp.repeat(k, rep, axis=1).transpose(1, 0, 2),
                       jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)),
                batch_size=h["heads_at_once"])              # [H, T, D]
    return o.transpose(1, 0, 2).reshape(T, -1) @ _get(lp, r, "wo"), k, v


def _mamba(hn, lp, r, h, h_at=(), state_dtype=F32):
    """(y [T, d], u [T, conv_dim] the conv's inputs, whose rows ``t -
    taps + 2 .. t`` are the window after token t; the recurrence's state
    [len(h_at), H, P, N] after the tokens ``h_at``, ascending).
    ``state_dtype``: what the carried state is rounded to after every
    token (a test's lower precision)."""
    T = hn.shape[0]
    H, P, N, G = h["m_heads"], h["m_head_dim"], h["d_state"], h["n_groups"]
    C = H * P
    conv = C + 2 * G * N                        # x, B and C
    zxd = hn @ _get(lp, r, "w_in")
    z, u, dt = zxd[:, :C], zxd[:, C:C + conv], zxd[:, C + conv:]
    w, bias = _get(lp, r, "conv_w"), _get(lp, r, "conv_b")      # [taps, conv]
    taps = w.shape[0]
    up = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u], 0)
    uc = jax.nn.silu(bias + sum(w[j] * up[j:j + T] for j in range(taps)))
    xs = uc[:, :C].reshape(T, H, P)
    b = jnp.repeat(uc[:, C:C + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(uc[:, C + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _get(lp, r, "dt_bias"))           # [T, H]
    a = -jnp.exp(_get(lp, r, "A_log"))                          # [H]

    def step(st, t):
        dt_t, x_t, b_t, c_t = t
        st = (jnp.exp(dt_t * a)[:, None, None] * st
              + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        st = st.astype(state_dtype).astype(F32)
        return st, jnp.einsum("hpn,hn->hp", st, c_t)

    st, ys, kept, at = jnp.zeros((H, P, N), F32), [], [], 0
    for stop in tuple(t + 1 for t in h_at) + (T,):
        st, y = lax.scan(step, st, (dt[at:stop], xs[at:stop], b[at:stop],
                                    c[at:stop]))
        ys.append(y)
        kept.append(st)
        at = stop
    y = jnp.concatenate(ys, 0) + _get(lp, r, "D")[:, None] * xs
    y = (y.reshape(T, C) * jax.nn.silu(z)).reshape(T, G, C // G)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + h["eps"])
    y = y.reshape(T, C) * _get(lp, r, "mix_norm")
    return (y @ _get(lp, r, "w_out"), u,
            jnp.stack(kept[:-1]) if h_at else jnp.zeros((0, H, P, N), F32))


def route(u, gate, bias, h):
    """[T, E] weights: ``p`` at each token's chosen experts, 0 elsewhere."""
    T = u.shape[0]
    s = jax.nn.sigmoid(u @ gate)
    idx = jnp.argsort(-(s + bias), axis=-1, stable=True)[:, :h["top_k"]]
    p = jnp.take_along_axis(s, idx, -1)
    if h["norm_topk"]:
        p = p / (jnp.sum(p, -1, keepdims=True) + 1e-20)
    p = p * h["scale"]
    return jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx].set(p)


def routed_latent(u, lp, r, h, only=None):
    """The held experts' part of the routed sum, in the latent [T,
    latent] (``only`` an expert's index here: a piece a test adds up)."""
    comb = route(u, _get(lp, r, "gate"), _get(lp, r, "bias"), h)
    ul = u @ _get(lp, r, "w_dn")

    def one(acc, e):
        w1, w2 = (lax.dynamic_index_in_dim(
            lax.index_in_dim(lp[n], r, 0, keepdims=False), e, 0,
            keepdims=False).astype(F32) for n in ("w1", "w2"))
        p = lax.dynamic_index_in_dim(comb, h["first"] + e, 1, keepdims=True)
        return acc + p * (_relu2(ul @ w1) @ w2), None

    es = (jnp.arange(lp["w1"].shape[1]) if only is None
          else jnp.asarray([only]))
    return lax.scan(one, jnp.zeros_like(ul), es)[0]


def _experts(u, lp, r, h, parts=("routed", "shared")):
    """The ``E`` mixer on the normed input ``u`` (``parts``: what a test
    leaves out)."""
    y = jnp.zeros_like(u)
    if "routed" in parts:
        y = y + routed_latent(u, lp, r, h) @ _get(lp, r, "w_up")
    if "shared" in parts:
        y = y + _relu2(u @ _get(lp, r, "ws1")) @ _get(lp, r, "ws2")
    return y


def _layers(tree, tokens, plan, h, upto=None, h_at=(), parts=("routed",
                                                              "shared")):
    """(x [T, d] after ``upto`` layers (all), [k], [v] of the attention
    layers passed, [u], [h] of the Mamba layers passed)."""
    x = tree["embed"][tokens].astype(F32)
    ks, vs, us, hs = [], [], [], []
    for entry in plan[:upto]:
        lp, r = _leaves(tree, entry)
        hn = _rms(x, _get(lp, r, "norm1"), h["eps"])
        if entry[0] == "*":
            y, k, v = _attention(hn, lp, r, h)
            ks.append(k)
            vs.append(v)
        elif entry[0] == "M":
            y, u, st = _mamba(hn, lp, r, h, h_at)
            us.append(u)
            hs.append(st)
        else:
            y = _experts(hn, lp, r, h, parts)
        x = x + y
    return x, ks, vs, us, hs


@functools.partial(jax.jit, static_argnames=("plan", "hp", "parts"))
def logits_from(tree, tokens, first, n_rows, *, plan, hp,
                parts=("routed", "shared")):
    """Next-token logits [n_rows.shape[0], vocab] of one sequence
    ``tokens`` [T] at positions ``first .. first + rows`` (``n_rows`` is
    a dummy array whose length is the static row count)."""
    h = dict(hp)
    with jax.default_matmul_precision("highest"):
        x = _layers(tree, tokens, plan, h, parts=parts)[0]
        x = _rms(x, tree["final_norm"].astype(F32), h["eps"])
        x = lax.dynamic_slice_in_dim(x, first, n_rows.shape[0], axis=0)
        return x @ tree["head"].astype(F32)


@functools.partial(jax.jit, static_argnames=("plan", "hp", "upto", "h_at"))
def states(tree, tokens, *, plan, hp, upto=None, h_at=()):
    """What a cache holds of one sequence ``tokens`` [T], from the first
    ``upto`` layers (all): (k, v [attention layers, T, n_kv, D]; u [Mamba
    layers, T, conv_dim], the conv's inputs; h [Mamba layers, len(h_at),
    H, P, N], the recurrence's state after the tokens ``h_at``)."""
    with jax.default_matmul_precision("highest"):
        _, ks, vs, us, hs = _layers(tree, tokens, plan, dict(hp), upto, h_at)
    stack = lambda a: jnp.stack(a) if a else None
    return stack(ks), stack(vs), stack(us), stack(hs)
