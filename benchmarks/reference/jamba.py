"""Jamba (``model_type`` ``jamba``: AI21-Jamba2-3B), plainly: the forward
pass in ``jax.numpy``.

float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, one sequence at a time, the recurrence a plain
``lax.scan`` over the tokens, and no import from the program. It follows
the published ``config.json`` and HF's ``modeling_jamba.py`` (``h`` is the
RMS-normed input; RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``; no bias but
the conv's and ``dt``'s):

* layer i: ``x = x + mixer_i(norm1(x))``; ``x = x + W_down (silu(W_gate
  h) * W_up h)`` with ``h = norm2(x)``; a final RMSNorm, then the head,
  TIED to the embedding (``tie_word_embeddings`` true), no scaling.
* attention (``i % attn_layer_period == attn_layer_offset``): q as
  ``n_head`` heads, k and v as ``n_kv`` heads of ``d / n_head``; causal
  ``softmax(q k^T / sqrt(head)) v``, every query head on its K/V head;
  an output projection. No rotary, no positional term of any kind.
* Mamba (the rest): ``[u | z] = W_in h``; ``u = silu(b + sum_j w[j] *
  u_{t - (taps - 1) + j})`` (depthwise, causal, zeros before the
  sequence); ``[dt_r | B | C] = W_x u``, each through its own RMSNorm;
  ``dt = softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``; per token
  and channel ``h_t[c, :] = exp(dt_t[c] A[c, :]) h_{t-1}[c, :] + dt_t[c]
  B_t u_t[c]``, ``y_t[c] = h_t[c, :] . C_t + D[c] u_t[c]``; out =
  ``W_out (y * silu(z))``.

The weights are the benchmark's own (``weights_jamba.make_jamba``),
stacked by stretch, a layer upcast at a time; ``plan``
(``weights_jamba.plan``) says where each layer's leaves lie. The tree
keeps ``A_log`` as [N, C] and ``conv_w`` as [taps, C] (channels last);
here the state is HF's ``h`` [C, N]. ``hp`` is ``hyper(c)``: the numbers
of the configuration file this file reads, as a hashable tuple.
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
        eps=c["rms_norm_eps"], d_state=c["mamba_d_state"],
        dt_rank=c["mamba_dt_rank"]).items()))


def _leaves(tree, entry):
    """One layer's leaves, still stacked, and its repeat."""
    _, key, place, r = entry
    return (tree[key] if place is None else tree[key][place]), r


def _get(stacked, r, name):
    return stacked[name][r].astype(F32)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _attention(u, lp, r, h):
    """(y [T, d], k, v [T, n_kv, D] as cached: no position in them)."""
    T, d = u.shape
    D = d // h["n_head"]
    q = (u @ _get(lp, r, "wq")).reshape(T, h["n_head"], D)
    k = (u @ _get(lp, r, "wk")).reshape(T, h["n_kv"], D)
    v = (u @ _get(lp, r, "wv")).reshape(T, h["n_kv"], D)
    rep = h["n_head"] // h["n_kv"]
    s = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, rep, axis=1))
    s = s / jnp.sqrt(F32(D))
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, jnp.repeat(v, rep, axis=1))
    return o.reshape(T, -1) @ _get(lp, r, "wo"), k, v


def _mamba(hn, lp, r, h, h_at=(), terms=("D", "norms")):
    """(y [T, d], u [T, C] the conv's inputs, whose rows ``t - taps + 2
    .. t`` are the window after token t; the scan's state [len(h_at), C,
    N] after the tokens ``h_at``, ascending). ``terms``: what a control
    leaves out (the ``D u`` term; the three inner norms)."""
    T = hn.shape[0]
    u, z = jnp.split(hn @ _get(lp, r, "w_in"), 2, axis=-1)
    w, bias = _get(lp, r, "conv_w"), _get(lp, r, "conv_b")     # [taps, C]
    taps = w.shape[0]
    up = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u], 0)
    uc = jax.nn.silu(bias + sum(w[j] * up[j:j + T] for j in range(taps)))
    R, N = h["dt_rank"], h["d_state"]
    dt_r, b, c = jnp.split(uc @ _get(lp, r, "w_x"), (R, R + N), axis=-1)
    if "norms" in terms:
        dt_r = _rms(dt_r, _get(lp, r, "dt_norm"), h["eps"])
        b = _rms(b, _get(lp, r, "b_norm"), h["eps"])
        c = _rms(c, _get(lp, r, "c_norm"), h["eps"])
    dt = jax.nn.softplus(dt_r @ _get(lp, r, "w_dt") + _get(lp, r, "b_dt"))
    a = -jnp.exp(_get(lp, r, "A_log")).T                       # [C, N]

    def step(st, xs):
        dt_t, u_t, b_t, c_t = xs
        st = jnp.exp(dt_t[:, None] * a) * st \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return st, st @ c_t

    st, ys, kept, at = jnp.zeros(a.shape, F32), [], [], 0
    for stop in tuple(t + 1 for t in h_at) + (T,):
        st, y = lax.scan(step, st, (dt[at:stop], uc[at:stop], b[at:stop],
                                    c[at:stop]))
        ys.append(y)
        kept.append(st)
        at = stop
    y = jnp.concatenate(ys, 0)
    if "D" in terms:
        y = y + _get(lp, r, "D") * uc
    return ((y * jax.nn.silu(z)) @ _get(lp, r, "w_out"), u,
            jnp.stack(kept[:-1]) if h_at else jnp.zeros((0,) + a.shape, F32))


def _layers(tree, tokens, plan, h, upto=None, h_at=(), terms=("D", "norms")):
    """(x [T, d] after ``upto`` layers (all), [k], [v] of the attention
    layers passed, [u], [h] of the Mamba layers passed)."""
    x = tree["embed"][tokens].astype(F32)
    ks, vs, us, hs = [], [], [], []
    for entry in plan[:upto]:
        lp, r = _leaves(tree, entry)
        hn = _rms(x, _get(lp, r, "norm1"), h["eps"])
        if entry[0] == "attention":
            y, k, v = _attention(hn, lp, r, h)
            ks.append(k)
            vs.append(v)
        else:
            y, u, st = _mamba(hn, lp, r, h, h_at, terms)
            us.append(u)
            hs.append(st)
        x = x + y
        hn = _rms(x, _get(lp, r, "norm2"), h["eps"])
        x = x + (jax.nn.silu(hn @ _get(lp, r, "w_gate"))
                 * (hn @ _get(lp, r, "w_up"))) @ _get(lp, r, "w_down")
    return x, ks, vs, us, hs


@functools.partial(jax.jit, static_argnames=("plan", "hp", "terms"))
def logits_from(tree, tokens, first, n_rows, *, plan, hp,
                terms=("D", "norms")):
    """Next-token logits [n_rows.shape[0], vocab] of one sequence
    ``tokens`` [T] at positions ``first .. first + rows`` (``n_rows`` is
    a dummy array whose length is the static row count)."""
    h = dict(hp)
    with jax.default_matmul_precision("highest"):
        x = _layers(tree, tokens, plan, h, terms=terms)[0]
        x = _rms(x, tree["final_norm"].astype(F32), h["eps"])
        x = lax.dynamic_slice_in_dim(x, first, n_rows.shape[0], axis=0)
        return x @ tree["embed"].astype(F32).T


@functools.partial(jax.jit, static_argnames=("plan", "hp", "upto", "h_at"))
def states(tree, tokens, *, plan, hp, upto=None, h_at=()):
    """What a cache holds of one sequence ``tokens`` [T], from the first
    ``upto`` layers (all): (k, v [attention layers, T, n_kv, D]; u [Mamba
    layers, T, C], the conv's inputs; h [Mamba layers, len(h_at), C, N],
    the scan's state after the tokens ``h_at``)."""
    with jax.default_matmul_precision("highest"):
        _, ks, vs, us, hs = _layers(tree, tokens, plan, dict(hp), upto, h_at)
    stack = lambda a: jnp.stack(a) if a else None
    return stack(ks), stack(vs), stack(us), stack(hs)
