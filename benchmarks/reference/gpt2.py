"""GPT-2, plainly: forward, loss, gradients and AdamW in ``jax.numpy``.

float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching tricks, and no import from the program. It follows
"Language Models are Unsupervised Multitask Learners" (Radford et al.,
2019) as released: learned positions, pre-LayerNorm blocks, a fused
``c_attn`` of width 3d split into q, k, v, causal softmax attention
scaled by 1/sqrt(head), tanh-GELU MLP of width ``n_inner``, final
LayerNorm, tied unembedding. Departures: none in the mathematics;
dropout is absent (inference, and training at rate 0).

The weights are the benchmark's own (``weights.make_gpt2``), stacked
over layers; each layer is upcast to float32 inside the scan, so a
model kept in bfloat16 never gets a second whole copy.

``precision`` is how training's CONTROL is made (the program has no
lower-precision training path; serving's control is the program's own
``kv_int8=True``): ``"f32"`` is the reference, ``"int8"`` rounds every
matmul operand to 8-bit symmetric codes first (activations per row,
weights per output column, K and V per token and head, and in the
backward pass the incoming gradient per row) — the nearest precision
below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _fq(x, axis):
    """Fake int8: symmetric codes along ``axis``, straight-through."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _mm_int8(x, w):
    """x [T, a] @ w [a, b] with every operand in int8, both ways."""
    return _fq(x, -1) @ _fq(w, 0)


def _mm_int8_fwd(x, w):
    xq, wq = _fq(x, -1), _fq(w, 0)
    return xq @ wq, (xq, wq)


def _mm_int8_bwd(res, g):
    xq, wq = res
    gq = _fq(g, -1)
    return gq @ wq.T, xq.T @ gq


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _mm(x, w, precision):
    return _mm_int8(x, w) if precision == "int8" else x @ w


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(x, lp, n_head, eps, precision, with_kv=False):
    """x [T, d] float32, lp one layer's leaves (any float dtype); with
    ``with_kv`` also this layer's keys and values, [H, T, hd] each."""
    lp = {k: v.astype(F32) for k, v in lp.items()}
    T, d = x.shape
    hd = d // n_head
    qkv = _mm(_ln(x, lp["ln1_g"], lp["ln1_b"], eps), lp["wqkv"], precision)
    q, k, v = (t.reshape(T, n_head, hd).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))         # [H, T, hd]
    if precision == "int8":
        k, v = _fq(k, -1), _fq(v, -1)
    s = jnp.einsum("htd,hsd->hts", q, k) / jnp.sqrt(F32(hd))
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,hsd->htd", p, v).transpose(1, 0, 2).reshape(T, d)
    x = x + _mm(o, lp["wo"], precision)
    h = _gelu_new(_mm(_ln(x, lp["ln2_g"], lp["ln2_b"], eps), lp["w1"],
                      precision) + lp["b1"])
    x = x + _mm(h, lp["w2"], precision) + lp["b2"]
    return (x, k, v) if with_kv else x


def _hidden(tree, tokens, n_head, eps, precision, remat=False):
    """tokens [T] -> final-LayerNormed hidden states [T, d]."""
    T = tokens.shape[0]
    x = tree["embed"][tokens].astype(F32) + tree["pos"][:T].astype(F32)
    body = functools.partial(_block, n_head=n_head, eps=eps,
                             precision=precision)
    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(lambda x, lp: (body(x, lp), None), x, tree["layers"])
    return _ln(x, tree["lnf_g"].astype(F32), tree["lnf_b"].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision"))
def logits_from(tree, tokens, first, n_rows, *, n_head, eps=1e-5,
                precision="f32"):
    """Next-token logits [n_rows.shape[0], vocab] of one sequence
    ``tokens`` [T] at positions ``first .. first + rows`` (``n_rows`` is
    a dummy array whose length is the static row count)."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(tree, tokens, n_head, eps, precision)
        x = lax.dynamic_slice_in_dim(x, first, n_rows.shape[0], axis=0)
        return _mm(x, tree["embed"].astype(F32).T, precision)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def kv_error(tree, tokens, got_k, got_v, *, n_head, eps=1e-5):
    """How far cached keys and values lie from the reference's, layer by
    layer: ``got_k``/``got_v`` [L, H, hd, T] are what a cache holds for
    the first T positions of a sequence that starts with ``tokens`` [T]
    (causal: later tokens change nothing there). Returns [L, 4] sums of
    squares: K difference, K reference, V difference, V reference."""
    T = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        x = tree["embed"][tokens].astype(F32) + tree["pos"][:T].astype(F32)

        def layer(x, xs):
            lp, gk, gv = xs
            x, k, v = _block(x, lp, n_head, eps, "f32", with_kv=True)
            sq = lambda a: jnp.sum(jnp.square(a))
            dk = gk.astype(F32).transpose(0, 2, 1) - k
            dv = gv.astype(F32).transpose(0, 2, 1) - v
            return x, jnp.stack([sq(dk), sq(k), sq(dv), sq(v)])

        return lax.scan(layer, x, (tree["layers"], got_k, got_v))[1]


def _loss(tree, tokens, targets, n_head, eps, precision):
    """Summed next-token cross-entropy of rows [R, S]."""
    def row(tok, tgt):
        x = _hidden(tree, tok, n_head, eps, precision, remat=True)
        lg = _mm(x, tree["embed"].astype(F32).T, precision)
        return -jnp.sum(jnp.take_along_axis(
            jax.nn.log_softmax(lg, -1), tgt[:, None], 1))
    return jnp.sum(lax.map(lambda a: row(*a), (tokens, targets)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "precision",
                                             "block_rows"))
def loss_and_grads(tree, tokens, targets, *, n_head, eps=1e-5,
                   precision="f32", block_rows=2):
    """Mean cross-entropy over all rows of ``tokens`` [B, S] and its
    gradient, accumulated ``block_rows`` rows at a time."""
    B, S = tokens.shape
    blocks = (tokens.reshape(B // block_rows, block_rows, S),
              targets.reshape(B // block_rows, block_rows, S))
    with jax.default_matmul_precision("highest"):
        def one(acc, blk):
            l, g = jax.value_and_grad(_loss)(tree, *blk, n_head, eps,
                                             precision)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None
        zero = (F32(0), jax.tree.map(jnp.zeros_like, tree))
        (l, g), _ = lax.scan(one, zero, blocks)
    n = B * S
    return l / n, jax.tree.map(lambda a: a / n, g)


@jax.jit
def adamw_step(tree, m, v, g, count, hp):
    """One AdamW step as optax.adamw defines it: bias-corrected
    moments, decoupled weight decay on every leaf, ``hp`` =
    (learning_rate, b1, b2, eps, weight_decay)."""
    lr, b1, b2, eps, wd = hp
    t = count + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    def upd(p, m, v):
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    return jax.tree.map(upd, tree, m, v), m, v
