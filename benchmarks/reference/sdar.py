"""SDAR-MoE (``sdar_moe``: JetLM SDAR-30B-A3B-Chat), plainly: the forward
pass and the generation rule in ``jax.numpy``.

float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, one sequence at a time, and no import from the
program. It follows the published ``config.json`` (48 identical layers:
``decoder_sparse_step`` 1, ``mlp_only_layers`` [], so
``intermediate_size`` is unread; ``use_sliding_window`` false; untied
head) and HF's ``SDARMoe*`` / ``Qwen3Moe*`` modules (``u`` is the
RMS-normed input; no bias anywhere, ``attention_bias`` false; RMSNorm
``x / sqrt(mean(x^2) + eps) * g``, ``eps`` 1e-6):

* block: ``x <- x + Attn(RMSNorm(x))``; ``x <- x + MoE(RMSNorm(x))``; a
  final RMSNorm; ``logits = x W_head`` (2048 -> 151,936).
* attention: ``q, k, v = x W_q, x W_k, x W_v`` (2048 -> 32 x 128, 4 x
  128, 4 x 128); q and k RMS-normed over each head's 128 values (one
  weight vector for q, one for k) BEFORE RoPE (``theta`` 1e6 over the
  whole head, rotate-half, no scaling); softmax at ``1 / sqrt(128)``,
  K/V head ``h`` serves query heads ``8h .. 8h + 7``; ``W_o`` 4096 ->
  2048. **The mask is block-causal with block W**, in prefill and in
  generation alike: a position sees every earlier position and the whole
  of its own block.
* MoE: ``g = softmax(x W_r)`` over the 128 experts; the top 8 (of equal
  scores the lower index); weights ``g`` of the chosen divided by their
  sum (``norm_topk_prob``); ``out = sum_i w_i W_down,i (silu(W_gate,i x)
  * W_up,i x)`` (2048 -> 768 -> 2048); no shared expert.
* generation (the family's public ``generate.py``,
  ``block_diffusion_generate``, as ISSUE 48's author recalls it;
  ``config.json`` gives neither block length nor schedule: the
  configuration file's ``assumed``): the sequence is ``ceil((P + n_new)
  / W)`` blocks. The first ``(P // W) * W`` prompt tokens are stored
  block-causally. Then block by block: the block starts as its prompt
  tokens (if any) and mask tokens elsewhere; for ``denoising_steps``
  steps, run the block, take at every still-masked position ``t = argmax
  logits`` and ``c = softmax(logits)[t]``, commit the ``W /
  denoising_steps`` masked positions of highest ``c``
  (``low_confidence_static``; of equal ``c`` the lower position); when no
  mask is left the block is run once more and ITS K/V are what later
  blocks see. A committed position never changes.

Departures from the public script, all of them:

* "masked" is a FLAG of the position, not ``token == mask id`` (a head
  of random weights emits every id, and the traffic draws prompt ids
  from the whole vocabulary);
* a block with fewer masks than a step commits (a prompt's tail) commits
  what it has, and nothing in the steps it has no mask for;
* experts are taken ONE AT A TIME (a scan), each upcast alone, so that
  7 GB of bfloat16 experts never stand in float32 at once; each is
  applied to every token and weighted by its share (exactly 0 for a
  token that did not choose it): the same sum at 16x the
  multiplications;
* left out: the schedules whose number of forwards a block depends on
  the logits (``low_confidence_dynamic``, ``entropy_bounded``) and
  sampling at a temperature.

``forward_with`` is the one pass: ANY positions and ANY dense mask, so
that one call can also hold, behind a finished sequence, copies of some
of its blocks as they stood at an earlier denoising step (each copy sees
the finished blocks before its own and itself): what ``denoise`` gives a
call at a time. The weights are the benchmark's own
(``weights_sdar.make_sdar``): ``tree["layers"][name][l]``, ``embed``,
``head``, ``final_norm``. ``hp`` is ``hyper(c)``: the numbers of the
configuration file this file reads, as a hashable tuple.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32


def hyper(c: dict) -> tuple:
    """The configuration's numbers this reference reads."""
    g = c["generation"]
    return tuple(sorted(dict(
        n_layer=c["num_hidden_layers"], n_head=c["num_attention_heads"],
        n_kv=c["num_key_value_heads"], head=c["head_dim"],
        eps=c["rms_norm_eps"], theta=float(c["rope_theta"]),
        top_k=c["num_experts_per_tok"], norm_topk=bool(c["norm_topk_prob"]),
        block=g["block_length"], steps=g["denoising_steps"],
        mask_id=g["mask_token_id"]).items()))


def _get(tree, l, name):
    return tree["layers"][name][l].astype(F32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, positions, theta):
    """x [T, H, D] at ``positions`` [T], rotate-half over the whole head."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)        # [D/2]
    ang = positions.astype(F32)[:, None] * inv[None]            # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]      # [T, 1, D]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(u, tree, l, positions, mask, h):
    """(y [T, d], k, v [T, n_kv, D]: keys normed and roped, as a cache
    would hold them)."""
    T, D = u.shape[0], h["head"]
    q = (u @ _get(tree, l, "wq")).reshape(T, h["n_head"], D)
    k = (u @ _get(tree, l, "wk")).reshape(T, h["n_kv"], D)
    v = (u @ _get(tree, l, "wv")).reshape(T, h["n_kv"], D)
    q = _rope(_rms(q, _get(tree, l, "q_norm"), h["eps"]), positions,
              h["theta"])
    k = _rope(_rms(k, _get(tree, l, "k_norm"), h["eps"]), positions,
              h["theta"])
    rep = h["n_head"] // h["n_kv"]              # query head i reads K/V i // rep
    s = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, rep, axis=1))
    s = s / jnp.sqrt(F32(D))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, jnp.repeat(v, rep, axis=1))
    return o.reshape(T, -1) @ _get(tree, l, "wo"), k, v


def route(u, gate, h):
    """[T, E] weights: each token's share at its chosen experts, 0
    elsewhere."""
    g = jax.nn.softmax(u @ gate, axis=-1)
    idx = jnp.argsort(-g, axis=-1, stable=True)[:, :h["top_k"]]
    p = jnp.take_along_axis(g, idx, -1)
    if h["norm_topk"]:
        p = p / jnp.sum(p, -1, keepdims=True)
    return jnp.zeros_like(g).at[jnp.arange(u.shape[0])[:, None], idx].set(p)


def _moe(u, tree, l, h):
    comb = route(u, _get(tree, l, "gate"), h)

    def one(acc, e):
        w1, w3, w2 = (lax.dynamic_index_in_dim(
            lax.index_in_dim(tree["layers"][n], l, 0, keepdims=False), e, 0,
            keepdims=False).astype(F32) for n in ("w1", "w3", "w2"))
        y = (jax.nn.silu(u @ w1) * (u @ w3)) @ w2
        return acc + lax.dynamic_index_in_dim(comb, e, 1) * y, None

    return lax.scan(one, jnp.zeros_like(u),
                    jnp.arange(tree["layers"]["w1"].shape[1]))[0]


def block_mask(T: int, W: int):
    """[T, T] bool: row ``i`` sees column ``j`` iff ``j // W <= i // W``."""
    at = np.arange(T) // W
    return at[:, None] >= at[None, :]


def _pass(tree, tokens, positions, mask, h):
    """(x [T, d] behind the final norm, k, v [layers, T, n_kv, D])."""
    x = tree["embed"][tokens].astype(F32)
    ks, vs = [], []
    for l in range(h["n_layer"]):
        y, k, v = _attention(_rms(x, _get(tree, l, "op_norm"), h["eps"]),
                             tree, l, positions, mask, h)
        x = x + y
        x = x + _moe(_rms(x, _get(tree, l, "ffn_norm"), h["eps"]), tree, l, h)
        ks.append(k)
        vs.append(v)
    x = _rms(x, tree["final_norm"].astype(F32), h["eps"])
    return x, jnp.stack(ks), jnp.stack(vs)


@functools.partial(jax.jit, static_argnames=("hp", "layers"))
def forward_with(tree, tokens, positions, mask, rows, *, hp, layers=None):
    """ONE sequence ``tokens`` [T] at ``positions`` [T] under the dense
    ``mask`` [T, T] (row sees column) -> (logits [R, vocab] of the rows
    ``rows`` [R], k, v [len(layers), T, n_kv, D] of the layers
    ``layers``; all of them when None)."""
    h = dict(hp)
    with jax.default_matmul_precision("highest"):
        x, ks, vs = _pass(tree, tokens, positions, mask, h)
        logits = x[rows] @ tree["head"].astype(F32).T
    if layers is not None:
        pick = np.asarray(layers, np.int32)
        ks, vs = ks[pick], vs[pick]
    return logits, ks, vs


def forward(tree, tokens, *, hp):
    """Logits [T, vocab] of one sequence ``tokens`` [T] (a whole number
    of blocks) under the dense block-causal mask."""
    T = tokens.shape[0]
    return forward_with(tree, jnp.asarray(tokens), jnp.arange(T),
                        jnp.asarray(block_mask(T, dict(hp)["block"])),
                        jnp.arange(T), hp=hp, layers=())[0]


def denoise(tree, history, block, committed, *, hp):
    """Logits [W, vocab] of a block in the middle of its denoising:
    ``history`` [P] the finished tokens before it (a whole number of
    blocks), ``block`` [W] its tokens, ``committed`` [W] bool which of
    them are committed (the others are fed the mask token)."""
    h = dict(hp)
    fed = np.where(np.asarray(committed, bool), np.asarray(block),
                   h["mask_id"])
    tokens = np.concatenate([np.asarray(history), fed]).astype(np.int32)
    T = len(tokens)
    return forward_with(tree, jnp.asarray(tokens), jnp.arange(T),
                        jnp.asarray(block_mask(T, h["block"])),
                        jnp.arange(T - h["block"], T), hp=hp, layers=())[0]


def commit_order(logits, masked, per_step: int):
    """What one denoising step commits, from its ``logits`` [W, vocab]
    and the still ``masked`` [W]: (positions, their tokens, confidence
    [W] of every position's best token): the ``per_step`` masked
    positions of highest confidence, of equal confidence the lower."""
    logits = np.asarray(logits, np.float64)
    best = logits.argmax(-1)
    conf = 1.0 / np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)
    order = sorted(np.flatnonzero(masked), key=lambda w: (-conf[w], w))
    take = order[:per_step]
    return take, best[take], conf


def generate(tree, prompt, n_new: int, *, hp):
    """``prompt`` + ``n_new`` tokens by the rule of the module docstring.
    Returns (tokens [P + n_new], ``log``: [(token, committing step)] of
    every position from the prompt's last whole block on, -1 for the
    prompt's own, the last block's excess included)."""
    h = dict(hp)
    W, steps = h["block"], h["steps"]
    prompt = np.asarray(prompt, np.int32)
    body = len(prompt) - len(prompt) % W
    seq, log = list(prompt[:body]), []
    n_blocks = -(-(len(prompt) + n_new) // W) - body // W
    for j in range(n_blocks):
        own = prompt[body:] if j == 0 else prompt[:0]
        block = np.zeros((W,), np.int32)
        block[:len(own)] = own
        masked = np.arange(W) >= len(own)
        at = np.where(masked, steps, -1)
        for s in range(steps):
            if not masked.any():
                break
            take, toks, _ = commit_order(
                denoise(tree, seq, block, ~masked, hp=hp), masked, W // steps)
            block[take], masked[take], at[take] = toks, False, s
        seq += [int(t) for t in block]
        log += [(int(t), int(a)) for t, a in zip(block, at)]
    return np.asarray(seq[:len(prompt) + n_new], np.int32), log
