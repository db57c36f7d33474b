"""LFM2-MoE family (``model_type`` ``lfm2_moe``: LiquidAI LFM2-24B-A2B),
pure functional JAX.

A decoder whose layers are of two OPERATOR kinds and two FFN kinds
(HF ``Lfm2Moe*``; ``u`` is the RMS-normed input, no bias anywhere):

* layer ``l``: ``h = x + Op_l(norm(x))``, ``out = h + FFN_l(norm(h))``;
  a final RMSNorm, then the (tied) head.
* ``Op`` = grouped-query attention where ``layer_types[l] ==
  "full_attention"``: q as ``n_heads`` heads, k and v as ``n_kv_heads``;
  q and k RMS-normed over the head's dims (one weight ``[head_dim]``
  each) BEFORE RoPE (rotate-half over the whole head); causal softmax
  attention scaled ``1 / sqrt(head_dim)``; an output projection.
* ``Op`` = gated short convolution where ``layer_types[l] == "conv"``:
  ``[B | C | X] = W_in u``; ``z = B * X``; a depthwise causal
  convolution of ``conv_L_cache`` taps over ``z`` (zeros before the
  sequence, no bias); ``y = W_out (C * conv)``. What a decode step
  needs of the past is the last ``conv_L_cache - 1`` values of ``z``:
  a FIXED-size state a layer a sequence, where attention has pages.
* ``FFN`` = dense SwiGLU (width ``d_ff``) for ``l < num_dense_layers``,
  else routed experts (``moe.route_sigmoid_topk`` +
  ``moe.sorted_expert_ffn``: sigmoid scores, a selection bias, the
  ``top_k`` renormalised, every routed token computed, none dropped).

Parameters are stacked by STRETCH, not by layer: ``kvpage.
compress_layers`` groups the layers into segments of whole periods
(``params["seg<i>"]``: one dict of leaves ``[repeats, ...]`` a layer of
the period), each one ``lax.scan``, so that compile time does not grow
with the 40 published layers. :func:`forward` is the plain whole-
sequence pass; :func:`paged_spec` is what ``serve_paged_greedy``'s
plane asks of a family (``kvpage.PagedSpec``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.models import kvpage, moe
from mpi_acx_tpu.models.llama import rmsnorm, rope

_PUBLISHED_LAYERS = ("conv", "conv") + (
    "full_attention", "conv", "conv", "conv") * 9 + ("full_attention", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab: int = 65536
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 11776                # dense SwiGLU width
    moe_d_ff: int = 1536             # one expert's width
    n_experts: int = 64
    top_k: int = 4
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    max_seq: int = 128000
    # The experts held HERE: ``experts_held`` of them from
    # ``experts_first`` (None: all). The router keeps its width.
    experts_first: int = 0
    experts_held: Optional[int] = None
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None     # prefill attention; None = auto
    decode_flash: Optional[bool] = None  # paged decode kernels; None = auto

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held)


def lfm2_24b_a2b() -> Lfm2Config:
    """LFM2-24B-A2B as published (40 layers, 64 experts top 4)."""
    return Lfm2Config()


def tiny_lfm2(**over) -> Lfm2Config:
    """Small config for tests: one dense conv layer, then two periods
    ``attn, conv, conv, conv`` with 8 experts top 2, d = 64."""
    base = dict(vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
                moe_d_ff=32, n_experts=8, top_k=2,
                layer_types=("conv",) + ("full_attention", "conv", "conv",
                                         "conv") * 2,
                num_dense_layers=1, max_seq=256)
    base.update(over)
    return Lfm2Config(**base)


Params = Dict[str, Any]
_EXPERT_STACKS = ("w1", "w3", "w2")    # of a routed FFN: never sliced


def layer_kinds(cfg: Lfm2Config) -> Tuple[kvpage.LayerKind, ...]:
    return tuple(
        kvpage.LayerKind(
            operator="attention" if t == "full_attention" else "conv",
            ffn="dense" if l < cfg.num_dense_layers else "moe",
            cache="pages" if t == "full_attention" else "state")
        for l, t in enumerate(cfg.layer_types))


def segments(cfg: Lfm2Config) -> Tuple[kvpage.Segment, ...]:
    return kvpage.compress_layers(layer_kinds(cfg))


def leaf_shapes(cfg: Lfm2Config, kind: kvpage.LayerKind) -> Dict[str, tuple]:
    """One layer's leaves: name -> (shape, init; None = ones, "bias" =
    the router's selection bias, else a normal's scale)."""
    d, dh = cfg.d_model, cfg.head_dim
    s = 0.02
    out = {"op_norm": ((d,), None), "ffn_norm": ((d,), None)}
    if kind.operator == "attention":
        out.update(wq=((d, cfg.n_heads * dh), s),
                   wk=((d, cfg.n_kv_heads * dh), s),
                   wv=((d, cfg.n_kv_heads * dh), s),
                   wo=((cfg.n_heads * dh, d), s),
                   q_norm=((dh,), None), k_norm=((dh,), None))
    else:
        out.update(w_in=((d, 3 * d), s), conv_w=((d, cfg.conv_L_cache), s),
                   w_out=((d, d), s))
    if kind.ffn == "dense":
        out.update(w1=((d, cfg.d_ff), s), w3=((d, cfg.d_ff), s),
                   w2=((cfg.d_ff, d), s))
    else:
        n, f = cfg.n_held, cfg.moe_d_ff
        out.update(gate=((d, cfg.n_experts), s),
                   bias=((cfg.n_experts,), "bias"),
                   w1=((n, d, f), s), w3=((n, d, f), s), w2=((n, f, d), s))
    return out


def init_params(key: jax.Array, cfg: Lfm2Config) -> Params:
    """f32 parameters, stacked by segment (module docstring); tied
    embedding and head. The selection bias is small and NOT zero (HF
    initialises zeros), so that selection and weight differ."""
    params = {"embed": jax.random.normal(
        jax.random.fold_in(key, 0), (cfg.vocab, cfg.d_model)) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,))}
    n = 0
    for seg in segments(cfg):
        layers = []
        for kind in seg.period:
            leaves = {}
            for name, (shape, init) in sorted(leaf_shapes(cfg, kind).items()):
                n += 1
                k = jax.random.fold_in(key, n)
                shape = (seg.repeats,) + shape
                if init is None:
                    leaves[name] = jnp.ones(shape)
                elif init == "bias":
                    leaves[name] = jax.random.uniform(k, shape, jnp.float32,
                                                      -0.05, 0.05)
                else:
                    leaves[name] = jax.random.normal(k, shape) * init
            layers.append(leaves)
        params[seg.key] = layers[0] if len(layers) == 1 else tuple(layers)
    return params


def cast_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """The tree in ``dtype`` for inference; the router (``gate``,
    ``bias``) and the norms stay f32: they are computed in f32."""
    return kvpage.cast_params(params, dtype, ("gate", "bias"))


# -- the layer functions -----------------------------------------------------


def _w(lp, name, dtype):
    return lp[name].astype(dtype)


def _qkv(cfg: Lfm2Config, lp: Params, x: jax.Array, positions: jax.Array):
    """q [B, S, Hq, Dh], k, v [B, S, Hkv, Dh] as they go into attention
    and the cache: QK-norm over the head's dims, then RoPE, both in
    float32 with ONE rounding to the compute type at the end (a
    rounding between them is in every cached key: 0.003 of the pages'
    relative error)."""
    B, S, _ = x.shape
    u = rmsnorm(x, lp["op_norm"], cfg.norm_eps)
    dh = cfg.head_dim
    q = (u @ _w(lp, "wq", x.dtype)).reshape(B, S, cfg.n_heads, dh)
    k = (u @ _w(lp, "wk", x.dtype)).reshape(B, S, cfg.n_kv_heads, dh)
    v = (u @ _w(lp, "wv", x.dtype)).reshape(B, S, cfg.n_kv_heads, dh)

    def norm_rope(t, g):
        t = rmsnorm(t.astype(jnp.float32), g, cfg.norm_eps)
        return rope(t, positions, cfg.rope_theta).astype(x.dtype)
    return norm_rope(q, lp["q_norm"]), norm_rope(k, lp["k_norm"]), v


def _attn_out(cfg: Lfm2Config, lp: Params, x: jax.Array, o: jax.Array):
    return x + o @ _w(lp, "wo", x.dtype)


def _conv_op(cfg: Lfm2Config, lp: Params, x: jax.Array, z_before: jax.Array):
    """The gated short conv with its residual. x [B, S, d]; ``z_before``
    [B, taps, d] the gated input at the ``taps = conv_L_cache - 1``
    positions before x (zeros before a sequence). Returns (x + y,
    ``zs`` [B, taps + S, d]: ``z_before`` then this call's z, whose last
    ``taps`` rows are the state the next token needs)."""
    S, L = x.shape[1], cfg.conv_L_cache
    u = rmsnorm(x, lp["op_norm"], cfg.norm_eps)
    b, c, xx = jnp.split(u @ _w(lp, "w_in", x.dtype), 3, axis=-1)
    zs = jnp.concatenate([z_before.astype(x.dtype), b * xx], axis=1)
    w = lp["conv_w"].astype(jnp.float32)                       # [d, L]
    conv = sum(w[:, j] * zs[:, j:j + S].astype(jnp.float32)
               for j in range(L))
    y = (c * conv.astype(x.dtype)) @ _w(lp, "w_out", x.dtype)
    return x + y, zs


def _dense_ffn(cfg: Lfm2Config, lp: Params, x: jax.Array):
    u = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    h = jax.nn.silu(u @ _w(lp, "w1", x.dtype)) * (u @ _w(lp, "w3", x.dtype))
    return x + h @ _w(lp, "w2", x.dtype)


def _moe_ffn(cfg: Lfm2Config, lp: Params, x: jax.Array, live=None):
    """(x + the held experts' part, idx [T, k] the experts chosen).
    ``live`` [T] bool: the tokens whose result anybody receives (None:
    all); the others' experts are not computed, their part is zeros."""
    u = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(-1, cfg.d_model)
    idx, p = moe.route_sigmoid_topk(
        u, lp["gate"], lp["bias"], cfg.top_k, cfg.routed_scaling_factor,
        cfg.norm_topk_prob)
    # (with "repeat" the expert matrices are the segment's whole stacks)
    y = moe.sorted_expert_ffn(u, _w(lp, "w1", x.dtype), _w(lp, "w3", x.dtype),
                              _w(lp, "w2", x.dtype), idx, p,
                              first=cfg.experts_first, layer=lp.get("repeat"),
                              live=live)
    return x + y.astype(x.dtype).reshape(x.shape), idx


def _ffn(cfg: Lfm2Config, lp: Params, x: jax.Array, kind: str, live=None):
    return (_dense_ffn(cfg, lp, x) if kind == "dense"
            else _moe_ffn(cfg, lp, x, live))


def _head(params: Params, cfg: Lfm2Config, x: jax.Array):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _conv_seq(cfg: Lfm2Config, lp: Params, x: jax.Array, start, last_index,
              snapshot):
    """``PagedSpec.seq_state``: the gated short conv over whole
    sequences from the ``taps`` gated inputs before them (``start`` [1,
    taps, d]; None: zeros) and, with ``snapshot``, the last ``taps`` of
    every ``snapshot`` tokens and those at ``last_index``."""
    B, S, _ = x.shape
    taps = cfg.conv_L_cache - 1
    before = (jnp.zeros((B, taps, cfg.d_model), x.dtype) if start is None
              else jnp.broadcast_to(start, (B, taps, cfg.d_model)))
    x, zs = _conv_op(cfg, lp, x, before)
    if snapshot is None:
        return x, None, None
    n = S // snapshot
    pages = zs[0, taps:taps + n * snapshot].reshape(n, snapshot, cfg.d_model)
    return (x, pages[:, snapshot - taps:],
            lax.dynamic_slice_in_dim(zs[0], last_index + 1, taps, axis=0))


def forward(params: Params, cfg: Lfm2Config, tokens: jax.Array) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): the plain
    whole-sequence pass, no cache."""
    return kvpage.forward(params, cfg, paged_spec(cfg), tokens)


# -- the paged plane's seam --------------------------------------------------


def paged_spec(cfg: Lfm2Config) -> kvpage.PagedSpec:
    """What ``serve_paged_greedy``'s plane asks of this family: pages
    for the attention layers ([L_attn, P, n_kv_heads, head_dim, pt],
    GQA-native through the shared write and walk), a fixed state
    ``[conv_L_cache - 1, d_model]`` a slot a conv layer (one leaf, a
    snapshot of it with every whole prompt page: 8 KB beside a page's
    512 KB), the router's width for the routing counters. int8 pages
    are not wired: the tails would want a precision of their own."""
    def decode_conv(cfg, lp, x, held, at, live=None):
        # (a layer of it is 0.5 MB at 64 slots: sliced out and put back,
        # every slot's: ``live`` is not read)
        st = lax.dynamic_index_in_dim(held, at, 0, keepdims=False)
        x, zs = _conv_op(cfg, lp, x, st)
        st = zs[:, -(cfg.conv_L_cache - 1):]
        return x, lax.dynamic_update_index_in_dim(
            held, st.astype(held.dtype), at, 0)

    return kvpage.PagedSpec(
        segments=segments(cfg),
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_rep=cfg.n_heads // cfg.n_kv_heads,
        state=jax.ShapeDtypeStruct((cfg.conv_L_cache - 1, cfg.d_model),
                                   cfg.dtype),
        n_experts=cfg.n_experts, kv_int8=False, moe_whole=_EXPERT_STACKS,
        ffn_built=(("dense", "_dense_ffn"),
                   ("moe", "sorted_expert_ffn/"
                    + moe.select_grouped_matmul().__name__)),
        embed=lambda params, cfg, token, pos:
            params["embed"][token][:, None, :].astype(cfg.dtype),
        qkv=lambda cfg, lp, x, pos: _qkv(cfg, lp, x, pos[:, None]),
        attn_out=_attn_out, state_op=decode_conv, ffn=_ffn,
        head=lambda params, cfg, x: _head(params, cfg, x)[:, 0],
        seq_qkv=_qkv, seq_state=_conv_seq, seq_head=_head)
