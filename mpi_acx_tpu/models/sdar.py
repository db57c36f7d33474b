"""SDAR-MoE family (``model_type`` ``sdar_moe``: JetLM SDAR-30B-A3B-Chat),
pure functional JAX: a qwen3-moe decoder that GENERATES BY DIFFUSION
OVER BLOCKS.

The layers (HF ``SDARMoe*``; ``u`` is the RMS-normed input, no bias
anywhere, every layer alike):

* layer: ``h = x + Attn(norm(x))``, ``out = h + MoE(norm(h))``; a final
  RMSNorm, then the UNTIED head.
* ``Attn``: grouped-query attention, q as ``n_heads`` heads, k and v as
  ``n_kv_heads`` of ``head_dim`` (its own number, not ``d_model /
  n_heads``); q and k RMS-normed over the head's dims (one weight
  ``[head_dim]`` each) BEFORE RoPE (rotate-half over the whole head,
  ``rope_theta``, no scaling); softmax at ``1 / sqrt(head_dim)``; an
  output projection. **The mask is block-causal** with block
  ``block_length`` = W, in prefill and in generation alike: a position
  sees every earlier position and the WHOLE of its own block.
* ``MoE``: ``g = softmax(u W_r)`` over all experts in float32, the
  ``top_k`` largest, their weights divided by their sum
  (``norm_topk_prob``); SwiGLU experts, every routed token computed,
  none dropped, no shared expert (``moe.route_softmax_topk`` +
  ``moe.sorted_expert_ffn``).

Generation (the family's public ``generate.py``, the static
low-confidence schedule, greedy): the sequence is blocks of W positions;
a block starts as what of the prompt falls into it and mask tokens
elsewhere, is denoised over ``denoising_steps`` forwards, each of which
commits the ``W / denoising_steps`` masked positions whose best token
has the highest probability, and is then run once more, finished, for
the K/V that later blocks see. The paged plane owns all of that
(``kvpage.paged_decode_chunk``'s block arm, ``kvpage.sequence_pass``'s
block-causal mask): this module is the family's operators and its spec
(:func:`paged_spec`), no loop and no pass of its own.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from mpi_acx_tpu.models import kvpage, moe
from mpi_acx_tpu.models.llama import rmsnorm, rope


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_d_ff: int = 768              # one expert's width
    n_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_seq: int = 32768
    # generation by diffusion over blocks (module docstring)
    block_length: int = 4
    denoising_steps: int = 4
    mask_token_id: int = 151669
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None     # prefill attention; None = auto
    decode_flash: Optional[bool] = None  # paged decode kernels; None = auto


def sdar_30b_a3b() -> SdarConfig:
    """SDAR-30B-A3B-Chat as published (48 layers, 128 experts top 8)."""
    return SdarConfig()


def tiny_sdar(**over) -> SdarConfig:
    """Small config for tests: three layers, 8 experts top 2, d = 64,
    4 / 2 heads of 16, blocks of 4 positions denoised in 4 steps."""
    base = dict(vocab=96, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
                head_dim=16, moe_d_ff=32, n_experts=8, top_k=2, max_seq=256,
                mask_token_id=95)
    base.update(over)
    return SdarConfig(**base)


Params = Dict[str, Any]
_EXPERT_STACKS = ("w1", "w3", "w2")    # of the routed FFN: never sliced
_KIND = kvpage.LayerKind(operator="attention", ffn="moe", cache="pages")


def segments(cfg: SdarConfig) -> Tuple[kvpage.Segment, ...]:
    return (kvpage.Segment("layers", (_KIND,), cfg.n_layers),)


def leaf_shapes(cfg: SdarConfig) -> Dict[str, tuple]:
    """One layer's leaves: name -> (shape, init; None = ones, else a
    normal's scale)."""
    d, dh, s = cfg.d_model, cfg.head_dim, 0.02
    n, f = cfg.n_experts, cfg.moe_d_ff
    return {"op_norm": ((d,), None), "ffn_norm": ((d,), None),
            "wq": ((d, cfg.n_heads * dh), s),
            "wk": ((d, cfg.n_kv_heads * dh), s),
            "wv": ((d, cfg.n_kv_heads * dh), s),
            "wo": ((cfg.n_heads * dh, d), s),
            "q_norm": ((dh,), None), "k_norm": ((dh,), None),
            "gate": ((d, n), s),
            "w1": ((n, d, f), s), "w3": ((n, d, f), s), "w2": ((n, f, d), s)}


def init_params(key: jax.Array, cfg: SdarConfig) -> Params:
    """f32 parameters, the layers stacked ``[n_layers, ...]`` under
    ``"layers"``; embedding and head untied."""
    d = cfg.d_model
    params = {
        "embed": jax.random.normal(jax.random.fold_in(key, 0),
                                   (cfg.vocab, d)) * 0.02,
        "head": jax.random.normal(jax.random.fold_in(key, 1),
                                  (cfg.vocab, d)) * 0.02,
        "final_norm": jnp.ones((d,))}
    layers = {}
    for n, (name, (shape, init)) in enumerate(sorted(
            leaf_shapes(cfg).items())):
        shape = (cfg.n_layers,) + shape
        layers[name] = (jnp.ones(shape) if init is None else
                        jax.random.normal(jax.random.fold_in(key, 2 + n),
                                          shape) * init)
    params["layers"] = layers
    return params


def cast_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """The tree in ``dtype`` for inference; the router and the norms
    stay f32: they are computed in f32."""
    return kvpage.cast_params(params, dtype, ("gate",))


# -- the layer functions -----------------------------------------------------


def _w(lp, name, dtype):
    return lp[name].astype(dtype)


def _qkv(cfg: SdarConfig, lp: Params, x: jax.Array, positions: jax.Array):
    """q [B, S, Hq, Dh], k, v [B, S, Hkv, Dh] as they go into attention
    and the cache: QK-norm over the head's dims, then RoPE, both in
    float32 with ONE rounding to the compute type at the end (LFM2's
    ``norm_rope`` form: a rounding between them is in every cached
    key)."""
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


def _attn_out(cfg: SdarConfig, lp: Params, x: jax.Array, o: jax.Array):
    return x + o @ _w(lp, "wo", x.dtype)


def _moe_ffn(cfg: SdarConfig, lp: Params, x: jax.Array, kind: str = "moe",
             live=None):
    """(x + the experts' part, idx [T, k] the experts chosen). ``live``
    [T] bool: the rows whose result anybody receives (None: all); the
    others' experts are not computed, their part is zeros."""
    u = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(-1, cfg.d_model)
    idx, p = moe.route_softmax_topk(u, lp["gate"], cfg.top_k,
                                    cfg.norm_topk_prob)
    # (with "repeat" the expert matrices are the whole stacks)
    y = moe.sorted_expert_ffn(u, _w(lp, "w1", x.dtype), _w(lp, "w3", x.dtype),
                              _w(lp, "w2", x.dtype), idx, p,
                              layer=lp.get("repeat"), live=live)
    return x + y.astype(x.dtype).reshape(x.shape), idx


def _head(params: Params, cfg: SdarConfig, x: jax.Array):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["head"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def forward(params: Params, cfg: SdarConfig, tokens: jax.Array) -> jax.Array:
    """tokens [B, S] int32 (S a whole number of blocks) -> logits [B, S,
    vocab] (f32): the plain whole-sequence pass under the block-causal
    mask, no cache."""
    return kvpage.forward(params, cfg, paged_spec(cfg), tokens)


# -- the paged plane's seam --------------------------------------------------


def paged_spec(cfg: SdarConfig) -> kvpage.PagedSpec:
    """What ``serve_paged_greedy``'s plane asks of this family: pages
    for every layer ([L, P, n_kv_heads, head_dim, pt], GQA-native
    through the shared write and walk), the router's width for the
    routing counters, and HOW IT GENERATES: blocks of ``block_length``
    positions, ``denoising_steps`` forwards a block and one that stores
    it, the mask token's id. A step's ``pos`` is the block's first
    position."""
    def at(pos, x):             # [B, W]: the block's positions
        return pos[:, None] + jnp.arange(x.shape[1])

    return kvpage.PagedSpec(
        segments=segments(cfg),
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_rep=cfg.n_heads // cfg.n_kv_heads,
        n_experts=cfg.n_experts, moe_whole=_EXPERT_STACKS,
        block=cfg.block_length, denoise_steps=cfg.denoising_steps,
        mask_token=cfg.mask_token_id,
        ffn_built=(("moe", "sorted_expert_ffn/"
                    + moe.select_grouped_matmul().__name__),),
        embed=lambda params, cfg, token, pos:
            params["embed"][token].astype(cfg.dtype),
        qkv=lambda cfg, lp, x, pos: _qkv(cfg, lp, x, at(pos, x)),
        attn_out=_attn_out, ffn=_moe_ffn, head=_head,
        seq_qkv=_qkv, seq_head=_head)
