"""Llama-family decoder (RMSNorm, RoPE, SwiGLU, grouped-query attention),
pure functional JAX.

Second model family next to the GPT-2 transformer (models/transformer.py);
the workload behind the reference's "Llama-3-8B activation/grad
pipeline exchange" config. Same TPU-first construction: stacked-layer params
scanned with ``lax.scan`` (stage-sliceable for pipeline parallelism with
:func:`mpi_acx_tpu.models.transformer.stage_slice`-style reshapes), bf16
compute with f32 norms/softmax, static shapes, and the shared flash/dense
attention policy (GQA K/V heads are broadcast to query heads before the
kernel — the cache still stores only ``n_kv_heads``, which is GQA's
inference memory win).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.ops.wquant import wread


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 8          # GQA: queries share K/V head groups
    n_layers: int = 32
    d_ff: int = 14336            # SwiGLU hidden
    max_seq: int = 8192
    rope_theta: float = 500000.0
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None  # None = shared auto policy
    decode_flash: Optional[bool] = None  # decode kernel; None = auto

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def llama3_8b() -> LlamaConfig:
    """Llama-3-8B geometry."""
    return LlamaConfig()


def tiny_llama(vocab: int = 256, d_model: int = 64, n_heads: int = 4,
               n_kv_heads: int = 2, n_layers: int = 2, d_ff: int = 128,
               max_seq: int = 64) -> LlamaConfig:
    """Small config for tests and virtual-mesh dryruns."""
    return LlamaConfig(vocab=vocab, d_model=d_model, n_heads=n_heads,
                       n_kv_heads=n_kv_heads, n_layers=n_layers, d_ff=d_ff,
                       max_seq=max_seq, rope_theta=10000.0)


Params = Dict[str, Any]


def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Stacked-layer parameter pytree ([n_layers] leading axis per leaf)."""
    k = jax.random.split(key, 8)
    L, d, ff = cfg.n_layers, cfg.d_model, cfg.d_ff
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    s = 0.02

    def nrm(key, *shape, scale=s):
        return jax.random.normal(key, shape, jnp.float32) * scale

    return {
        "embed": nrm(k[0], cfg.vocab, d),
        "layers": {
            "attn_norm": jnp.ones((L, d)),
            "wq": nrm(k[1], L, d, hq * dh),
            "wk": nrm(k[2], L, d, hkv * dh),
            "wv": nrm(k[3], L, d, hkv * dh),
            "wo": nrm(k[4], L, hq * dh, d, scale=s / (2 * L) ** 0.5),
            "mlp_norm": jnp.ones((L, d)),
            "w_gate": nrm(k[5], L, d, ff),
            "w_up": nrm(k[6], L, d, ff),
            "w_down": nrm(k[7], L, ff, d, scale=s / (2 * L) ** 0.5),
        },
        "final_norm": jnp.ones((d,)),
        # Untied output head (Llama style).
        "unembed": nrm(jax.random.fold_in(key, 99), cfg.vocab, d),
    }


def rmsnorm(x, g, eps=1e-5):
    x32 = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * g).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x [..., S, H, D], positions [S] (or [..., S])."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    ang = positions[..., None].astype(jnp.float32) * freqs       # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                             # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D]: broadcast K/V head groups
    to the query heads (GQA -> MHA view for the attention kernel)."""
    if n_rep == 1:
        return x
    B, S, H, D = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (B, S, H, n_rep, D)).reshape(B, S, H * n_rep, D)


def _qkv(cfg: LlamaConfig, lp: Params, x: jax.Array, positions: jax.Array):
    B, S, _ = x.shape
    h = rmsnorm(x, lp["attn_norm"])
    q = (h @ wread(lp, "wq", x.dtype)).reshape(B, S, cfg.n_heads,
                                               cfg.head_dim)
    k = (h @ wread(lp, "wk", x.dtype)).reshape(B, S, cfg.n_kv_heads,
                                               cfg.head_dim)
    v = (h @ wread(lp, "wv", x.dtype)).reshape(B, S, cfg.n_kv_heads,
                                               cfg.head_dim)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attend(cfg: LlamaConfig, q, k, v):
    """Post-RoPE attention with K/V broadcast to query heads; the kernel
    choice delegates to the shared flash/dense policy."""
    from mpi_acx_tpu.ops.attention import select_attention
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    o = select_attention(cfg.use_flash)(q, k, v)
    B, S = q.shape[:2]
    return o.reshape(B, S, cfg.n_heads * cfg.head_dim)


def _mlp(cfg: LlamaConfig, lp: Params, x: jax.Array):
    h = rmsnorm(x, lp["mlp_norm"])
    gate = jax.nn.silu(h @ wread(lp, "w_gate", x.dtype))
    up = h @ wread(lp, "w_up", x.dtype)
    return x + (gate * up) @ wread(lp, "w_down", x.dtype)


def block(cfg: LlamaConfig, lp: Params, x: jax.Array,
          positions: jax.Array) -> jax.Array:
    q, k, v = _qkv(cfg, lp, x, positions)
    x = x + _attend(cfg, q, k, v) @ wread(lp, "wo", x.dtype)
    return _mlp(cfg, lp, x)


def _hidden(params: Params, cfg: LlamaConfig,
            tokens: jax.Array) -> jax.Array:
    """The model trunk: tokens [B, S] -> final-rmsnormed hidden states
    [B, S, d]. Shared by :func:`forward` and the chunked-CE loss path so
    dtype policy / block wiring can never diverge between them."""
    S = tokens.shape[1]
    assert S <= cfg.max_seq, (S, cfg.max_seq)
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(S)

    def body(x, lp):
        return block(cfg, lp, x, positions), None

    x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"])


def forward(params: Params, cfg: LlamaConfig,
            tokens: jax.Array) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] f32."""
    x = _hidden(params, cfg, tokens)
    return jnp.einsum("bsd,vd->bsv", x, params["unembed"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params: Params, cfg: LlamaConfig, tokens: jax.Array,
            targets: jax.Array,
            xent_chunk: int | None = None) -> jax.Array:
    """Mean next-token cross-entropy; ``xent_chunk`` selects the
    memory-bounded chunked-vocab CE (ops/xent.py — the [B, S, vocab]
    logits never materialize; same values/grads to fp summation
    order)."""
    if xent_chunk is not None:
        from mpi_acx_tpu.ops.xent import chunked_xent_ll
        B, S = tokens.shape
        x = _hidden(params, cfg, tokens)
        ll = chunked_xent_ll(x.reshape(B * S, -1), params["unembed"],
                             targets.reshape(-1), xent_chunk)
        return -jnp.mean(ll)
    logits = forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# -- KV-cache decode (GQA: the cache stores only n_kv_heads) ---------------


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int,
                  kv_int8: bool = False):
    """GQA cache (n_kv_heads, the memory win); ``kv_int8=True`` stores
    int8 codes + per-(position, head) f32 scales (ops/kvquant.py).
    Layout [L, B, Hkv, Dh, max_len] (decoding.new_kv_cache)."""
    from mpi_acx_tpu.models.decoding import new_kv_cache
    return new_kv_cache(cfg.n_layers, batch, cfg.n_kv_heads, cfg.head_dim,
                        max_len, cfg.dtype, kv_int8)


def prefill(params: Params, cfg: LlamaConfig, tokens: jax.Array,
            max_len: int, last_only: bool = False,
            kv_int8: bool = False, last_index=None):
    """Prompt pass filling a fresh KV cache (layout: init_kv_cache).
    Prefill attention runs on the exact bf16 K/V; with ``kv_int8`` only
    the CACHE entries are quantized. ``last_index`` (traced scalar):
    unembed position ``last_index`` alone — bucket-padded serving
    prompts (see transformer.prefill)."""
    B, S = tokens.shape
    assert S <= max_len and S <= cfg.max_seq, (S, max_len, cfg.max_seq)
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(S)

    def body(x, lp):
        q, k, v = _qkv(cfg, lp, x, positions)
        x = x + _attend(cfg, q, k, v) @ wread(lp, "wo", x.dtype)
        x = _mlp(cfg, lp, x)
        return x, (k, v)

    x, (ks, vs) = lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"])
    if last_index is not None:
        x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = jnp.einsum("bsd,vd->bsv", x, params["unembed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    from mpi_acx_tpu.models.decoding import fill_kv_cache
    cache = fill_kv_cache(init_kv_cache(cfg, B, max_len,
                                        kv_int8=kv_int8), ks, vs, S)
    return logits, cache


from mpi_acx_tpu.models.decoding import (  # noqa: F401  (re-export)
    grouped_decode_attend,
)


def decode_step(params: Params, cfg: LlamaConfig, cache,
                token: jax.Array):
    """One autoregressive step; token [B] -> (logits [B, vocab] f32,
    updated cache). Fixed shapes: jit once per generation.

    The cache update runs through the shared carry-scan
    (decoding.decode_layer_scan): in-place updates, 1.9x faster decode
    on v5e than scan-ys stacking."""
    pos = jnp.asarray(cache["pos"])
    max_len = cache["k"].shape[-1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    x = params["embed"][token][:, None, :].astype(cfg.dtype)
    # Scalar pos -> shared position [1]; per-slot pos [B] (serving) ->
    # [B, 1] so each slot's RoPE rotates by its own position.
    positions = pos[:, None] if pos.ndim else jnp.full((1,), pos)

    def qkv_fn(lp, x, pos):
        return _qkv(cfg, lp, x, positions)               # k,v [B,1,Hkv,D]

    def attend_fn(lp, x, q, kc, vc, pos):
        o = grouped_decode_attend(q, kc, vc, pos, max_len, n_rep,
                                  flash=cfg.decode_flash)
        return _mlp(cfg, lp, x + o @ wread(lp, "wo", x.dtype))

    from mpi_acx_tpu.models.decoding import run_decode_layers
    x, out_cache = run_decode_layers(params["layers"], x, cache,
                                     qkv_fn, attend_fn)
    x = rmsnorm(x, params["final_norm"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["unembed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)[:, 0]
    return logits, out_cache


def generate(params: Params, cfg: LlamaConfig, prompt: jax.Array,
             n_new: int, max_len: Optional[int] = None,
             kv_int8: bool = False) -> jax.Array:
    """Greedy decode: prompt [B, S] -> [B, S + n_new]. ``kv_int8``
    selects the quantized KV cache (ops/kvquant.py)."""
    from mpi_acx_tpu.models.decoding import greedy_generate
    return greedy_generate(
        lambda t, ml, lo: prefill(params, cfg, t, ml, last_only=lo,
                                  kv_int8=kv_int8),
        lambda c, t: decode_step(params, cfg, c, t),
        prompt, n_new, cfg.max_seq, max_len)


def generate_sample(params: Params, cfg: LlamaConfig, prompt: jax.Array,
                    n_new: int, key: jax.Array, temperature: float = 1.0,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    max_len: Optional[int] = None,
                    kv_int8: bool = False) -> jax.Array:
    """Stochastic decode (temperature / top-k / top-p nucleus)."""
    from mpi_acx_tpu.models.decoding import sample_generate
    return sample_generate(
        lambda t, ml, lo: prefill(params, cfg, t, ml, last_only=lo,
                                  kv_int8=kv_int8),
        lambda c, t: decode_step(params, cfg, c, t),
        prompt, n_new, cfg.max_seq, key, temperature, top_k, top_p, max_len)
