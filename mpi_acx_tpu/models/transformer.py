"""Decoder-only transformer (GPT-2 family), pure functional JAX.

TPU-first choices:
* parameters live in float32, compute casts to bfloat16 so every matmul
  lands on the MXU at full rate;
* attention/MLP shapes are [*, d_model] x [d_model, big] einsums — large,
  batched, static — exactly what XLA tiles well;
* no Python control flow depends on data; the layer stack is a
  ``lax.scan`` over stacked layer parameters (single compiled layer body,
  fast compiles at depth);
* the head dim and FFN dim are the tensor-parallel shardable axes, and the
  sequence axis is the ring-attention/sequence-parallel axis — the
  distributed train step in mpi_acx_tpu.train slices these with shard_map.

GPT-2 125M is `gpt2_small()`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.ops.wquant import wread

from mpi_acx_tpu.models.decoding import grouped_decode_attend


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 50257
    d_model: int = 768
    n_heads: int = 12
    n_layers: int = 12
    d_ff: int = 3072
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16  # compute dtype (params stay f32)
    # Pallas blockwise flash-attention kernel (ops/attention.py) instead of
    # dense-mask attention: O(S) memory, causal-skipped FLOPs. None = auto:
    # flash on TPU for S >= 1024 (measured v5e crossover: dense wins below —
    # kernel grid overhead; flash 1.4x at 2048, 5.3x at 4096), dense
    # elsewhere. Flash requires S % 128 == 0 (block sizes self-fit to S).
    use_flash: Optional[bool] = None
    # Decode-attention backend (ops/flash_decode.py): None = auto (the
    # length-aware Pallas kernel on TPU for long 128-aligned caches),
    # True = always the kernel (interpret mode off-TPU), False = dense.
    decode_flash: Optional[bool] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def gpt2_small() -> TransformerConfig:
    """GPT-2 124M: 12L / 768d / 12H / 3072ff."""
    return TransformerConfig()


def tiny_config(vocab: int = 512, d_model: int = 128, n_heads: int = 4,
                n_layers: int = 4, d_ff: int = 512,
                max_seq: int = 128) -> TransformerConfig:
    """Small config for tests and virtual-mesh dryruns."""
    return TransformerConfig(vocab=vocab, d_model=d_model, n_heads=n_heads,
                             n_layers=n_layers, d_ff=d_ff, max_seq=max_seq)


Params = Dict[str, Any]


def init_params(key: jax.Array, cfg: TransformerConfig) -> Params:
    """Stacked-layer parameter pytree: every per-layer tensor has a leading
    [n_layers] axis (scanned in forward; sliceable into pipeline stages)."""
    k = jax.random.split(key, 8)
    L, d, ff = cfg.n_layers, cfg.d_model, cfg.d_ff
    s = 0.02

    def nrm(key, *shape, scale=s):
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    return {
        "embed": nrm(k[0], cfg.vocab, d),
        "pos": nrm(k[1], cfg.max_seq, d),
        "layers": {
            "ln1_g": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
            "wqkv": nrm(k[2], L, d, 3 * d),
            "wo": nrm(k[3], L, d, d, scale=s / jnp.sqrt(2 * L).item()),
            "ln2_g": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
            "w1": nrm(k[4], L, d, ff), "b1": jnp.zeros((L, ff)),
            "w2": nrm(k[5], L, ff, d, scale=s / jnp.sqrt(2 * L).item()),
            "b2": jnp.zeros((L, d)),
        },
        "lnf_g": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
    }


def layernorm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _attend(cfg: TransformerConfig, q, k, v):
    """Causal attention with the per-shape kernel choice (flash vs dense);
    [B, S, H, Dh] -> [B, S, d]."""
    B, S = q.shape[:2]
    from mpi_acx_tpu.ops.attention import select_attention
    o = select_attention(cfg.use_flash)(q, k, v)
    return o.reshape(B, S, cfg.d_model)


def block(cfg: TransformerConfig, lp: Params, x: jax.Array) -> jax.Array:
    """One transformer block; x [B, S, d] in compute dtype."""
    q, k, v = _qkv(cfg, lp, x)
    x = x + _attend(cfg, q, k, v) @ wread(lp, "wo", x.dtype)
    return _mlp(cfg, lp, x)


def _hidden(params: Params, cfg: TransformerConfig,
            tokens: jax.Array) -> jax.Array:
    """The model trunk: tokens [B, S] -> final-layernormed hidden states
    [B, S, d]. Shared by :func:`forward` and the chunked-CE loss path so
    dtype policy / block wiring can never diverge between them."""
    S = tokens.shape[1]
    x = (params["embed"][tokens] + params["pos"][:S]).astype(cfg.dtype)

    def body(x, lp):
        return block(cfg, lp, x), None

    x, _ = lax.scan(body, x, params["layers"])
    return layernorm(x, params["lnf_g"], params["lnf_b"])


def forward(params: Params, cfg: TransformerConfig,
            tokens: jax.Array) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32)."""
    x = _hidden(params, cfg, tokens)
    # Tied unembedding (GPT-2 style): bf16 operands, f32 accumulation —
    # this matmul is ~1/3 of forward FLOPs and must ride the MXU at full
    # rate (f32 operands here cost 1.45x whole-model latency on v5e).
    return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def loss_fn(params: Params, cfg: TransformerConfig, tokens: jax.Array,
            targets: jax.Array,
            xent_chunk: int | None = None) -> jax.Array:
    """Mean next-token cross-entropy.

    ``xent_chunk`` selects the memory-bounded chunked-vocab CE
    (ops/xent.py): the [B, S, vocab] logits never materialize — the
    hidden states go straight into the online-logsumexp scan, and the
    custom VJP recomputes logit tiles in the backward. Same values and
    gradients up to fp summation order; the win is HBM (the logits are
    the largest tensor in a training step at GPT-2 vocab)."""
    if xent_chunk is not None:
        from mpi_acx_tpu.ops.xent import chunked_xent_ll
        B, S = tokens.shape
        x = _hidden(params, cfg, tokens)
        ll = chunked_xent_ll(x.reshape(B * S, -1), params["embed"],
                             targets.reshape(-1), xent_chunk)
        return -jnp.mean(ll)
    logits = forward(params, cfg, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def cast_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """Cast the whole parameter tree for inference. Decode steps are
    HBM-bandwidth-bound on re-reading the parameters every token; bf16
    weights halve that traffic (measured 1.4x decode throughput on v5e).
    Training should keep f32 master weights."""
    return jax.tree.map(lambda p: p.astype(dtype), params)


# -- KV-cache decode -------------------------------------------------------
#
# Static-shape autoregressive inference: the cache holds [L, B, H, Dh,
# max_len] for k and v (decoding.to_cache_layout: tokens on the lane
# dimension); every decode step attends over the full cache width with
# an iota<=pos mask, so the jitted step has one shape for the whole
# generation (no recompiles, MXU-friendly).


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  kv_int8: bool = False):
    """Zeroed cache pytree: {'k','v': [L, B, H, Dh, max_len], 'pos':
    int32}. ``kv_int8=True`` stores int8 codes plus per-(position,
    head) f32 scale buffers 'ks'/'vs' [L, B, H, 1, max_len]
    (ops/kvquant.py) — half the cache-read bandwidth, the binding term
    at long max_len."""
    from mpi_acx_tpu.models.decoding import new_kv_cache
    return new_kv_cache(cfg.n_layers, batch, cfg.n_heads, cfg.head_dim,
                        max_len, cfg.dtype, kv_int8)


def _qkv(cfg: TransformerConfig, lp: Params, x: jax.Array):
    B, S, _ = x.shape
    h = layernorm(x, lp["ln1_g"], lp["ln1_b"])
    qkv = h @ wread(lp, "wqkv", x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    rs = lambda t: t.reshape(B, S, cfg.n_heads, cfg.head_dim)
    return rs(q), rs(k), rs(v)


def _mlp(cfg: TransformerConfig, lp: Params, x: jax.Array):
    h = layernorm(x, lp["ln2_g"], lp["ln2_b"])
    y = jax.nn.gelu(h @ wread(lp, "w1", x.dtype) + lp["b1"].astype(x.dtype))
    return x + y @ wread(lp, "w2", x.dtype) + lp["b2"].astype(x.dtype)


def prefill(params: Params, cfg: TransformerConfig, tokens: jax.Array,
            max_len: int, last_only: bool = False, ffn=None,
            kv_int8: bool = False, last_index=None):
    """Run the prompt through the model, filling a fresh KV cache.

    tokens [B, S] -> (logits [B, S, vocab] f32, cache with pos=S).
    With ``last_only`` the unembedding runs on the final position alone
    (logits [B, 1, vocab]) — for generation, which discards the rest,
    this skips ~1/3 of prefill FLOPs and the [B, S, vocab] materialization.
    ``last_index`` (traced scalar) generalizes it to "the unembedding
    runs on position ``last_index`` alone" — for bucket-padded prompts
    (models/serving.py) whose real last token is not the last row.

    ``ffn(cfg, lp, x) -> x`` overrides the block's feed-forward half
    (default :func:`_mlp`); the MoE family reuses this whole scaffold
    with its routed FFN (models/moe_transformer.py) — the cache layout,
    scan wiring, and guards live only here. ``kv_int8`` selects the
    quantized cache (init_kv_cache); prefill attention itself runs on
    the exact bf16 K/V — only the CACHE entries are quantized.
    """
    ffn = ffn or _mlp
    B, S = tokens.shape
    assert S <= max_len, (S, max_len)
    assert S <= cfg.max_seq, (S, cfg.max_seq)
    x = (params["embed"][tokens] + params["pos"][:S]).astype(cfg.dtype)

    def body(x, lp):
        q, k, v = _qkv(cfg, lp, x)
        x = x + _attend(cfg, q, k, v) @ wread(lp, "wo", x.dtype)
        x = ffn(cfg, lp, x)
        return x, (k, v)

    x, (ks, vs) = lax.scan(body, x, params["layers"])
    x = layernorm(x, params["lnf_g"], params["lnf_b"])
    if last_index is not None:
        x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    elif last_only:
        x = x[:, -1:]
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    # One cache-layout definition: init_kv_cache allocates,
    # decoding.fill_kv_cache fills (quantizing when int8).
    from mpi_acx_tpu.models.decoding import fill_kv_cache
    cache = fill_kv_cache(init_kv_cache(cfg, B, max_len,
                                        kv_int8=kv_int8), ks, vs, S)
    return logits, cache


def decode_step(params: Params, cfg: TransformerConfig, cache,
                token: jax.Array, ffn=None):
    """One autoregressive step. token [B] int32 -> (logits [B, vocab] f32,
    updated cache). Fixed shapes: jit once, run for the whole generation.
    ``ffn`` overrides the feed-forward half as in :func:`prefill`.

    The cache update runs through the shared carry-scan
    (decoding.decode_layer_scan) so XLA updates it in place — 1.9x
    faster decode on v5e than the scan-xs/ys structure."""
    ffn = ffn or _mlp
    pos = jnp.asarray(cache["pos"])
    max_len = cache["k"].shape[-1]
    # Scalar pos: one learned position row for the whole batch; [B]
    # pos (continuous-batching serving): each slot reads its own row.
    pe = (params["pos"][pos][:, None, :] if pos.ndim
          else params["pos"][pos][None, None, :])
    x = (params["embed"][token][:, None, :] + pe).astype(cfg.dtype)

    def qkv_fn(lp, x, pos):
        return _qkv(cfg, lp, x)                        # [B, 1, H, Dh]

    def attend_fn(lp, x, q, kc, vc, pos):
        o = grouped_decode_attend(q, kc, vc, pos, max_len, n_rep=1,
                                  flash=cfg.decode_flash)
        return ffn(cfg, lp, x + o @ wread(lp, "wo", x.dtype))

    from mpi_acx_tpu.models.decoding import run_decode_layers
    x, out_cache = run_decode_layers(params["layers"], x, cache,
                                     qkv_fn, attend_fn)
    x = layernorm(x, params["lnf_g"], params["lnf_b"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)[:, 0]
    return logits, out_cache


def paged_spec(cfg: TransformerConfig):
    """What the paged plane asks of a family (``kvpage.PagedSpec``), for
    GPT-2: every layer attention + dense MLP + pages, one segment of
    ``n_layers`` one-layer periods over ``params["layers"]``."""
    from mpi_acx_tpu.models import kvpage

    def embed(params, cfg, token, pos):
        pe = params["pos"][pos][:, None, :]
        return (params["embed"][token][:, None, :] + pe).astype(cfg.dtype)

    def head(params, cfg, x):
        x = layernorm(x, params["lnf_g"], params["lnf_b"])
        return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                          preferred_element_type=jnp.float32)[:, 0]

    return kvpage.PagedSpec(
        segments=(kvpage.Segment("layers", (kvpage.LayerKind(),),
                                 cfg.n_layers),),
        n_kv_heads=cfg.n_heads, head_dim=cfg.head_dim,
        ffn_built=(("dense", "_mlp"),),
        embed=embed, head=head,
        qkv=lambda cfg, lp, x, pos: _qkv(cfg, lp, x),
        attn_out=lambda cfg, lp, x, o: x + o @ wread(lp, "wo", x.dtype),
        ffn=lambda cfg, lp, x, kind: _mlp(cfg, lp, x),
        prefill=lambda params, cfg, tokens, last_index, kv_int8, page_tokens:
            prefill(params, cfg, tokens, tokens.shape[1], kv_int8=kv_int8,
                    last_index=last_index),
        suffix_prefill=lambda params, cfg, suffix, hk, hv, tail, last_index,
            kv_int8, page_tokens: kvpage.prefill_with_history(
                params, cfg, suffix, hk, hv, last_index, kv_int8=kv_int8))


def generate(params: Params, cfg: TransformerConfig, prompt: jax.Array,
             n_new: int, max_len: Optional[int] = None,
             kv_int8: bool = False) -> jax.Array:
    """Greedy decode: prompt [B, S] -> [B, S + n_new] (jit-compatible;
    the decode loop is a lax.scan of n_new fixed-shape steps).
    ``kv_int8`` selects the quantized KV cache (ops/kvquant.py) — half
    the cache bandwidth, the binding stream at long max_len."""
    from mpi_acx_tpu.models.decoding import greedy_generate
    return greedy_generate(
        lambda t, ml, lo: prefill(params, cfg, t, ml, last_only=lo,
                                  kv_int8=kv_int8),
        lambda c, t: decode_step(params, cfg, c, t),
        prompt, n_new, cfg.max_seq, max_len)


def generate_sample(params: Params, cfg: TransformerConfig,
                    prompt: jax.Array, n_new: int, key: jax.Array,
                    temperature: float = 1.0, top_k: Optional[int] = None,
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


def stage_slice(params: Params, n_stages: int) -> Params:
    """Reshape stacked layers [L, ...] -> [n_stages, L/n_stages, ...] so a
    shard_map P('pp') spec hands each pipeline stage its own layer block.
    Works on any family's params dict with a stacked 'layers' subtree
    (GPT-2 and Llama both)."""
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    per = L // n_stages

    def rs(p):
        return p.reshape((n_stages, per) + p.shape[1:])

    out = dict(params)
    out["layers"] = jax.tree.map(rs, params["layers"])
    return out


def stage_slice_interleaved(params: Params, n_stages: int,
                            n_virtual: int) -> Params:
    """Reshape stacked layers [L, ...] -> [pp, v, L/(pp*v), ...] for the
    interleaved pipeline schedule: global stage g = j*pp + s lands at
    [s, j] (device s, chunk j), so consecutive layer blocks snake over
    the devices n_virtual times."""
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    G = n_stages * n_virtual
    assert L % G == 0, (L, n_stages, n_virtual)
    per = L // G

    def rs(p):
        q = p.reshape((n_virtual, n_stages, per) + p.shape[1:])
        return jnp.swapaxes(q, 0, 1)                # [pp, v, per, ...]

    out = dict(params)
    out["layers"] = jax.tree.map(rs, params["layers"])
    return out
