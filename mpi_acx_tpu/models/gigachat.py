"""GigaChat3 Ultra family (``model_type`` ``deepseek_v3``: ai-sage
GigaChat3.1-702B-A36B), pure functional JAX.

The published DeepSeek-V3 block (``x`` the residual stream; pre-norm
RMSNorm; no bias anywhere):

* **Latent attention (MLA), every layer.** ``c_q = norm(W_DQ u)``;
  ``q = W_UQ c_q`` as ``n_heads`` heads of ``[q_nope (128) | q_rope
  (64)]``. ``[c | k_r] = W_DKV u``; ``c_kv = norm(c)``; ``k_rope =
  RoPE(k_r)``, ONE head shared by all query heads; ``q_rope`` gets the
  same RoPE. What a token leaves in the cache is ONE row a layer, ``[c_kv
  (512) | k_rope (64)]``. ``k_nope_h = W_UK_h c_kv``, ``v_h = W_UV_h
  c_kv`` (192); ``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t)
  . k_rope(s)) * softmax_scale``, causal softmax in f32, ``o = W_O
  concat_h(sum_s p_h v_h)``. ``softmax_scale = 192^-0.5 * m^2`` with ``m
  = 0.1 * mscale_all_dim * ln(factor) + 1`` (YaRN; :func:`attn_scale`).
  RoPE is YaRN's (:func:`yarn_inv_freq`: the inverse frequencies blended
  between interpolation and extrapolation by the ramp between the two
  betas; ``mscale == mscale_all_dim``, so cos and sin are not scaled),
  over the pairs ``(2i, 2i + 1)`` of the 64 rope values, the rotated
  pair written to ``(i, 32 + i)`` (DeepSeek's checkpoint convention: a
  fixed permutation that q and k share).
* **Two paths over the one cache.** A whole-sequence pass (prefill, a
  suffix prefill behind a radix hit, :func:`forward`) UP-PROJECTS the
  rows to ``n_heads`` heads of K (128 + 64) and V (192) and runs causal
  attention at head width 192 (``ops.attention.flash_rows_attention``;
  a suffix prefill up-projects the gathered history too). A decode step
  ABSORBS ``W_UK`` into the query and ``W_UV`` behind the attend and
  never forms K or V: ``q~_h = W_UK_h^T q_nope_h`` (512), ``score = q~_h
  . c_kv + q_rope_h . k_rope``, ``u_h = sum_s p_h c_kv(s)``, ``o_h =
  W_UV_h u_h``: all ``n_heads`` heads read the same 576-wide row, and
  the value is the row's first 512 values (``kvpage.PagedSpec.v_dim``).
* **FFN.** SwiGLU of ``d_ff`` in the ``first_k_dense`` leading layers;
  after them ``shared(u) + sum_i p_i E_i(u)``: a shared SwiGLU expert
  every token takes, beside ``moe.route_sigmoid_group_topk`` (sigmoid
  scores, a selection bias, the best ``topk_group`` of ``n_group``
  groups by their two best biased scores, the ``top_k`` inside them,
  renormalised, times ``routed_scaling_factor``) over
  ``moe.sorted_expert_ffn``: every routed pair whose expert is HELD
  here (``experts_first``, ``experts_held``) computed, none dropped;
  what the absent experts would have added is left out.
* **Head.** Final RMSNorm, an untied ``[d, vocab]`` matrix, f32 logits.
  The multi-token-prediction module is not part of this file.

Parameters are stacked by stretch (``kvpage.compress_layers``:
``params["seg0"]`` the dense layers, ``params["seg1"]`` the expert
layers, leaves ``[repeats, ...]``), each one ``lax.scan``.
:func:`paged_spec` is what ``serve_paged_greedy``'s plane asks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpi_acx_tpu.models import kvpage, moe
from mpi_acx_tpu.models.llama import rmsnorm


@dataclasses.dataclass(frozen=True)
class GigaChatConfig:
    vocab: int = 128256
    d_model: int = 7168
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    d_ff: int = 18432                # dense SwiGLU width
    moe_d_ff: int = 2048             # one expert's width, the shared one's
    n_layers: int = 64
    first_k_dense: int = 3
    n_experts: int = 256
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6
    rope_theta: float = 100000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq: int = 262144
    # The experts held HERE: ``experts_held`` of them from
    # ``experts_first`` (None: all). The router keeps its width.
    experts_first: int = 0
    experts_held: Optional[int] = None
    # Tokens an expert layer takes at a time in a whole-sequence pass:
    # the sorted pairs' copies are ``top_k`` rows a token, held or not.
    moe_block: int = 2048
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None     # prefill attention; None = auto
    decode_flash: Optional[bool] = None  # paged decode kernels; None = auto

    @property
    def n_held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    @property
    def row_dim(self) -> int:
        """What a token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def gigachat31_702b() -> GigaChatConfig:
    """GigaChat3.1-702B-A36B as published (64 layers, 256 experts)."""
    return GigaChatConfig()


def tiny_gigachat(**over) -> GigaChatConfig:
    """Small config for tests, every mechanism present: one dense layer,
    two expert layers of 2 groups x 4 experts (the best group kept, top
    2 inside it) beside a shared expert; 4 heads on a 32 + 16 wide row."""
    base = dict(vocab=96, d_model=64, n_heads=4, q_lora_rank=48,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
                v_head_dim=24, d_ff=96, moe_d_ff=32, n_layers=3,
                first_k_dense=1, n_experts=8, top_k=2, n_group=2,
                topk_group=1, rope_original_max=64, rope_factor=4.0,
                max_seq=512, moe_block=16)
    base.update(over)
    return GigaChatConfig(**base)


Params = Dict[str, Any]
_EXPERT_STACKS = ("w1", "w3", "w2")    # of a routed FFN: never sliced


def layer_kinds(cfg: GigaChatConfig) -> Tuple[kvpage.LayerKind, ...]:
    return tuple(kvpage.LayerKind(
        operator="latent_attention",
        ffn="dense" if l < cfg.first_k_dense else "moe", cache="pages")
        for l in range(cfg.n_layers))


def segments(cfg: GigaChatConfig) -> Tuple[kvpage.Segment, ...]:
    segs = kvpage.compress_layers(layer_kinds(cfg))
    assert all(len(s.period) == 1 for s in segs), segs
    return segs


def leaf_shapes(cfg: GigaChatConfig, kind: kvpage.LayerKind) -> Dict[str, tuple]:
    """One layer's leaves: name -> (shape, init; None = ones, "bias" =
    the router's selection bias, else a normal's scale)."""
    d, H, s = cfg.d_model, cfg.n_heads, 0.02
    out = {"attn_norm": ((d,), None), "ffn_norm": ((d,), None),
           "w_dq": ((d, cfg.q_lora_rank), s),
           "q_norm": ((cfg.q_lora_rank,), None),
           "w_uq": ((cfg.q_lora_rank, H * cfg.qk_head_dim), s),
           "w_dkv": ((d, cfg.row_dim), s),
           "kv_norm": ((cfg.kv_lora_rank,), None),
           "w_uk": ((cfg.kv_lora_rank, H * cfg.qk_nope_head_dim), s),
           "w_uv": ((cfg.kv_lora_rank, H * cfg.v_head_dim), s),
           "w_o": ((H * cfg.v_head_dim, d), s)}
    if kind.ffn == "dense":
        out.update(w1=((d, cfg.d_ff), s), w3=((d, cfg.d_ff), s),
                   w2=((cfg.d_ff, d), s))
    else:
        n, f = cfg.n_held, cfg.moe_d_ff
        out.update(gate=((d, cfg.n_experts), s),
                   bias=((cfg.n_experts,), "bias"),
                   w1=((n, d, f), s), w3=((n, d, f), s), w2=((n, f, d), s),
                   ws1=((d, f), s), ws3=((d, f), s), ws2=((f, d), s))
    return out


def init_params(key: jax.Array, cfg: GigaChatConfig) -> Params:
    """f32 parameters, stacked by segment; untied embedding and head.
    The selection bias is small and NOT zero (HF initialises zeros), so
    that selection and weight differ."""
    k = lambda n: jax.random.fold_in(key, n)
    params = {
        "embed": jax.random.normal(k(0), (cfg.vocab, cfg.d_model)) * 0.02,
        "head": jax.random.normal(k(1), (cfg.d_model, cfg.vocab)) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,))}
    n = 1
    for seg in segments(cfg):
        leaves = {}
        for name, (shape, init) in sorted(
                leaf_shapes(cfg, seg.period[0]).items()):
            n += 1
            shape = (seg.repeats,) + shape
            if init is None:
                leaves[name] = jnp.ones(shape)
            elif init == "bias":
                leaves[name] = jax.random.uniform(k(n), shape, jnp.float32,
                                                  -0.05, 0.05)
            else:
                leaves[name] = jax.random.normal(k(n), shape) * init
        params[seg.key] = leaves
    return params


def cast_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """The tree in ``dtype`` for inference; the router (``gate``,
    ``bias``) and the norms stay f32: they are computed in f32."""
    return kvpage.cast_params(params, dtype, ("gate", "bias"))


# -- positions ---------------------------------------------------------------


def yarn_inv_freq(cfg: GigaChatConfig) -> np.ndarray:
    """[rope / 2] inverse frequencies: YaRN's blend of the interpolated
    (``/ factor``) and the original ones by a linear ramp over the pair
    index between the pairs that turn ``beta_fast`` and ``beta_slow``
    times within the original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(pair_of(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / cfg.rope_factor * ramp + extra * (1 - ramp)).astype(
        np.float32)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attn_scale(cfg: GigaChatConfig) -> float:
    """``qk_head_dim^-0.5 * m^2``, ``m`` YaRN's ``mscale_all_dim`` term."""
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return cfg.qk_head_dim ** -0.5 * m * m


def _rope(cfg: GigaChatConfig, x: jax.Array, positions: jax.Array):
    """x [..., S, H, rope] f32 at ``positions`` [S] (or [..., S]): pairs
    ``(2i, 2i + 1)`` rotated, written to ``(i, rope / 2 + i)``. cos and
    sin carry ``mscale / mscale_all_dim`` (1 as published)."""
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    amp = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
           / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos, sin = (amp * jnp.cos(ang)[..., None, :],
                amp * jnp.sin(ang)[..., None, :])
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# -- the layer functions -----------------------------------------------------


def _w(lp, name, dtype):
    return lp[name].astype(dtype)


def _latent(cfg: GigaChatConfig, lp: Params, x: jax.Array, positions):
    """x [B, S, d] -> (q_nope [B, S, H, nope] f32, q_rope [B, S, H,
    rope] f32 with RoPE, row [B, S, row_dim]: ``[c_kv | k_rope]`` as it
    goes into the cache). The norms and RoPE in float32 with ONE
    rounding to the compute type where a value is stored."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    u = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    cq = rmsnorm(u @ _w(lp, "w_dq", x.dtype), lp["q_norm"], cfg.norm_eps)
    q = jnp.dot(cq, _w(lp, "w_uq", x.dtype),
                preferred_element_type=jnp.float32).reshape(
                    B, S, H, cfg.qk_head_dim)
    ckr = jnp.dot(u, _w(lp, "w_dkv", x.dtype),
                  preferred_element_type=jnp.float32)
    c = rmsnorm(ckr[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_rope = _rope(cfg, ckr[..., None, r:], positions)[..., 0, :]
    row = jnp.concatenate([c, k_rope], -1).astype(x.dtype)
    return (q[..., :cfg.qk_nope_head_dim],
            _rope(cfg, q[..., cfg.qk_nope_head_dim:], positions), row)


def _up(cfg: GigaChatConfig, lp: Params, rows: jax.Array):
    """Rows ``[T, row_dim]`` up-projected: (k [H, T, nope + rope], v [H,
    T, v]), the shared ``k_rope`` under every head."""
    T, H, r = rows.shape[0], cfg.n_heads, cfg.kv_lora_rank
    c = rows[:, :r]
    k_nope = (c @ _w(lp, "w_uk", rows.dtype)).reshape(T, H, -1)
    v = (c @ _w(lp, "w_uv", rows.dtype)).reshape(T, H, -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        rows[:, None, r:], (T, H, cfg.qk_rope_head_dim))], -1)
    return k.transpose(1, 0, 2), v.transpose(1, 0, 2)


def _rows_attend(cfg: GigaChatConfig, q, k, v, offset: int):
    """Causal attention of ``q`` [H, S, D], rows ``offset ..`` of the
    sequence whose keys are ``k`` [H, Sk, D], ``v`` [H, Sk, Dv]: the
    Pallas kernel where the auto policy (``use_flash`` None: on a TPU
    at lengths Mosaic tiles) or ``use_flash`` says so, else plainly."""
    from mpi_acx_tpu import backend
    from mpi_acx_tpu.ops.attention import _NEG_INF, flash_rows_attention
    S, Sk = q.shape[1], k.shape[1]
    flash = cfg.use_flash
    if flash is None:
        flash = backend.on_tpu() and S % 64 == 0 and Sk % 128 == 0
    if flash:
        return flash_rows_attention(q, k, v, q_offset=offset,
                                    scale=attn_scale(cfg))
    q = (q.astype(jnp.float32) * attn_scale(cfg)).astype(q.dtype)
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   preferred_element_type=jnp.float32)
    seen = (offset + jnp.arange(S))[:, None] >= jnp.arange(Sk)[None]
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v)


def _sequence_attention(cfg: GigaChatConfig, lp: Params, x: jax.Array,
                        positions, history=None):
    """The UNABSORBED path over one sequence, residual included: x [1,
    S, d] -> (x, row [S, row_dim]). ``history`` [row_dim, P]: the cached
    rows of the P positions before (a pool's layout), up-projected with
    the sequence's own; their keys lie in front."""
    S, H = x.shape[1], cfg.n_heads
    q_nope, q_rope, row = _latent(cfg, lp, x, positions)
    q = jnp.concatenate([q_nope, q_rope], -1)[0].astype(x.dtype)
    rows, P = row[0], 0
    if history is not None:
        P = history.shape[-1]
        # (keys past the suffix's own are padding: to a block's whole)
        rows = jnp.concatenate([history.T.astype(x.dtype), rows,
                                jnp.zeros((-(P + S) % 128, cfg.row_dim),
                                          x.dtype)])
    k, v = _up(cfg, lp, rows)
    o = _rows_attend(cfg, q.transpose(1, 0, 2), k, v, P)
    o = o.transpose(1, 0, 2).reshape(1, S, H * cfg.v_head_dim)
    return x + o @ _w(lp, "w_o", x.dtype), row[0]


def _decode_qkv(cfg: GigaChatConfig, lp: Params, x: jax.Array, pos):
    """``PagedSpec.qkv`` of a latent pool, the ABSORBED query: x [B, 1,
    d] -> (q [B, 1, H, row_dim]: ``[W_UK_h^T q_nope_h | q_rope_h]``, the
    token's row [B, 1, 1, row_dim])."""
    q_nope, q_rope, row = _latent(cfg, lp, x, pos[:, None])
    w_uk = _w(lp, "w_uk", x.dtype).reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    qa = jnp.einsum("bshn,chn->bshc", q_nope.astype(x.dtype), w_uk,
                    preferred_element_type=jnp.float32)
    q = jnp.concatenate([qa, q_rope], -1).astype(x.dtype)
    return q, row[:, :, None, :]


def _decode_attn_out(cfg: GigaChatConfig, lp: Params, x: jax.Array, o):
    """``PagedSpec.attn_out``: o [B, 1, H * kv_lora_rank], each head's
    ``sum_s p c_kv(s)``, through ``W_UV_h`` and ``W_O``, + residual."""
    B, H = x.shape[0], cfg.n_heads
    w_uv = _w(lp, "w_uv", x.dtype).reshape(cfg.kv_lora_rank, H, -1)
    o = jnp.einsum("bshc,chv->bshv", o.reshape(B, 1, H, -1), w_uv)
    return x + o.reshape(B, 1, -1) @ _w(lp, "w_o", x.dtype)


def _swiglu(u, w1, w3, w2):
    return (jax.nn.silu(u @ w1) * (u @ w3)) @ w2


def _dense_ffn(cfg: GigaChatConfig, lp: Params, x: jax.Array):
    u = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps)
    return x + _swiglu(u, *(_w(lp, n, x.dtype) for n in ("w1", "w3", "w2")))


def _shared_ffn(cfg: GigaChatConfig, lp: Params, u: jax.Array):
    """The shared expert: every token's, whoever holds which experts."""
    return _swiglu(u, *(_w(lp, n, u.dtype) for n in ("ws1", "ws3", "ws2")))


def _moe_ffn(cfg: GigaChatConfig, lp: Params, x: jax.Array, live=None):
    """(x + shared + the held experts' part, idx [T, k] the experts
    chosen, kept [T, n_group] the routing groups they were chosen in).
    ``live`` [T] bool: the tokens whose result anybody receives (None:
    all); the others' held experts are not computed (the shared expert
    is: its weights are read once whatever the rows)."""
    d = cfg.d_model
    u = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps).reshape(-1, d)
    idx, p, kept = moe.route_sigmoid_group_topk(
        u, lp["gate"], lp["bias"], cfg.top_k, cfg.n_group, cfg.topk_group,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    # (with "repeat" the expert matrices are the segment's whole stacks)
    w = tuple(_w(lp, n, x.dtype) for n in _EXPERT_STACKS)

    def held(u, idx, p, live=None):
        return moe.sorted_expert_ffn(u, *w, idx, p, first=cfg.experts_first,
                                     layer=lp.get("repeat"), live=live)
    T, blk = u.shape[0], cfg.moe_block
    rows = (u, idx, p) if live is None else (u, idx, p, live)
    if T > blk and T % blk == 0:
        y = lax.map(lambda a: held(*a), tuple(
            a.reshape((T // blk, blk) + a.shape[1:]) for a in rows))
        y = y.reshape(T, d)
    else:
        y = held(*rows)
    y = y + _shared_ffn(cfg, lp, u).astype(jnp.float32)
    return x + y.astype(x.dtype).reshape(x.shape), idx, kept


def _ffn(cfg: GigaChatConfig, lp: Params, x: jax.Array, kind: str,
         live=None):
    return (_dense_ffn(cfg, lp, x) if kind == "dense"
            else _moe_ffn(cfg, lp, x, live))


def _head(params: Params, cfg: GigaChatConfig, x: jax.Array):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.dot(x, params["head"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def forward(params: Params, cfg: GigaChatConfig, tokens: jax.Array):
    """tokens [1, S] int32 -> logits [1, S, vocab] (f32): the plain
    whole-sequence pass, no cache, one sequence."""
    return kvpage.forward(params, cfg, paged_spec(cfg), tokens)


# -- the paged plane's seam --------------------------------------------------


def paged_spec(cfg: GigaChatConfig) -> kvpage.PagedSpec:
    """What ``serve_paged_greedy``'s plane asks of this family: a LATENT
    pool ([L, P, 1, row_dim, pt]: one row a token a layer, read by all
    ``n_heads`` heads, the value its first ``kv_lora_rank`` values; no V
    pool), the absorbed query and output, the decode attend's own scale,
    the router's width and which of its experts are held here. int8
    latent pages are not wired."""
    return kvpage.PagedSpec(
        segments=segments(cfg), n_kv_heads=1, head_dim=cfg.row_dim,
        n_rep=cfg.n_heads, v_dim=cfg.kv_lora_rank,
        attn_scale=attn_scale(cfg), n_experts=cfg.n_experts,
        experts_held=(cfg.experts_first, cfg.n_held), kv_int8=False,
        moe_whole=_EXPERT_STACKS,
        ffn_built=(("dense", "_dense_ffn"),
                   ("moe", "_shared_ffn+sorted_expert_ffn/"
                    + moe.select_grouped_matmul().__name__)),
        embed=lambda params, cfg, token, pos:
            params["embed"][token][:, None, :].astype(cfg.dtype),
        qkv=_decode_qkv, attn_out=_decode_attn_out, ffn=_ffn,
        head=lambda params, cfg, x: _head(params, cfg, x)[:, 0],
        seq_attention=_sequence_attention, seq_head=_head)
