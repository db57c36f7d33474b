"""Tensor-parallel (Megatron-style) inference for the model families.

Decoding is latency-bound — each autoregressive step is a skinny
[B, 1, *] pass that one chip's HBM bandwidth gates. Head-parallel
attention + column/row-parallel MLP split every weight matrix (and the KV
cache) over a 'tp' mesh axis so each step streams 1/tp of the weights per
chip, at the cost of two ``psum``s per layer (the classic Megatron
residual-boundary all-reduces) riding ICI.

The whole generation — prefill, KV cache, the ``lax.scan`` decode loop,
greedy or temperature/top-k/top-p sampling — runs inside ONE ``shard_map``
program (:func:`_run_generation`, shared by the families): the cache never
leaves its shard, XLA sees the full schedule, and every rank computes
identical logits (each psum replicates them), so the emitted tokens agree
rank-to-rank by construction.

GPT-2 (:func:`make_tp_generate`) shards the packed qkv by attention head;
Llama (:func:`make_tp_generate_llama`) shards by KV-HEAD GROUP — each rank
holds ``n_kv_heads/tp`` K/V heads plus their ``n_rep`` query heads, so the
per-rank cache keeps GQA's bandwidth win and grouped-query attention runs
against the un-repeated local cache. Greedy output matches the
single-device generate paths exactly (tests/test_tp_inference.py).

The reference has no serving stack (SURVEY.md §0: "not a training
framework" — and not an inference one either); this is the
application-layer counterpart of train.py's tensor parallelism, built on
the same mesh/collective substrate.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mpi_acx_tpu.models import llama as lm
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.models.decoding import (decode_layer_scan,
                                         grouped_decode_attend,
                                         new_kv_cache, sample_logits)
from mpi_acx_tpu.ops.attention import select_attention
from mpi_acx_tpu.ops.wquant import wread


def _run_generation(hooks, layers, prompt, key, n_new, *, pick):
    """The family-independent TP generation loop (per-shard code).

    hooks: embed(tokens [B,S]) -> x; embed_tok(tok [B], pos) -> x [B,1,d];
    prefill_layer(x, lp) -> (x, (k, v));
    decode_qkv(lp, x, pos) -> (q, k, v) (k/v [B, 1, H_local, D]);
    decode_attend(lp, x, q, kc, vc, pos, max_len) -> x (kc/vc are the
    layer's updated cache slices); finish(x) -> logits [B, S, vocab] f32.

    The decode loop owns the cache writes through the shared carry-scan
    (models.decoding.decode_layer_scan): in-place per-layer updates,
    1.9x faster decode on v5e than scan-ys stacking.
    """
    B, S = prompt.shape
    max_len = S + n_new

    x = hooks["embed"](prompt)
    x, (ks, vs) = lax.scan(hooks["prefill_layer"], x, layers)
    logits0 = hooks["finish"](x[:, -1:])[:, 0]            # [B, vocab]

    # Prefill K/V are token-major ([L, B, S, H_local, D]); the cache is
    # [L, B, H_local, D, max_len] (decoding.to_cache_layout).
    cache = _pack_prefill_cache(ks, vs, max_len, kv_int8=False)
    kc, vc = cache["k"], cache["v"]

    def dec_body(carry, step_key):
        kc, vc, pos, tok = carry
        x = hooks["embed_tok"](tok, pos)
        x, kc, vc = decode_layer_scan(
            layers, x, kc, vc, pos, hooks["decode_qkv"],
            lambda lp, x, q, kc_l, vc_l, pos: hooks["decode_attend"](
                lp, x, q, kc_l, vc_l, pos, max_len))
        nxt = pick(hooks["finish"](x)[:, 0], step_key)
        return (kc, vc, pos + 1, nxt), tok

    first = pick(logits0, key)
    keys = jax.random.split(jax.random.fold_in(key, 1), n_new)
    (_, _, _, _), toks = lax.scan(
        dec_body, (kc, vc, jnp.asarray(S, jnp.int32), first), keys)
    return jnp.concatenate([prompt, jnp.moveaxis(toks, 0, 1)], axis=1)


def _make_pick(temperature, top_k, top_p, out_dtype):
    def pick(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(out_dtype)
        return sample_logits(logits, k, temperature, top_k,
                             top_p).astype(out_dtype)
    return pick


# -- GPT-2 family ----------------------------------------------------------


def _scale_keys(params) -> frozenset:
    """The int8 weight-only scale companions present in a checkpoint
    (ops/wquant.py). TP serving supports them for the dense matmul
    weights: the shard fns re-layout each companion alongside its
    weight, the spec trees gain matching entries (_specs_with_scales),
    and every weight read goes through ops.wquant.wread."""
    return frozenset(k for k in params["layers"] if k.endswith("_scale"))


def _specs_with_scales(specs, scale_keys: frozenset, scale_specs: dict,
                       where: str):
    """Extend a family's layer spec tree with entries for the scale
    companions actually present. Unknown companions (e.g. quantized MoE
    expert weights) raise LOUDLY — the alternative is multiplying raw
    int8 codes without their scales."""
    unknown = [k for k in scale_keys if k not in scale_specs]
    if unknown:
        raise ValueError(
            f"{where} does not support int8 quantization of {unknown} "
            f"(supported: {sorted(scale_specs)}); see ops/wquant.py")
    if not scale_keys:
        return specs
    out = dict(specs)
    out["layers"] = dict(specs["layers"],
                         **{k: scale_specs[k] for k in scale_keys})
    return out


def _tp_program_cache(mesh, per_shard, param_slots, data_specs,
                      out_specs, donate_argnums=()):
    """THE scale-keyed program cache every TP builder uses: one
    compiled shard_map program per tuple of int8 scale-key sets, so
    quantized and plain checkpoints (whose pytrees differ) share the
    per-shard code but get matching spec trees.

    ``param_slots``: one (base_specs, scale_specs, shard_fn, cfg,
    where) per leading parameter-tree argument of ``per_shard``; the
    remaining arguments use ``data_specs``. ``donate_argnums`` (indices
    into the combined ``(*param_trees, *data)`` argument list) lets a
    carry-style caller donate its buffers — TP serving donates the slot
    caches so each chunk updates them in place. Returns a plain
    callable ``fn(*param_trees, *data)``."""
    n = len(param_slots)
    cache: dict = {}

    def call(*args):
        key = tuple(_scale_keys(p) for p in args[:n])
        fn = cache.get(key)
        if fn is None:
            in_specs = tuple(
                _specs_with_scales(bs, sk, ss, where)
                for (bs, ss, _, _, where), sk in zip(param_slots, key)
            ) + tuple(data_specs)
            inner = shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False)

            def run(*a, _inner=inner):
                pt = tuple(slot[2](p, slot[3])
                           for slot, p in zip(param_slots, a[:n]))
                return _inner(*pt, *a[n:])

            fn = cache[key] = jax.jit(run,
                                      donate_argnums=donate_argnums)
        return fn(*args)

    return call


def tp_shard_params(params, cfg: tfm.TransformerConfig):
    """Re-layout the stacked GPT-2 pytree for head/FFN sharding: wqkv
    [L, d, 3d] -> [L, d, 3, H, Dh] (the head axis becomes shardable
    without splitting the packed q/k/v thirds) and wo [L, d, d] ->
    [L, H, Dh, d] (row-parallel by head). Int8 scale companions are
    re-laid-out alongside their weights (w1/w2 scales broadcast as-is)."""
    L, d = cfg.n_layers, cfg.d_model
    H, Dh = cfg.n_heads, cfg.head_dim
    lay = params["layers"]
    out = dict(params)
    out["layers"] = dict(
        lay,
        wqkv=lay["wqkv"].reshape(L, d, 3, H, Dh),
        wo=lay["wo"].reshape(L, H, Dh, d),
    )
    if "wqkv_scale" in lay:
        out["layers"]["wqkv_scale"] = lay["wqkv_scale"].reshape(
            L, 1, 3, H, Dh)
    if "wo_scale" in lay:
        out["layers"]["wo_scale"] = lay["wo_scale"].reshape(L, 1, 1, d)
    return out


def _gpt2_scale_specs(axis: str):
    """Spec entries for GPT-2 scale companions after tp_shard_params:
    per-OUTPUT-channel scales shard with their weight's output axis
    (wqkv: heads; w1: ffn) and replicate when the weight shards on its
    input side (wo, w2)."""
    return {
        "wqkv_scale": P(None, None, None, axis, None),
        "wo_scale": P(),
        "w1_scale": P(None, None, axis),
        "w2_scale": P(),
    }


def _moe_scale_specs(axis: str):
    """MoE TP serving supports int8 on the ATTENTION weights only (they
    ride the shared GPT-2 ops); expert-weight companions are absent
    here so _specs_with_scales rejects them loudly. One definition for
    plain AND speculative MoE TP serving."""
    gs = _gpt2_scale_specs(axis)
    return {k: gs[k] for k in ("wqkv_scale", "wo_scale")}


def tp_param_specs(axis: str = "tp"):
    """PartitionSpecs matching :func:`tp_shard_params` output: attention
    sharded on the head axis, MLP on the FFN axis, the rest replicated."""
    return {
        "embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
        "layers": {
            "ln1_g": P(), "ln1_b": P(),
            "wqkv": P(None, None, None, axis, None),
            "wo": P(None, axis),
            "ln2_g": P(), "ln2_b": P(),
            "w1": P(None, None, axis), "b1": P(None, axis),
            "w2": P(None, axis), "b2": P(),
        },
    }


def _gpt2_embed(params, cfg, tokens):
    """Token + learned-position embedding (replicated leaves)."""
    S = tokens.shape[1]
    return (params["embed"][tokens] + params["pos"][:S]).astype(cfg.dtype)


def _gpt2_finish(params, cfg, x):
    """Final layernorm + tied unembedding -> f32 logits."""
    x = tfm.layernorm(x, params["lnf_g"], params["lnf_b"])
    return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _gpt2_tp_layer_ops(cfg, tp: int, axis: str):
    """The head/FFN-split per-layer primitives shared by TP generation
    and TP speculative decoding: (local_qkv, out_proj, dense_mlp) over
    this rank's Hl = n_heads/tp head slice (two psums per layer at the
    residual boundaries — the classic Megatron split)."""
    H, Dh, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    assert H % tp == 0, (H, tp)
    Hl = H // tp

    def local_qkv(lp, x):
        B, S, _ = x.shape
        h = tfm.layernorm(x, lp["ln1_g"], lp["ln1_b"])
        qkv = h @ wread(lp, "wqkv", x.dtype).reshape(d, 3 * Hl * Dh)
        return (t.reshape(B, S, Hl, Dh) for t in jnp.split(qkv, 3, -1))

    def out_proj(lp, o, x):
        B, S = o.shape[:2]
        part = o.reshape(B, S, Hl * Dh) @ wread(lp, "wo",
                                                x.dtype).reshape(
            Hl * Dh, d)
        return x + lax.psum(part, axis)

    def dense_mlp(lp, x):
        h = tfm.layernorm(x, lp["ln2_g"], lp["ln2_b"])
        y = jax.nn.gelu(h @ wread(lp, "w1", x.dtype)
                        + lp["b1"].astype(x.dtype))
        part = y @ wread(lp, "w2", x.dtype)
        return x + lax.psum(part, axis) + lp["b2"].astype(x.dtype)

    return local_qkv, out_proj, dense_mlp


def make_tp_generate(cfg, mesh: Mesh, n_new: int,
                     axis: str = "tp", temperature: float = 0.0,
                     top_k: Optional[int] = None,
                     top_p: Optional[float] = None,
                     ffn=None, specs=None, shard_params=None,
                     scale_specs=None):
    """Builds a jitted tensor-parallel ``generate(params, prompt, key) ->
    tokens [B, S + n_new]`` over the mesh's ``axis``.

    params is the ORDINARY transformer pytree (tfm.init_params /
    cast_params output) — the TP re-layout happens inside the jit.
    ``temperature=0`` is greedy (key unused but still required, so the
    signature is stable across sampling configs).

    ``ffn(lp, x) -> x`` overrides the per-layer feed-forward half (the
    dense column/row-parallel MLP by default), with ``specs``/
    ``shard_params`` overriding the weight layout to match — the GPT-2-
    attention MoE family plugs in its expert-parallel FFN this way
    (:func:`make_tp_generate_moe`), mirroring the single-device ffn hook
    on tfm.prefill/decode_step.
    """
    tp = mesh.shape[axis]
    local_qkv, out_proj, dense_mlp = _gpt2_tp_layer_ops(cfg, tp, axis)
    mlp = ffn or dense_mlp
    shard_params_fn = shard_params or tp_shard_params
    specs = specs or tp_param_specs(axis)
    if scale_specs is None:
        scale_specs = _gpt2_scale_specs(axis)

    def per_shard(params, prompt, key):
        assert prompt.shape[1] + n_new <= cfg.max_seq

        def embed(tokens):
            return _gpt2_embed(params, cfg, tokens)

        def embed_tok(tok, pos):
            return (params["embed"][tok][:, None, :]
                    + params["pos"][pos][None, None, :]).astype(cfg.dtype)

        def prefill_layer(x, lp):
            q, k, v = local_qkv(lp, x)
            o = select_attention(cfg.use_flash)(q, k, v)
            return mlp(lp, out_proj(lp, o, x)), (k, v)

        def decode_qkv(lp, x, pos):
            return tuple(local_qkv(lp, x))

        def decode_attend(lp, x, q, kcl, vcl, pos, max_len):
            # Shared MHA decode attention (GQA construction, n_rep=1).
            o = grouped_decode_attend(q, kcl, vcl, pos, max_len, n_rep=1,
                                      flash=cfg.decode_flash)
            return mlp(lp, out_proj(lp, o, x))

        def finish(x):
            return _gpt2_finish(params, cfg, x)

        hooks = {"embed": embed, "embed_tok": embed_tok,
                 "prefill_layer": prefill_layer,
                 "decode_qkv": decode_qkv,
                 "decode_attend": decode_attend, "finish": finish}
        return _run_generation(
            hooks, params["layers"], prompt, key, n_new,
            pick=_make_pick(temperature, top_k, top_p, prompt.dtype))

    return _tp_program_cache(
        mesh, per_shard,
        [(specs, scale_specs, shard_params_fn, cfg,
          "TP GPT-2/MoE serving")],
        (P(), P()), P())


# -- MoE family (attention by head, experts over the same axis) ------------


# Attention re-layout is exactly the dense family's (cfg duck-types);
# expert tensors keep their layout — the [n_experts] dim shards directly.
tp_shard_params_moe = tp_shard_params


def tp_param_specs_moe(axis: str = "tp"):
    return {
        "embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
        "layers": {
            "ln1_g": P(), "ln1_b": P(),
            "wqkv": P(None, None, None, axis, None),
            "wo": P(None, axis),
            "ln2_g": P(), "ln2_b": P(), "gate": P(),
            "w1": P(None, axis), "w2": P(None, axis),
        },
    }


def _ep_dispatch_mode(mode: str, tokens: int, ep: int) -> str:
    """Resolve the effective EP dispatch for ONE routed call moving
    ``tokens`` tokens over an ``ep``-way axis. ``"auto"`` picks
    ``"sharded"`` when the token count divides the axis — in the
    drop-free serving regime the two paths are token-identical, so
    divisibility is the only real constraint — and falls back to
    ``"replicated"`` otherwise (B=1 latency decode, k-wide speculative
    verify windows). Shapes are static under jit, so the choice is
    made at trace time, per call site: a prefill can dispatch sharded
    while the same program's decode runs replicated."""
    if mode == "auto":
        return "sharded" if tokens % ep == 0 else "replicated"
    return mode


def _make_moe_ffn(cfg, tp: int, axis: str, ep_dispatch: str):
    """THE MoE expert-FFN hook every TP builder shares (generate,
    speculative, serving): validates the expert split, resolves
    ``ep_dispatch`` per call site (_ep_dispatch_mode), and applies the
    drop-free degrade — outside ``capacity_factor >= n_experts``,
    sharded dispatch forms different capacity groups than the
    single-device run, so "auto" degrades to replicated (bit-equal at
    any capacity) to keep the exact-parity contract; an EXPLICIT
    "sharded" request is honored as-is."""
    from mpi_acx_tpu.models.moe_transformer import _moe_ffn

    assert cfg.n_experts % tp == 0, (cfg.n_experts, tp)
    assert ep_dispatch in ("auto", "sharded", "replicated"), ep_dispatch
    side = ep_dispatch
    if side == "auto" and cfg.capacity_factor < cfg.n_experts:
        side = "replicated"

    def moe_ffn(lp, x):
        mode = _ep_dispatch_mode(side, x.shape[0] * x.shape[1], tp)
        return _moe_ffn(cfg, lp, x, ep_axis=axis,
                        replicated=mode == "replicated",
                        sharded_dispatch=mode == "sharded")

    return moe_ffn


def make_tp_generate_moe(cfg, mesh: Mesh, n_new: int, axis: str = "tp",
                         temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         ep_dispatch: str = "auto"):
    """Tensor-parallel MoE-transformer generation: the dense GPT-2
    builder with the expert-parallel routed FFN plugged into its ffn
    hook. Attention splits by head (two psums per layer); each rank
    hosts ``n_experts/tp`` experts.

    ``ep_dispatch`` selects how tokens reach their experts:

    * ``"auto"`` (default) — per call site (trace-time, shapes are
      static): ``"sharded"`` whenever the call's token count divides
      tp, ``"replicated"`` otherwise. Prefill (B*S tokens) and
      batch-serving decode get real EP scaling; B=1 latency decode
      falls back to replicated instead of raising. Outside the
      drop-free regime (capacity_factor < n_experts) auto degrades to
      replicated entirely — sharded capacity groups differ from the
      single-device run's there (_make_moe_ffn holds the rule).
    * ``"sharded"`` — REAL expert-parallel dispatch
      (moe.moe_layer_sharded_dispatch): each rank routes only its
      exclusive 1/tp token slice and the capacity-bounded
      ``all_to_all`` of the training EP path carries tokens to their
      expert's rank and back, then one all_gather re-replicates.
      Router + dispatch work per rank genuinely scales as 1/tp —
      this is the path that scales past small tp. Requires every
      routed call's token count to divide tp (decode routes B tokens
      per step; raises at trace time).
    * ``"replicated"`` — every rank routes ALL tokens, local expert
      block + one psum (moe.moe_layer_replicated_ep): only the expert
      FLOPs shard, but any batch size works and routing is bit-equal
      to the single-device dispatch at any capacity.

    In the drop-free regime (``capacity_factor >= n_experts``, the
    serving guard — see moe_transformer.decode_step) all paths emit
    tokens identical to the single-device ``generate``
    (tests/test_tp_inference.py covers tp=4 and tp=8, plus the auto
    fallback at an indivisible batch)."""
    moe_ffn = _make_moe_ffn(cfg, mesh.shape[axis], axis, ep_dispatch)

    return make_tp_generate(cfg, mesh, n_new, axis=axis,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, ffn=moe_ffn,
                            specs=tp_param_specs_moe(axis),
                            shard_params=tp_shard_params_moe,
                            scale_specs=_moe_scale_specs(axis))


# -- Llama family (GQA: shard by KV-head group) ----------------------------


def tp_shard_params_llama(params, cfg: lm.LlamaConfig):
    """Head-axis re-layout for the Llama pytree: wq [L, d, Hq*Dh] ->
    [L, d, Hq, Dh], wk/wv -> [L, d, Hkv, Dh], wo -> [L, Hq, Dh, d].
    Contiguous head chunks keep each KV group's query heads on the same
    rank as their K/V head (query head h belongs to group h // n_rep).
    Int8 scale companions are re-laid-out alongside their weights."""
    L, d = cfg.n_layers, cfg.d_model
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lay = params["layers"]
    out = dict(params)
    out["layers"] = dict(
        lay,
        wq=lay["wq"].reshape(L, d, Hq, Dh),
        wk=lay["wk"].reshape(L, d, Hkv, Dh),
        wv=lay["wv"].reshape(L, d, Hkv, Dh),
        wo=lay["wo"].reshape(L, Hq, Dh, d),
    )
    for name, shp in (("wq", (L, 1, Hq, Dh)), ("wk", (L, 1, Hkv, Dh)),
                      ("wv", (L, 1, Hkv, Dh)), ("wo", (L, 1, 1, d))):
        if name + "_scale" in lay:
            out["layers"][name + "_scale"] = \
                lay[name + "_scale"].reshape(shp)
    return out


def _llama_scale_specs(axis: str):
    """Spec entries for Llama scale companions after
    tp_shard_params_llama (output-side scales shard with their heads /
    ffn axis; input-side-sharded weights get replicated scales)."""
    return {
        "wq_scale": P(None, None, axis, None),
        "wk_scale": P(None, None, axis, None),
        "wv_scale": P(None, None, axis, None),
        "wo_scale": P(),
        "w_gate_scale": P(None, None, axis),
        "w_up_scale": P(None, None, axis),
        "w_down_scale": P(),
    }


def tp_param_specs_llama(axis: str = "tp"):
    return {
        "embed": P(), "final_norm": P(), "unembed": P(),
        "layers": {
            "attn_norm": P(), "mlp_norm": P(),
            "wq": P(None, None, axis, None),
            "wk": P(None, None, axis, None),
            "wv": P(None, None, axis, None),
            "wo": P(None, axis),
            "w_gate": P(None, None, axis),
            "w_up": P(None, None, axis),
            "w_down": P(None, axis),
        },
    }


def _llama_tp_layer_ops(cfg, tp: int, axis: str):
    """Llama per-layer primitives for TP generation AND TP speculative
    decoding, sharded by KV-HEAD GROUP: (local_qkv, out_proj, mlp,
    n_rep). Each rank holds Hkv/tp K/V heads plus their n_rep query
    heads, so the local cache stays un-repeated (GQA's bandwidth win
    survives the split)."""
    Hq, Hkv, Dh, d = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                      cfg.d_model)
    assert Hkv % tp == 0, (Hkv, tp)
    n_rep = Hq // Hkv
    Hkv_l, Hq_l = Hkv // tp, (Hkv // tp) * n_rep

    def mlp(lp, x):
        h = lm.rmsnorm(x, lp["mlp_norm"])
        gate = jax.nn.silu(h @ wread(lp, "w_gate", x.dtype))
        up = h @ wread(lp, "w_up", x.dtype)
        part = (gate * up) @ wread(lp, "w_down", x.dtype)
        return x + lax.psum(part, axis)

    def local_qkv(lp, x, positions):
        B, S, _ = x.shape
        h = lm.rmsnorm(x, lp["attn_norm"])
        q = (h @ wread(lp, "wq", x.dtype).reshape(
            d, Hq_l * Dh)).reshape(B, S, Hq_l, Dh)
        k = (h @ wread(lp, "wk", x.dtype).reshape(
            d, Hkv_l * Dh)).reshape(B, S, Hkv_l, Dh)
        v = (h @ wread(lp, "wv", x.dtype).reshape(
            d, Hkv_l * Dh)).reshape(B, S, Hkv_l, Dh)
        q = lm.rope(q, positions, cfg.rope_theta)
        k = lm.rope(k, positions, cfg.rope_theta)
        return q, k, v

    def out_proj(lp, o, x):
        B, S = o.shape[:2]
        part = o.reshape(B, S, Hq_l * Dh) @ wread(lp, "wo",
                                                  x.dtype).reshape(
            Hq_l * Dh, d)
        return x + lax.psum(part, axis)

    return local_qkv, out_proj, mlp, n_rep


def make_tp_generate_llama(cfg: lm.LlamaConfig, mesh: Mesh, n_new: int,
                           axis: str = "tp", temperature: float = 0.0,
                           top_k: Optional[int] = None,
                           top_p: Optional[float] = None):
    """Tensor-parallel Llama generation: ``tp`` must divide
    ``n_kv_heads``; each rank serves ``n_kv_heads/tp`` KV groups and their
    query heads, so the local cache stays un-repeated (GQA's bandwidth
    win per rank) and grouped-query decode runs exactly as the
    single-device path (llama.decode_step), just on the group slice.
    """
    tp = mesh.shape[axis]
    local_qkv, out_proj, mlp, n_rep = _llama_tp_layer_ops(cfg, tp, axis)

    def per_shard(params, prompt, key):
        assert prompt.shape[1] + n_new <= cfg.max_seq

        def embed(tokens):
            return params["embed"][tokens].astype(cfg.dtype)

        def embed_tok(tok, pos):
            return params["embed"][tok][:, None, :].astype(cfg.dtype)

        def prefill_layer(x, lp):
            S = x.shape[1]
            q, k, v = local_qkv(lp, x, jnp.arange(S))
            kr, vr = lm._repeat_kv(k, n_rep), lm._repeat_kv(v, n_rep)
            o = select_attention(cfg.use_flash)(q, kr, vr)
            return mlp(lp, out_proj(lp, o, x)), (k, v)

        def decode_qkv(lp, x, pos):
            return local_qkv(lp, x, jnp.full((1,), pos))

        def decode_attend(lp, x, q, kcl, vcl, pos, max_len):
            # The shared grouped-GQA construction, on this rank's slice;
            # its flat [B, 1, Hq_l*Dh] output feeds out_proj directly.
            o = grouped_decode_attend(q, kcl, vcl, pos, max_len, n_rep,
                                      flash=cfg.decode_flash)
            return mlp(lp, out_proj(lp, o, x))

        def finish(x):
            x = lm.rmsnorm(x, params["final_norm"])
            return jnp.einsum("bsd,vd->bsv", x,
                              params["unembed"].astype(x.dtype),
                              preferred_element_type=jnp.float32)

        hooks = {"embed": embed, "embed_tok": embed_tok,
                 "prefill_layer": prefill_layer,
                 "decode_qkv": decode_qkv,
                 "decode_attend": decode_attend, "finish": finish}
        return _run_generation(
            hooks, params["layers"], prompt, key, n_new,
            pick=_make_pick(temperature, top_k, top_p, prompt.dtype))

    return _tp_program_cache(
        mesh, per_shard,
        [(tp_param_specs_llama(axis), _llama_scale_specs(axis),
          tp_shard_params_llama, cfg, "TP Llama serving")],
        (P(), P()), P())


# -- Tensor-parallel SPECULATIVE decoding ----------------------------------


def _pack_prefill_cache(ks, vs, cap, kv_int8):
    """Allocate a cap-length per-shard cache and land the prefill K/V
    through decoding.fill_kv_cache — the single definition of the
    (int8) cache layout, so the TP serving path cannot drift from the
    single-device one."""
    from mpi_acx_tpu.models.decoding import fill_kv_cache
    L, B, S, H, D = ks.shape
    cache = new_kv_cache(L, B, H, D, cap, ks.dtype, kv_int8)
    return fill_kv_cache(cache, ks, vs, S)


def _tp_family_ops(cfg, tp: int, axis: str, ffn=None,
                   kv_int8: bool = False):
    """GPT-2-scaffold ops with the speculative-core signatures
    (models.speculative._make_run ``ops``), tensor-parallel per shard:
    (prefill, window, decode). Each rank holds its Hl-head slice of the
    weights and KV cache; logits are assembled replicated by the
    per-layer psums, so the speculative accept/roll-back control flow —
    argmax chains, acceptance counts, while_loop conditions — computes
    identically on every rank by construction. ``ffn(lp, x) -> x``
    overrides the feed-forward half (the MoE family plugs in its
    replicated-EP routed FFN, exactly as on make_tp_generate)."""
    local_qkv, out_proj, dense_mlp = _gpt2_tp_layer_ops(cfg, tp, axis)
    mlp = ffn or dense_mlp

    embed = lambda params, tokens: _gpt2_embed(params, cfg, tokens)  # noqa: E731
    finish = lambda params, x: _gpt2_finish(params, cfg, x)  # noqa: E731

    def qkv_fn(lp, x, pos):
        return tuple(local_qkv(lp, x))

    def make_attend(max_len):
        def attend_fn(lp, x, q, kcl, vcl, pos):
            o = grouped_decode_attend(q, kcl, vcl, pos, max_len, n_rep=1,
                                      flash=cfg.decode_flash)
            return mlp(lp, out_proj(lp, o, x))
        return attend_fn

    def prefill(params, _cfg, tokens, cap, last_only=True,
                last_index=None):
        x = embed(params, tokens)

        def pl(x, lp):
            q, k_, v_ = local_qkv(lp, x)
            o = select_attention(cfg.use_flash)(q, k_, v_)
            return mlp(lp, out_proj(lp, o, x)), (k_, v_)

        x, (ks, vs) = lax.scan(pl, x, params["layers"])
        if last_index is not None:     # traced: bucket-padded serving
            x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        elif last_only:
            x = x[:, -1:]
        logits = finish(params, x)
        # Per-(position, local-head) int8 when enabled: each rank
        # quantizes its own head slice — no cross-shard state.
        return logits, _pack_prefill_cache(ks, vs, cap, kv_int8)

    def decode(params, _cfg, cache, tok):
        pos = jnp.asarray(cache["pos"])
        max_len = cache["k"].shape[-1]
        # Scalar pos (generation/speculative) or [B] per-slot positions
        # (continuous-batching serving) — as transformer.decode_step.
        pe = params["pos"][pos]
        x = (params["embed"][tok][:, None, :]
             + (pe[:, None, :] if pos.ndim else pe[None, None, :])
             ).astype(cfg.dtype)
        from mpi_acx_tpu.models.decoding import run_decode_layers
        x, out_cache = run_decode_layers(params["layers"], x, cache,
                                         qkv_fn, make_attend(max_len))
        return finish(params, x)[:, 0], out_cache

    def window(params, _cfg, cache, tokens):
        W = tokens.shape[1]
        pos = cache["pos"]
        max_len = cache["k"].shape[-1]
        x = (params["embed"][tokens]
             + lax.dynamic_slice_in_dim(params["pos"], pos, W, 0)[None]
             ).astype(cfg.dtype)
        x, kc, vc = decode_layer_scan(
            params["layers"], x, cache["k"], cache["v"], pos, qkv_fn,
            make_attend(max_len))
        logits = finish(params, x)                        # [1, W, vocab]
        return logits, {"k": kc, "v": vc, "pos": pos + W}

    return prefill, window, decode


def _llama_tp_family_ops(cfg, tp: int, axis: str,
                         kv_int8: bool = False):
    """Llama counterpart of :func:`_tp_family_ops` (speculative-core
    signatures, KV-group-sharded): RoPE at absolute positions, grouped
    decode/window attention against the un-repeated local cache."""
    local_qkv, out_proj, mlp, n_rep = _llama_tp_layer_ops(cfg, tp, axis)

    def embed(params, tokens):
        return params["embed"][tokens].astype(cfg.dtype)

    def finish(params, x):
        x = lm.rmsnorm(x, params["final_norm"])
        return jnp.einsum("bsd,vd->bsv", x,
                          params["unembed"].astype(x.dtype),
                          preferred_element_type=jnp.float32)

    def make_attend(max_len):
        def attend_fn(lp, x, q, kcl, vcl, pos):
            o = grouped_decode_attend(q, kcl, vcl, pos, max_len, n_rep,
                                      flash=cfg.decode_flash)
            return mlp(lp, out_proj(lp, o, x))
        return attend_fn

    def prefill(params, _cfg, tokens, cap, last_only=True,
                last_index=None):
        x = embed(params, tokens)
        S = tokens.shape[1]

        def pl(x, lp):
            q, k_, v_ = local_qkv(lp, x, jnp.arange(S))
            kr, vr = lm._repeat_kv(k_, n_rep), lm._repeat_kv(v_, n_rep)
            o = select_attention(cfg.use_flash)(q, kr, vr)
            return mlp(lp, out_proj(lp, o, x)), (k_, v_)

        x, (ks, vs) = lax.scan(pl, x, params["layers"])
        if last_index is not None:     # traced: bucket-padded serving
            x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        elif last_only:
            x = x[:, -1:]
        logits = finish(params, x)
        return logits, _pack_prefill_cache(ks, vs, cap, kv_int8)

    def decode(params, _cfg, cache, tok):
        pos = jnp.asarray(cache["pos"])
        max_len = cache["k"].shape[-1]
        x = params["embed"][tok][:, None, :].astype(cfg.dtype)

        def qkv_fn(lp, x, pos):
            # Scalar pos -> shared position [1]; [B] per-slot pos
            # (serving) -> [B, 1] so RoPE rotates per slot.
            p = pos[:, None] if pos.ndim else jnp.full((1,), pos)
            return local_qkv(lp, x, p)

        from mpi_acx_tpu.models.decoding import run_decode_layers
        x, out_cache = run_decode_layers(params["layers"], x, cache,
                                         qkv_fn, make_attend(max_len))
        return finish(params, x)[:, 0], out_cache

    def window(params, _cfg, cache, tokens):
        W = tokens.shape[1]
        pos = cache["pos"]
        max_len = cache["k"].shape[-1]
        x = params["embed"][tokens].astype(cfg.dtype)

        def qkv_fn(lp, x, pos):
            return local_qkv(lp, x, pos + jnp.arange(W))

        x, kc, vc = decode_layer_scan(
            params["layers"], x, cache["k"], cache["v"], pos, qkv_fn,
            make_attend(max_len))
        logits = finish(params, x)
        return logits, {"k": kc, "v": vc, "pos": pos + W}

    return prefill, window, decode


def make_tp_speculative_generate(draft_cfg, cfg, mesh: Mesh, n_new: int,
                                 k: int = 4, axis: str = "tp",
                                 temperature: float = 0.0,
                                 ep_dispatch: str = "auto"):
    """Tensor-parallel SPECULATIVE decoding: draft proposes, target
    verifies k tokens per window pass — with BOTH models Megatron-split
    over the mesh's ``axis`` inside one shard_map program (per-rank
    head slices of weights and KV caches, two psums per layer). The
    latency technique and the weight-streaming split compose: each
    draft step and each k-wide target window streams 1/tp of the
    weights per chip.

    GPT-2 and Llama families, freely mixed between draft and target
    (config type selects each side's ops; vocabularies must match).
    ``temperature=0`` is greedy: output
    tokens equal the single-device ``speculative_generate`` AND the
    target-only greedy decode (tests/test_tp_inference.py asserts both
    at tp=2/4); otherwise the stochastic accept/resample hooks run with
    the replicated key, every rank drawing identical samples.

    ``ep_dispatch`` (MoE sides only) follows make_tp_generate_moe's
    contract: ``"auto"`` (default) resolves PER CALL SITE by
    divisibility — the prompt prefill, and the k+1-wide verify window
    when ``B*(k+1)`` happens to divide tp, dispatch sharded; calls
    with indivisible token counts (single-token draft steps, most
    window geometries) fall back to replicated EP instead of raising.
    Parity exception: on an MoE side OUTSIDE the drop-free capacity
    regime, ``"auto"`` resolves to replicated for EVERY call — sharded
    dispatch forms different capacity groups than the single-device
    run, and this builder's contract is exact equality with
    ``speculative_generate`` (only the TARGET is required drop-free by
    ``_check_moe_target``; a tight-capacity DRAFT is legal, so its
    dispatch must stay bit-equal). Forcing ``"sharded"`` raises at
    trace time when any call's token count is indivisible (same rule
    as plain TP MoE serving).

    Returns a jitted ``generate(draft_params, params, prompt, key) ->
    (tokens [1, S+n_new], stats)`` with stats as in
    ``speculative_generate``.
    """
    from mpi_acx_tpu.models.speculative import (_greedy_hooks,
                                                _make_run, _sample_hooks)

    from mpi_acx_tpu.models.moe_transformer import (MoeTransformerConfig,
                                                    _moe_ffn)
    from mpi_acx_tpu.models.speculative import _check_moe_target

    def fam(c):
        """One dispatch per family: (speculative ops, specs, shard fn,
        scale_specs for int8 weight-only companions)."""
        if type(c) is lm.LlamaConfig:
            return (_llama_tp_family_ops(c, tp, axis),
                    tp_param_specs_llama(axis), tp_shard_params_llama,
                    _llama_scale_specs(axis))
        if type(c) is MoeTransformerConfig:
            moe_ffn = _make_moe_ffn(c, tp, axis, ep_dispatch)
            return (_tp_family_ops(c, tp, axis, ffn=moe_ffn),
                    tp_param_specs_moe(axis), tp_shard_params,
                    _moe_scale_specs(axis))
        if type(c) is tfm.TransformerConfig:
            return (_tp_family_ops(c, tp, axis), tp_param_specs(axis),
                    tp_shard_params, _gpt2_scale_specs(axis))
        raise TypeError(
            "TP speculative decoding supports the GPT-2, Llama, and "
            f"MoE-transformer families; got {type(c).__name__}")

    assert draft_cfg.vocab == cfg.vocab, (draft_cfg.vocab, cfg.vocab)
    assert k >= 2, k
    assert ep_dispatch in ("auto", "sharded", "replicated"), ep_dispatch
    # An MoE TARGET must be drop-free so the k-wide verify window
    # routes exactly like plain decode (same rule as the
    # single-device speculative API).
    _check_moe_target(cfg)
    tp = mesh.shape[axis]
    t_ops, specs_t, shard_t, sspecs_t = fam(cfg)
    d_ops, specs_d, shard_d, sspecs_d = fam(draft_cfg)
    hooks = (_greedy_hooks(k) if temperature == 0.0
             else _sample_hooks(k, float(temperature)))

    def per_shard(dparams, params, prompt, key):
        B, S = prompt.shape        # static at trace time
        run = _make_run(draft_cfg, cfg, S, n_new, k, *hooks,
                        ops=(t_ops[0], t_ops[1], d_ops[0], d_ops[2]))
        if B == 1:
            return run(dparams, params, prompt, key)
        # Batched: vmap the single-sequence loop over rows INSIDE the
        # shard (the same lift as models.speculative._build_batched).
        # The per-layer psums batch elementwise across ranks, so each
        # row's replicated-logits invariant — and therefore its
        # independent pacing — survives the composition.
        toks, rounds, acc = jax.vmap(
            lambda row, kk: run(dparams, params, row[None], kk)
        )(prompt, jax.random.split(key, B))
        return toks[:, 0], rounds, acc

    run = _tp_program_cache(
        mesh, per_shard,
        [(specs_d, sspecs_d, shard_d, draft_cfg,
          "TP speculative draft"),
         (specs_t, sspecs_t, shard_t, cfg, "TP speculative target")],
        (P(), P()), (P(), P(), P()))

    def generate(draft_params, params, prompt, key):
        toks, rounds, acc = run(draft_params, params, prompt, key)
        return toks, {"rounds": rounds, "drafted_accepted": acc}

    return generate


# -- Tensor-parallel CONTINUOUS BATCHING (models/serving.py contract) ------


def make_tp_server_fns(params, cfg, mesh: Mesh, chunk: int = 1,
                       axis: str = "tp", family: str = "gpt2",
                       ep_dispatch: str = "auto",
                       kv_int8: bool = False):
    """Server-fns tuple for models.serving._serve whose three programs
    run tensor-parallel over the mesh: continuous batching composes
    with the Megatron weight split. Each slot's KV cache shards by
    attention head (the same [L, B, H, D, max_len] layout with H on
    ``axis``); per-slot positions ride the shared decode scaffold's
    vector-pos mode unchanged, so outputs equal the single-device
    serve_greedy's token for token up to the matmul split's summation
    reorder (exact in f32; bf16 can flip near-tied argmaxes — the same
    caveat as every TP-vs-single-device comparison here, see
    tests/test_tp_inference.py), while every decode step streams 1/tp
    of the weights per rank.

    ``family``: "gpt2" (dense), "moe" (GPT-2 attention + the routed
    expert FFN through _tp_family_ops' ffn hook; each rank hosts
    n_experts/tp experts, ``ep_dispatch`` as make_tp_generate_moe —
    "auto" gives batch-serving decode the sharded all_to_all path and
    falls back per call site when the token count doesn't divide tp),
    or "llama" (GQA: slots hold the un-repeated KV-head-group cache,
    sharded by group). Greedy. ``kv_int8`` serves from int8 slot
    caches (gpt2/llama): each rank quantizes its own head slice on
    write and the shared scale-on-scores read keeps the codes as the
    attention operands — the long-context composition where cache
    bytes dominate even after the 1/tp weight split. Use::

        fns = make_tp_server_fns(params, cfg, mesh, chunk=8)
        outs = serving.serve_greedy(params, cfg, prompts, n_new,
                                    n_slots, max_len, family=tfm,
                                    chunk=8, server_fns=fns)

    int8 WEIGHT checkpoints work (wread + the sharded scale
    companions, exactly as make_tp_generate). The weight tree is
    re-laid-out and sharded ONCE here — the serve loop dispatches
    step programs every chunk, and re-sharding the full tree per
    dispatch (the one-shot generate builders' pattern) would double
    weight traffic in the latency-bound hot loop.
    """
    tp = mesh.shape[axis]
    # Reuse the speculative core's per-shard family ops — prefill with
    # a traced last_index, decode with vector pos — so the TP layer
    # wiring lives once per family (_tp_family_ops /
    # _llama_tp_family_ops), not per builder.
    if family == "gpt2":
        ops_prefill, _, ops_decode = _tp_family_ops(cfg, tp, axis,
                                                    kv_int8=kv_int8)
        specs = tp_param_specs(axis)
        scale_specs = _gpt2_scale_specs(axis)
        shard_fn = tp_shard_params
    elif family == "moe":
        if kv_int8:
            raise ValueError(
                "int8 KV slot caches: gpt2/llama only for now")
        moe_ffn = _make_moe_ffn(cfg, tp, axis, ep_dispatch)
        ops_prefill, _, ops_decode = _tp_family_ops(cfg, tp, axis,
                                                    ffn=moe_ffn)
        specs = tp_param_specs_moe(axis)
        scale_specs = _moe_scale_specs(axis)
        shard_fn = tp_shard_params_moe
    elif family == "llama":
        ops_prefill, _, ops_decode = _llama_tp_family_ops(
            cfg, tp, axis, kv_int8=kv_int8)
        specs = tp_param_specs_llama(axis)
        scale_specs = _llama_scale_specs(axis)
        shard_fn = tp_shard_params_llama
    else:
        raise ValueError(f"unknown family {family!r}")
    cspec = P(None, None, axis, None, None)     # [L, B, H, D, max_len]
    cache_spec = {"k": cspec, "v": cspec, "pos": P()}
    if kv_int8:
        cache_spec.update(ks=cspec, vs=cspec)   # scales shard by head

    # Pre-shard the weights eagerly (once per server, not per call).
    sspecs = _specs_with_scales(specs, _scale_keys(params), scale_specs,
                                "TP serving")
    shardings = jax.tree.map(
        lambda sp: jax.sharding.NamedSharding(mesh, sp), sspecs,
        is_leaf=lambda x: isinstance(x, P))
    sharded = jax.jit(lambda p: shard_fn(p, cfg),
                      out_shardings=shardings)(params)

    def per_shard_prefill(params, tokens, last):
        # The 'one' cache is bucket-length: the scatter lands rows
        # [0, S_bucket) into the slot (serving.scatter_fn contract);
        # its pos entry is dropped (the scatter sets the slot's).
        logits, cache = ops_prefill(params, cfg, tokens,
                                    cap=tokens.shape[1],
                                    last_index=last)
        cache.pop("pos")
        return logits, cache

    one_spec = dict(cache_spec)
    one_spec.pop("pos")
    prefill_prog = jax.jit(shard_map(
        per_shard_prefill, mesh=mesh, in_specs=(sspecs, P(), P()),
        out_specs=(P(), one_spec), check_vma=False))

    def per_shard_step(params, cache, tok):
        def one(carry, _):
            cache, tok = carry
            logits, cache = ops_decode(params, cfg, cache, tok)
            nxt = jnp.argmax(logits, axis=-1).astype(tok.dtype)
            return (cache, nxt), nxt

        (cache, _), toks = lax.scan(one, (cache, tok), None,
                                    length=chunk)
        return cache, toks

    # Donate the slot caches: the host loop always proceeds with the
    # returned slots, and a non-donated [L, B, H, D, max_len] pair
    # would cost a full-cache copy per chunk on top of doubled peak
    # memory.
    step_prog = jax.jit(shard_map(
        per_shard_step, mesh=mesh,
        in_specs=(sspecs, cache_spec, P()),
        out_specs=(cache_spec, P()), check_vma=False),
        donate_argnums=(1,))

    def per_shard_scatter(slots, one, slot_idx, new_pos):
        def land(cache, src):
            dst = lax.dynamic_index_in_dim(cache, slot_idx, 1,
                                           keepdims=False)
            dst = lax.dynamic_update_slice(
                dst, src[:, 0], (0,) * dst.ndim)
            return lax.dynamic_update_index_in_dim(cache, dst,
                                                   slot_idx, 1)
        out = {k: land(slots[k], one[k]) for k in one}
        out["pos"] = slots["pos"].at[slot_idx].set(new_pos)
        return out

    scatter_prog = jax.jit(shard_map(
        per_shard_scatter, mesh=mesh,
        in_specs=(cache_spec, one_spec, P(), P()),
        out_specs=cache_spec, check_vma=False),
        donate_argnums=(0,))

    def prefill_fn(tokens, last):
        return prefill_prog(sharded, tokens, last)

    # The weight tree as the server holds it, for inspection (which
    # device holds which shard — chip_smoke.py --chips 4 prints it).
    prefill_fn.sharded_params = sharded

    def step_fn(slots, tok, keys):
        slots, toks = step_prog(sharded, slots, tok)
        return slots, toks, keys

    def scatter_fn(slots, one, slot_idx, new_pos):
        return scatter_prog(slots, one, slot_idx, new_pos)

    return prefill_fn, step_fn, scatter_fn, chunk, kv_int8, None
