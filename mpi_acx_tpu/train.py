"""Distributed training step: dp x pp x tp/sp in one shard_map program.

The flagship composition of the framework's primitives (the counterpart of
the reference's pipeline-exchange driver configs):

* **pp** — pipeline stages over the 'pp' mesh axis; microbatch activations
  travel stage->stage by collective permute
  (mpi_acx_tpu.parallel.pipeline). At a 'pp' axis of ONE (GPipe, no
  virtual stages) nothing travels: the stage is called once a
  micro-batch, with no scan over ticks and no permute.
* **tp + sp** — inside each stage, attention runs sequence-parallel over
  the 'tp' axis with ring attention (K/V rotating on ICI), and the MLP
  runs tensor-parallel with the FFN dim sharded over 'tp' and one psum.
  At a 'tp' axis of ONE (a one-device mesh, or dp/pp alone) nothing
  rotates and nothing merges: ``ring_attention_batched`` is then one
  direct flash (or dense) attention call in the layer body, and the
  block's sequence slice, all_gather and psum compile to nothing.
* **dp** — the microbatch dim is sharded over 'dp'; gradients are averaged
  with one pmean.

Everything is a single jitted SPMD program: XLA sees the mesh, the
collectives, and the scan — no host in the loop.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.parallel.pipeline import (pipeline_forward,
                                           pipeline_forward_interleaved)
from mpi_acx_tpu.parallel.ring_attention import ring_attention_batched


def _gpt2_attn_sp(cfg, lp: Dict[str, Any], h: jax.Array,
                  tp_axis: str) -> jax.Array:
    """The GPT-2-layout attention half under sequence parallelism: each
    tp rank projects q/k/v for ITS sequence block, ring attention rotates
    K/V blocks on ICI, and the outputs are re-assembled with one
    all_gather. Shared by the dense and MoE families (same ln1/wqkv/wo
    leaf names)."""
    tpn = lax.axis_size(tp_axis)
    ti = lax.axis_index(tp_axis)
    mb, S, d = h.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    blk = S // tpn

    hn = tfm.layernorm(h, lp["ln1_g"], lp["ln1_b"])
    loc = lax.dynamic_slice_in_dim(hn, ti * blk, blk, axis=1)  # [mb,blk,d]
    qkv = loc @ lp["wqkv"].astype(h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(mb, blk, H, Dh)
    k = k.reshape(mb, blk, H, Dh)
    v = v.reshape(mb, blk, H, Dh)
    o = ring_attention_batched(q, k, v, tp_axis, causal=True,
                               use_flash=cfg.use_flash).reshape(mb, blk, d)
    o = o @ lp["wo"].astype(h.dtype)
    # Re-assemble the full sequence on every tp rank.
    attn = lax.all_gather(o, tp_axis, axis=1, tiled=True)     # [mb, S, d]
    return h + attn


def _block_sp_tp(cfg: tfm.TransformerConfig, lp: Dict[str, Any],
                 h: jax.Array, tp_axis: str) -> jax.Array:
    """Transformer block, sequence-parallel attention + tensor-parallel MLP.

    h: [mb, S, d] replicated over tp. lp's w1/b1/w2 are the LOCAL tp slices
    (shard_map hands us [d, ff/tp] etc.); wqkv/wo are replicated.
    """
    h = _gpt2_attn_sp(cfg, lp, h, tp_axis)

    # --- MLP: shard the FFN dim over tp; one psum to reduce ---
    hn = tfm.layernorm(h, lp["ln2_g"], lp["ln2_b"])
    y = jax.nn.gelu(hn @ lp["w1"].astype(h.dtype) +
                    lp["b1"].astype(h.dtype))                 # [mb,S,ff/tp]
    part = y @ lp["w2"].astype(h.dtype)
    return h + lax.psum(part, tp_axis) + lp["b2"].astype(h.dtype)


def _moe_block_sp_tp(cfg, lp: Dict[str, Any], h: jax.Array,
                     tp_axis: str):
    """MoE-transformer block under the flagship composition: the GPT-2
    attention half (sequence-parallel ring attention), then the routed
    expert FFN with EXPERTS sharded over the tp axis (EP folded onto the
    tp mesh axis).

    Tokens are REPLICATED over tp here, so the replicated-EP path
    applies: each rank routes all tokens but runs only its LOCAL expert
    block, and one psum assembles the output — 1/tp the expert FLOPs
    per rank and a single collective per layer
    (moe.moe_layer_replicated_ep; routing is bit-equal to the
    single-device dispatch).

    Returns ``(h, (load_balance, router_z))`` — the router auxiliaries
    ride the pipeline scan's aux accumulator (pipeline_forward
    ``with_aux``) into the flagship loss, so pp x tp MoE training
    carries the same regularization as the dp(+ep) trainer
    (models/moe_transformer.py). The aux pair is replicated over tp
    (full gates on every rank); the loss gates its contribution to
    ti == 0 to keep cotangent paths exclusive."""
    from mpi_acx_tpu.models.moe_transformer import _moe_ffn

    h = _gpt2_attn_sp(cfg, lp, h, tp_axis)
    return _moe_ffn(cfg, lp, h, ep_axis=tp_axis, replicated=True,
                    with_aux=True)


def _llama_block_sp_tp(cfg, lp: Dict[str, Any], h: jax.Array,
                       tp_axis: str) -> jax.Array:
    """Llama block (RMSNorm + RoPE + GQA + SwiGLU), sequence-parallel
    attention + tensor-parallel MLP — the Llama-family counterpart of
    :func:`_block_sp_tp`.

    h: [mb, S, d] replicated over tp. lp's w_gate/w_up/w_down are the
    LOCAL tp slices of the SwiGLU FFN; attention weights are replicated.
    RoPE uses each rank's GLOBAL positions (ti*blk + arange), so the
    sharded rotation matches the single-device computation exactly.
    """
    from mpi_acx_tpu.models import llama as lm

    tpn = lax.axis_size(tp_axis)
    ti = lax.axis_index(tp_axis)
    mb, S, d = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    blk = S // tpn

    # --- attention: shard the SEQUENCE over tp; ring-attend K/V blocks ---
    hn = lm.rmsnorm(h, lp["attn_norm"])
    loc = lax.dynamic_slice_in_dim(hn, ti * blk, blk, axis=1)  # [mb,blk,d]
    q = (loc @ lp["wq"].astype(h.dtype)).reshape(mb, blk, Hq, Dh)
    k = (loc @ lp["wk"].astype(h.dtype)).reshape(mb, blk, Hkv, Dh)
    v = (loc @ lp["wv"].astype(h.dtype)).reshape(mb, blk, Hkv, Dh)
    positions = ti * blk + jnp.arange(blk)
    q = lm.rope(q, positions, cfg.rope_theta)
    k = lm.rope(k, positions, cfg.rope_theta)
    # K/V stay at Hkv heads: the ring rotates the un-expanded GQA heads
    # (Hq/Hkv x less ICI traffic) and broadcasts per block.
    o = ring_attention_batched(q, k, v, tp_axis, causal=True,
                               use_flash=cfg.use_flash,
                               kv_repeat=Hq // Hkv)
    o = o.reshape(mb, blk, Hq * Dh) @ lp["wo"].astype(h.dtype)
    attn = lax.all_gather(o, tp_axis, axis=1, tiled=True)     # [mb, S, d]
    h = h + attn

    # --- SwiGLU MLP: shard the FFN dim over tp; one psum to reduce ---
    hn = lm.rmsnorm(h, lp["mlp_norm"])
    gate = jax.nn.silu(hn @ lp["w_gate"].astype(h.dtype))     # [mb,S,ff/tp]
    up = hn @ lp["w_up"].astype(h.dtype)
    part = (gate * up) @ lp["w_down"].astype(h.dtype)
    return h + lax.psum(part, tp_axis)


def param_specs(stage: bool = True) -> Dict[str, Any]:
    """PartitionSpecs for the stage-sliced GPT-2 parameter pytree
    (tfm.stage_slice output): layers carry a leading 'pp' stage axis; the
    FFN dims of w1/b1/w2 shard over 'tp'; everything else replicates."""
    pp = "pp" if stage else None
    return {
        "embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
        "layers": {
            "ln1_g": P(pp), "ln1_b": P(pp),
            "wqkv": P(pp), "wo": P(pp),
            "ln2_g": P(pp), "ln2_b": P(pp),
            "w1": P(pp, None, None, "tp"), "b1": P(pp, None, "tp"),
            "w2": P(pp, None, "tp", None), "b2": P(pp),
        },
    }


def llama_param_specs(stage: bool = True) -> Dict[str, Any]:
    """PartitionSpecs for the stage-sliced Llama parameter pytree: the
    SwiGLU FFN dims shard over 'tp'; attention/norms replicate per stage."""
    pp = "pp" if stage else None
    return {
        "embed": P(), "final_norm": P(), "unembed": P(),
        "layers": {
            "attn_norm": P(pp), "wq": P(pp), "wk": P(pp), "wv": P(pp),
            "wo": P(pp), "mlp_norm": P(pp),
            "w_gate": P(pp, None, None, "tp"),
            "w_up": P(pp, None, None, "tp"),
            "w_down": P(pp, None, "tp", None),
        },
    }


def moe_param_specs(stage: bool = True) -> Dict[str, Any]:
    """PartitionSpecs for the stage-sliced MoE-transformer pytree: the
    EXPERT dim of w1/w2 shards over 'tp' (EP on the tp mesh axis);
    attention, norms, and the gate replicate per stage."""
    pp = "pp" if stage else None
    return {
        "embed": P(), "pos": P(), "lnf_g": P(), "lnf_b": P(),
        "layers": {
            "ln1_g": P(pp), "ln1_b": P(pp),
            "wqkv": P(pp), "wo": P(pp),
            "ln2_g": P(pp), "ln2_b": P(pp),
            "gate": P(pp),
            "w1": P(pp, None, "tp"), "w2": P(pp, None, "tp"),
        },
    }


class _Family:
    """Model-family adapter: everything make_loss_and_grads needs to run a
    family through the dp x pp x tp/sp composition."""

    def __init__(self, block, embed, final, head, specs, tp_sharded,
                 has_aux=False):
        self.block = block           # (cfg, lp, h, tp_axis) -> h | (h, aux)
        self.embed = embed           # (params, cfg, tokens) -> x [...,S,d]
        self.final = final           # (params, ys) -> ys
        self.head = head             # (params) -> [vocab, d] logits matrix
        self.specs = specs           # () -> PartitionSpec tree
        self.tp_sharded = tp_sharded  # layer-leaf name -> bool
        self.has_aux = has_aux       # block returns (h, (balance, z))


def _family(cfg) -> _Family:
    from mpi_acx_tpu.models.llama import LlamaConfig, rmsnorm
    from mpi_acx_tpu.models.moe_transformer import MoeTransformerConfig

    if isinstance(cfg, LlamaConfig):
        return _Family(
            block=_llama_block_sp_tp,
            embed=lambda p, c, t: p["embed"][t].astype(c.dtype),
            final=lambda p, ys: rmsnorm(ys, p["final_norm"]),
            head=lambda p: p["unembed"],
            specs=llama_param_specs,
            tp_sharded=lambda k: k in ("w_gate", "w_up", "w_down"),
        )
    if isinstance(cfg, MoeTransformerConfig):
        return _Family(
            block=_moe_block_sp_tp,
            embed=lambda p, c, t: (p["embed"][t] +
                                   p["pos"][:t.shape[-1]]).astype(c.dtype),
            final=lambda p, ys: tfm.layernorm(ys, p["lnf_g"], p["lnf_b"]),
            head=lambda p: p["embed"],
            specs=moe_param_specs,
            tp_sharded=lambda k: k in ("w1", "w2"),
            has_aux=True,
        )
    return _Family(
        block=_block_sp_tp,
        embed=lambda p, c, t: (p["embed"][t] +
                               p["pos"][:t.shape[-1]]).astype(c.dtype),
        final=lambda p, ys: tfm.layernorm(ys, p["lnf_g"], p["lnf_b"]),
        head=lambda p: p["embed"],
        specs=param_specs,
        tp_sharded=lambda k: k in ("w1", "b1", "w2"),
    )


def make_loss_and_grads(cfg, mesh: Mesh, n_micro: int, n_virtual: int = 1,
                        remat: bool = False,
                        dp_quant_bits: int | None = None,
                        aux_weight: float = 1e-2, z_weight: float = 1e-3,
                        schedule: str = "gpipe",
                        xent_chunk: int | None = None):
    """Builds a jitted (params, tokens, targets) -> (loss, grads) over a
    ('dp','pp','tp') mesh — the shard_map core every optimizer shares.
    Returned grads carry the same shardings as params, so any elementwise
    optimizer applied outside stays correctly sharded by propagation.

    cfg selects the model family (tfm.TransformerConfig or
    llama.LlamaConfig — both run the same composition through their
    _Family adapter). params must be tfm.stage_slice(init_params(...),
    pp_size) — or tfm.stage_slice_interleaved(..., pp_size, n_virtual)
    when ``n_virtual > 1`` selects the interleaved pipeline schedule
    (bubble / n_virtual; needs n_micro % pp == 0). tokens/targets:
    [n_micro, micro_batch, S] int32, batch over 'dp'.

    ``remat=True`` wraps each layer body in ``jax.checkpoint`` and keeps
    a layer's INPUT and its ATTENTION KERNEL'S OUTPUT: the backward pass
    recomputes the block's activations (layer norms, q/k/v, the MLP; at
    ``tp >= 2`` the ring attention and its collectives too) instead of
    keeping them live through the whole pipeline scan, but where the
    layer calls the flash kernel directly (``tp = 1`` on the flash path)
    the kernel's ``o`` and ``lse`` are saved by name
    (``ops.attention.FLASH_RESIDUALS``) and the backward runs no second
    attention forward. That costs ``n_layer x n_micro x (B*S*d*2 +
    B*H*S*4)`` bytes a stage (B a micro-batch) beside the layer inputs'
    ``n_layer x n_micro x B*S*d*2``; a ring of two or more and the dense
    path name nothing and are recomputed whole. The recompute is one
    more forward of the blocks less that kernel: about a quarter more
    FLOPs than no remat (a third where nothing is kept), the standard
    trade when HBM, not the MXU, is the binding constraint. Gradients
    are the same function, so the exact-match tests hold with remat on
    (tests/test_train.py).

    ``dp_quant_bits=8`` replaces the exact dp-gradient pmean with the
    int8-quantized ring all-reduce (parallel/quantized.py, after EQuARX)
    — ~4x less traffic on the dp axis, the one that rides DCN in
    multi-slice layouts, at ~<1% gradient error. None (default) keeps
    gradient sync exact.

    For the MoE family the loss is CE + ``aux_weight`` * load-balance +
    ``z_weight`` * router-z, with the router auxiliaries threaded
    through the pipeline scan (pipeline_forward ``with_aux``) and
    normalized per (layer, microbatch) router call — at pp=tp=1,
    n_micro=1 the scalar exact-matches the dp+ep trainer's
    moe_transformer.loss_fn (tests/test_train_moe_flagship.py). The
    weights are ignored by the dense families.

    ``schedule="1f1b"`` swaps the autodiff-through-the-scan backward for
    the memory-bounded 1F1B schedule (pipeline._pipeline_1f1b_engine):
    one slot scan whose body runs the stage forward and an explicit
    ``jax.vjp`` backward from an interval-colored input buffer, so peak
    activation residency is O(pp) (O(n_virtual * pp) interleaved)
    instead of O(n_micro) scan residuals. Same loss and gradients as
    the GPipe path (tests/test_train_1f1b.py asserts exact parity at
    dp2 x pp2 x tp2 for all three families). Because every rank must
    execute the stage collectives in lockstep, the slot body computes
    both the forward and the backward unconditionally and masks the
    accumulations (~2x the op count of the cond-based pipeline-level
    schedule; the win is memory, not FLOPs). ``n_virtual > 1`` composes
    1F1B with the interleaved schedule — memory win AND the bubble/v
    win together (Megatron interleaved 1F1B; needs n_micro % pp == 0).
    """
    n_stages = mesh.shape["pp"]
    fam = _family(cfg)
    from mpi_acx_tpu.models.moe_transformer import MoeTransformerConfig
    if isinstance(cfg, MoeTransformerConfig):
        assert cfg.n_experts % mesh.shape["tp"] == 0, (
            f"n_experts ({cfg.n_experts}) must divide by the 'tp' mesh "
            f"axis ({mesh.shape['tp']}) — experts shard over tp")
    assert schedule in ("gpipe", "1f1b"), schedule

    def ll_sum(head_mat, ys_blk, tg_blk):
        """Summed target log-likelihood of a rank's exclusive slice.
        ``xent_chunk`` selects the memory-bounded chunked-vocab path
        (ops/xent.py — the [tokens, vocab] logits tensor never
        materializes; identical values/grads up to fp summation order),
        None the naive log_softmax."""
        if xent_chunk is None:
            logits = ys_blk.astype(jnp.float32) @ head_mat.T
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(logp, tg_blk[..., None], -1)[..., 0]
            return jnp.sum(ll)
        from mpi_acx_tpu.ops.xent import chunked_xent_ll
        d = ys_blk.shape[-1]
        return jnp.sum(chunked_xent_ll(
            ys_blk.reshape(-1, d), head_mat, tg_blk.reshape(-1),
            xent_chunk))

    def reduce_grad(g, tp_sharded: bool, pp_sharded: bool):
        """Gradient reduction rule shared by both schedules: pmean over
        dp (mean loss over the global batch), psum over every axis the
        leaf is REPLICATED on, nothing over sharded axes."""
        if dp_quant_bits is not None:
            from mpi_acx_tpu.parallel.quantized import quantized_pmean
            g = quantized_pmean(g, "dp", dp_quant_bits)
        else:
            g = lax.pmean(g, "dp")
        if not tp_sharded:
            g = lax.psum(g, "tp")
        if not pp_sharded:
            g = lax.psum(g, "pp")
        return g

    def make_stage_fn():
        layer_fn = lambda lp, h: fam.block(cfg, lp, h, "tp")  # noqa: E731
        if remat:
            # Keeps what only the attention kernel can produce (the
            # direct flash call's o and lse, where the layer has one);
            # a layer with no such name is recomputed whole.
            from mpi_acx_tpu.ops.attention import FLASH_RESIDUALS
            layer_fn = jax.checkpoint(
                layer_fn, policy=jax.checkpoint_policies
                .save_only_these_names(*FLASH_RESIDUALS))
        if fam.has_aux:
            def stage_fn(stage_layers, h):
                def body(carry, lp):
                    h, lb, rz = carry
                    h, (b_lb, b_rz) = layer_fn(lp, h)
                    return (h, lb + b_lb, rz + b_rz), None
                zero = jnp.zeros((), jnp.float32)
                (h, lb, rz), _ = lax.scan(body, (h, zero, zero),
                                          stage_layers)
                return h, (lb, rz)
        else:
            def stage_fn(stage_layers, h):
                def body(h, lp):
                    return layer_fn(lp, h), None
                h, _ = lax.scan(body, h, stage_layers)
                return h
        return stage_fn

    def per_shard(params, tokens, targets):
        def loss_fn(params):
            # Embed on every rank (dp-local microbatches). The pipeline
            # consumes xs only on stage 0, so the embedding-gather cotangent
            # path is exclusive to stage 0 by construction.
            S = tokens.shape[-1]
            x = fam.embed(params, cfg, tokens)         # [M, mbl, S, d]
            stage_fn = make_stage_fn()

            aux = None
            if n_virtual > 1:
                ys = pipeline_forward_interleaved(
                    stage_fn, params["layers"], x, "pp", n_virtual,
                    with_aux=fam.has_aux)
            elif n_stages == 1:
                # One stage hands nothing on: the stage once a
                # micro-batch in the trace, no scan over ticks. Each
                # micro-batch's residuals stay the layer scan's own
                # arrays; stacked by a scan they cost a copy of a
                # micro-batch's slice each way and put the remat step
                # over the TPU compiler's memory budget (PERF.md, PR 49).
                stage_layers = jax.tree.map(lambda p: p[0], params["layers"])
                outs = [stage_fn(stage_layers, x[m])
                        for m in range(x.shape[0])]
                if fam.has_aux:
                    ys = (jnp.stack([y for y, _ in outs]),
                          jax.tree.map(lambda *a: sum(a),
                                       *[a for _, a in outs]))
                else:
                    ys = jnp.stack(outs)
            else:
                ys = pipeline_forward(stage_fn, params["layers"], x, "pp",
                                      with_aux=fam.has_aux)
            if fam.has_aux:
                ys, aux = ys
            ys = fam.final(params, ys)

            # EXCLUSIVE loss paths: every rank scores only its own slice —
            # its tp sequence block, and only on the last pipeline stage —
            # and the scalar is assembled by psum. This keeps every
            # parameter's cotangent path unique, so gradient reduction is a
            # plain psum over the axes a leaf is replicated on (redundant
            # loss computation would scale cotangents by the redundancy).
            tpn = lax.axis_size("tp")
            ti = lax.axis_index("tp")
            si = lax.axis_index("pp")
            blk = S // tpn
            ys_blk = lax.dynamic_slice_in_dim(ys, ti * blk, blk, axis=2)
            tg_blk = lax.dynamic_slice_in_dim(targets, ti * blk, blk, axis=2)
            contrib = jnp.where(si == n_stages - 1,
                                ll_sum(fam.head(params), ys_blk, tg_blk),
                                0.0)
            if fam.has_aux:
                # Aux is replicated over tp (full gates everywhere) and
                # device-varying over pp (each stage owns its layers):
                # gate to ti == 0 for an exclusive cotangent path, then
                # the same psum that assembles the CE sums every stage's
                # contribution exactly once. Normalize per router call —
                # one call per (layer, microbatch) — to match the dp+ep
                # trainer's mean-over-layers convention.
                lb_c = jnp.where(ti == 0, aux[0], 0.0)
                rz_c = jnp.where(ti == 0, aux[1], 0.0)
                total, lb_t, rz_t = lax.psum((contrib, lb_c, rz_c),
                                             ("pp", "tp"))
                calls = cfg.n_layers * tokens.shape[0]
                aux_term = (aux_weight * lb_t + z_weight * rz_t) / calls
            else:
                total = lax.psum(contrib, ("pp", "tp"))
                aux_term = 0.0
            n_tok = tokens.shape[0] * tokens.shape[1] * S
            return -total / n_tok + aux_term

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # With check_vma=False the transpose of psum is psum (replication is
        # untracked), so the loss-assembly psum over ('pp','tp') all-reduces
        # the per-rank unit seeds: every cotangent — and thus every gradient
        # leaf — is uniformly scaled by pp*tp. Undo it explicitly.
        group = lax.axis_size("pp") * lax.axis_size("tp")
        grads = jax.tree.map(lambda g: g / group, grads)
        loss = lax.pmean(loss, "dp")

        # Gradient reduction rule: see reduce_grad ('tp' psum for
        # attention/norm leaves, 'pp'+'tp' for the embedding family; no
        # reduction over axes the leaf is sharded on).
        out = dict(grads)
        for k in grads:
            if k != "layers":
                out[k] = reduce_grad(grads[k], False, False)
        out["layers"] = {
            k: reduce_grad(grads["layers"][k], fam.tp_sharded(k), True)
            for k in grads["layers"]
        }
        return loss, out

    def per_shard_1f1b(params, tokens, targets):
        """The 1F1B counterpart of per_shard: a thin adapter over
        pipeline._pipeline_1f1b_engine (the slot scan, timetable, and
        ring buffers live THERE, once — round-4 verdict item #5). This
        wires in the flagship specifics: ``lockstep=True`` because the
        stage body contains tp collectives (every rank computes every
        slot and masks accumulations), the tail (final-norm + head)
        loss vjp, the embedding vjp at global stage 0, and the MoE
        router-aux seeds gated to ti == 0 (exclusive-path rule).
        ``n_virtual > 1`` runs the interleaved 1F1B schedule."""
        from mpi_acx_tpu.parallel.pipeline import _pipeline_1f1b_engine
        M, mbl, S = tokens.shape
        tpn = lax.axis_size("tp")
        ti = lax.axis_index("tp")
        blk = S // tpn
        n_tok = M * mbl * S
        calls = cfg.n_layers * M

        slayers = jax.tree.map(lambda p: p[0], params["layers"])
        if n_virtual == 1:
            slayers = jax.tree.map(lambda p: p[None], slayers)  # chunk axis
        tail = {k: v for k, v in params.items() if k != "layers"}
        zero_tail = jax.tree.map(jnp.zeros_like, tail)
        stage_fn = make_stage_fn()

        # fam.embed/final/head only read the tail leaves; hand them a
        # params dict without the layer stack (its layout differs
        # between the chunked and flat cases and is never touched).
        def with_tail(tailp):
            return dict(tailp, layers=None)

        x_all = fam.embed(params, cfg, tokens)     # [M, mbl, S, d]

        def tail_ll(tailp, y, tgt_m):
            # This rank's EXCLUSIVE loss share for one microbatch: the
            # local tp sequence slice, collective-free (assembly is one
            # psum of the accumulated scalars after the scan).
            full = with_tail(tailp)
            ys = fam.final(full, y)
            ys_blk = lax.dynamic_slice_in_dim(ys, ti * blk, blk, axis=1)
            tg_blk = lax.dynamic_slice_in_dim(tgt_m, ti * blk, blk,
                                              axis=1)
            return ll_sum(fam.head(full), ys_blk, tg_blk)

        def loss_side(y_, m):
            tgt_m = lax.dynamic_index_in_dim(targets, m, 0,
                                             keepdims=False)
            llsum, tail_vjp = jax.vjp(
                lambda tp_, yy: tail_ll(tp_, yy, tgt_m), tail, y_)
            d_tail, dy = tail_vjp(
                jnp.asarray(-1.0 / n_tok, llsum.dtype))
            return llsum, d_tail, dy.astype(y_.dtype)

        def embed_side(dx_, m):
            tok_m = lax.dynamic_index_in_dim(tokens, m, 0,
                                             keepdims=False)
            _, embed_vjp = jax.vjp(
                lambda tp_: fam.embed(with_tail(tp_), cfg, tok_m), tail)
            (d,) = embed_vjp(dx_.astype(x_all.dtype))
            return d

        if fam.has_aux:
            gate = (ti == 0).astype(jnp.float32)
            aux_seed = (aux_weight / calls * gate,
                        z_weight / calls * gate)
            aux_gate = ti == 0
        else:
            aux_seed = aux_gate = None

        lacc, aux_acc, gl, gt = _pipeline_1f1b_engine(
            stage_fn, slayers, x_all, "pp", n_virtual,
            loss_side=loss_side, zero_head=zero_tail,
            embed_side=embed_side, aux_seed=aux_seed,
            aux_gate=aux_gate, lockstep=True)

        if fam.has_aux:
            total_ll, lb_t, rz_t = lax.psum(
                (lacc, aux_acc[0], aux_acc[1]), ("pp", "tp"))
        else:
            total_ll = lax.psum(lacc, ("pp", "tp"))
        loss = -total_ll / n_tok
        if fam.has_aux:
            loss = loss + (aux_weight * lb_t + z_weight * rz_t) / calls
        loss = lax.pmean(loss, "dp")

        if n_virtual == 1:
            gl = jax.tree.map(lambda g: g[0], gl)  # drop chunk axis
        # These are TRUE local grads (manual vjp with exclusive seeds —
        # no autodiff loss-assembly psum to undo); reduce directly.
        out = {k: reduce_grad(gt[k], False, False) for k in gt}
        out["layers"] = {
            k: reduce_grad(gl[k][None], fam.tp_sharded(k), True)
            for k in gl
        }
        return loss, out

    specs = fam.specs()
    if n_virtual > 1:
        # Layer leaves gain a chunk axis after 'pp': P(pp, *r) -> P(pp,None,*r).
        specs = dict(specs)
        specs["layers"] = {
            k: P(*((s[0], None) + tuple(s[1:])))
            for k, s in specs["layers"].items()
        }
    data_spec = P(None, "dp")
    body = per_shard_1f1b if schedule == "1f1b" else per_shard
    fn = shard_map(body, mesh=mesh,
                   in_specs=(specs, data_spec, data_spec),
                   out_specs=(P(), specs),
                   check_vma=False)
    return jax.jit(fn), n_stages


def make_train_step(cfg: tfm.TransformerConfig, mesh: Mesh,
                    n_micro: int, lr: float = 1e-2, n_virtual: int = 1,
                    remat: bool = False, dp_quant_bits: int | None = None,
                    aux_weight: float = 1e-2, z_weight: float = 1e-3,
                    schedule: str = "gpipe",
                    xent_chunk: int | None = None):
    """Jitted (params, tokens, targets) -> (loss, new_params) SGD step
    (stateless optimizer; for stateful ones use make_train_step_optax)."""
    grad_fn, n_stages = make_loss_and_grads(cfg, mesh, n_micro,
                                            n_virtual=n_virtual,
                                            remat=remat,
                                            dp_quant_bits=dp_quant_bits,
                                            aux_weight=aux_weight,
                                            z_weight=z_weight,
                                            schedule=schedule,
                                            xent_chunk=xent_chunk)

    @jax.jit
    def step(params, tokens, targets):
        loss, grads = grad_fn(params, tokens, targets)
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return loss, new

    return step, n_stages


def make_train_step_optax(cfg: tfm.TransformerConfig, mesh: Mesh,
                          n_micro: int, optimizer, n_virtual: int = 1,
                          remat: bool = False,
                          dp_quant_bits: int | None = None,
                          aux_weight: float = 1e-2, z_weight: float = 1e-3,
                          schedule: str = "gpipe",
                          xent_chunk: int | None = None):
    """Distributed train step with any optax GradientTransformation.

    Returns (step, n_stages): step(params, opt_state, tokens, targets) ->
    (loss, new_params, new_opt_state). Initialize opt_state with
    ``optimizer.init(params)`` — its leaves mirror the parameter tree, so
    XLA's sharding propagation keeps optimizer moments sharded exactly
    like their parameters (pp-staged, tp-split FFN slices included), and
    the whole state checkpoints through mpi_acx_tpu.checkpoint.
    """
    import optax

    grad_fn, n_stages = make_loss_and_grads(cfg, mesh, n_micro,
                                            n_virtual=n_virtual,
                                            remat=remat,
                                            dp_quant_bits=dp_quant_bits,
                                            aux_weight=aux_weight,
                                            z_weight=z_weight,
                                            schedule=schedule,
                                            xent_chunk=xent_chunk)

    @jax.jit
    def step(params, opt_state, tokens, targets):
        loss, grads = grad_fn(params, tokens, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return loss, params, opt_state

    return step, n_stages
