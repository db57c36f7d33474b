"""Nemotron-H family (``model_type`` ``nemotron_h``: NVIDIA-Nemotron-3-
Super-120B-A12B), pure functional JAX: every layer is ONE mixer with its
own norm and residual, ``x <- x + mixer(RMSNorm(x))``, and which one the
config's ``pattern`` says a layer (``hybrid_override_pattern``):

* ``M``, Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(conv1d(xBC;
  depthwise, ``conv_kernel`` taps, bias))``, split ``x`` (``mamba_heads``
  heads of ``mamba_head_dim``), ``B``, ``C`` (``n_groups`` groups of
  ``ssm_state``); ``dt <- softplus(dt + dt_bias)``; ``a = -exp(A_log)`` a
  head; the state-space recurrence (``ops/ssd.py``: the decode step's
  update of a ``[heads, head_dim, state]`` float32 state a slot, the
  prefill's chunked scan); ``+ D x``; ``y <- RMSNorm_grouped(y * silu(z))
  * w`` over ``n_groups`` groups; ``W_out``. What a token needs of the
  past is that state and the conv's last ``conv_kernel - 1`` inputs: 4.25
  MB a slot a layer at the published widths.
* ``*``, attention: q as ``n_heads`` heads, k and v as ``n_kv_heads`` of
  ``head_dim``, causal softmax of ``q k^T / sqrt(head_dim)``, an output
  projection. NO positional term of any kind (HF's ``nemotron_h``
  attention applies none). Jamba's functions, by the same leaf names.
* ``E``, a latent mixture of experts: ``s = sigmoid(x W_g)`` in float32
  over ``n_experts``, the ``top_k`` of ``s + bias`` chosen (one routing
  group: no group limit), weighted by ``s`` normalised times
  ``routed_scaling_factor``; ``u = x W_dn`` into a latent of
  ``moe_latent``; ``r = sum_i w_i W2_i relu(W1_i u)^2`` over the chosen
  experts HELD here (two matrices an expert, no gate:
  ``moe.sorted_expert_ffn(w3=None)``); ``out = r W_up + W2_s relu(W1_s
  x)^2``, the shared expert at the model's width beside it. The
  up-projection is linear, so four chips' parts of ``r`` add up through
  it and the shared expert is counted once.

A final RMSNorm, an untied head. Precision: weights and matmuls in
``cfg.dtype`` (bfloat16) with float32 accumulation; norms, the router,
softplus, ``exp``, the conv's sum, the recurrence and the carried state
in float32; the conv window and the conv's output (``x``, ``B``, ``C``
as the recurrence reads them) in ``cfg.dtype``; ``A_log``, ``D``,
``dt_bias``, the router's ``gate`` and ``bias`` float32.

Leaves of a layer (``params["seg<i>"]``, stacked by stretch as
``kvpage.compress_layers`` groups the layers): ``norm1`` [d] in every
kind; ``M``: ``w_in`` [d, 2 C + 2 G N + H] (columns z | x | B | C | dt,
C = H P), ``conv_w`` [taps, C + 2 G N], ``conv_b``, ``dt_bias``,
``A_log``, ``D`` [H], ``mix_norm`` [C], ``w_out`` [C, d]; ``*``: ``wq``
[d, Hq Dh], ``wk``, ``wv`` [d, Hkv Dh], ``wo`` [Hq Dh, d]; ``E``:
``gate`` [d, E], ``bias`` [E], ``w_dn`` [d, latent], ``w1`` [held,
latent, f], ``w2`` [held, f, latent], ``w_up`` [latent, d], ``ws1`` [d,
fs], ``ws2`` [fs, d].
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.models import kvpage, moe
from mpi_acx_tpu.models.jamba import (attention_out, attention_qkv,
                                      conv_taps, conv_window, window_cuts)
from mpi_acx_tpu.models.llama import rmsnorm
from mpi_acx_tpu.ops import ssd

F32 = jnp.float32
# the published stack: 88 layers, periods of (5 M, 5 E, 1 *) but for two
_PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
              "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab: int = 131072
    d_model: int = 4096
    pattern: str = _PUBLISHED
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    n_experts: int = 512
    top_k: int = 22
    moe_latent: int = 1024
    moe_d_ff: int = 2688             # one expert's width, in the latent
    shared_d_ff: int = 5376          # the shared expert's, at d_model
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    norm_eps: float = 1e-5
    max_seq: int = 262144
    # The experts held HERE: ``experts_held`` of them from
    # ``experts_first`` (None: all). The router keeps its width.
    experts_first: int = 0
    experts_held: Optional[int] = None
    # Tokens an expert layer takes at a time in a whole-sequence pass:
    # the sorted pairs' copies are ``top_k`` rows a token, held or not.
    moe_block: int = 1024
    # Paged serving: which whole prompt pages keep a snapshot of the
    # Mamba layers' state (kvpage.PagedSpec.snapshot_every).
    snapshot_every: int = 4
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None     # prefill attention; None = auto
    decode_flash: Optional[bool] = None  # paged decode kernels; None = auto
    ssm_kernel: Optional[bool] = None    # ops/ssd.py's calls; None = auto

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """What the conv runs over: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state


def nemotron3_super_120b() -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B as published (88 layers)."""
    return NemotronHConfig()


def tiny_nemotron(**over) -> NemotronHConfig:
    """Small config for tests, every mechanism present: the published
    period's order ``MEMEMEM*EME`` (5 M, 5 E, 1 *), 4 Mamba heads of 8
    in 2 groups with a state of 16, chunks of 8, 4 query heads on 2 K/V
    heads, 8 experts top 3 in a latent of 16 beside a shared expert, a
    snapshot every second page."""
    base = dict(vocab=96, d_model=32, pattern="MEMEMEM*EME", n_heads=4,
                n_kv_heads=2, head_dim=8, mamba_heads=4, mamba_head_dim=8,
                ssm_state=16, n_groups=2, chunk_size=8, n_experts=8,
                top_k=3, moe_latent=16, moe_d_ff=24, shared_d_ff=40,
                max_seq=256, moe_block=16, snapshot_every=2)
    base.update(over)
    return NemotronHConfig(**base)


Params = Dict[str, Any]
_F32_LEAVES = ("A_log", "D", "dt_bias", "gate", "bias")
_EXPERT_STACKS = ("w1", "w2")          # of a routed FFN: never sliced
_KINDS = {
    "M": kvpage.LayerKind(operator="mamba2", ffn="none", cache="state"),
    "*": kvpage.LayerKind(operator="attention", ffn="none", cache="pages"),
    "E": kvpage.LayerKind(operator="none", ffn="moe", cache="none"),
}


def layer_kinds(cfg: NemotronHConfig) -> Tuple[kvpage.LayerKind, ...]:
    return tuple(_KINDS[ch] for ch in cfg.pattern)


def segments(cfg: NemotronHConfig) -> Tuple[kvpage.Segment, ...]:
    return kvpage.compress_layers(layer_kinds(cfg))


def leaf_shapes(cfg: NemotronHConfig, kind: kvpage.LayerKind
                ) -> Dict[str, tuple]:
    """One layer's leaves: name -> (shape, init: None = ones, "A" =
    ``log(uniform[1, 16])``, "dt" = the bias whose softplus is
    log-uniform in ``time_step_min .. time_step_max`` (floored), "bias"
    = the router's selection bias, else a normal's scale)."""
    d, s = cfg.d_model, 0.02
    out = {"norm1": ((d,), None)}
    if kind.operator == "mamba2":
        H, C = cfg.mamba_heads, cfg.d_inner
        out.update(w_in=((d, C + cfg.conv_dim + H), s),
                   conv_w=((cfg.conv_kernel, cfg.conv_dim), s),
                   conv_b=((cfg.conv_dim,), s), dt_bias=((H,), "dt"),
                   A_log=((H,), "A"), D=((H,), None),
                   mix_norm=((C,), None), w_out=((C, d), s))
    elif kind.operator == "attention":
        hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        out.update(wq=((d, hq), s), wk=((d, hkv), s), wv=((d, hkv), s),
                   wo=((hq, d), s))
    else:
        n, l, f = cfg.n_held, cfg.moe_latent, cfg.moe_d_ff
        out.update(gate=((d, cfg.n_experts), s),
                   bias=((cfg.n_experts,), "bias"), w_dn=((d, l), s),
                   w1=((n, l, f), s), w2=((n, f, l), s), w_up=((l, d), s),
                   ws1=((d, cfg.shared_d_ff), s),
                   ws2=((cfg.shared_d_ff, d), s))
    return out


def init_leaf(key, shape, init, cfg: NemotronHConfig, scale: float = 1.0):
    """One leaf in float32 (``shape`` may lead with the repeats)."""
    if init is None:
        return jnp.ones(shape, F32)
    if init == "A":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if init == "dt":
        lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, F32)
                                 * (hi - lo) + lo), cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    if init == "bias":
        return jax.random.uniform(key, shape, F32, -0.05, 0.05)
    return jax.random.normal(key, shape, F32) * (init * scale)


def init_params(key: jax.Array, cfg: NemotronHConfig,
                init_scale: float = 1.0) -> Params:
    """f32 parameters, stacked by segment; untied embedding and head.
    ``init_scale`` multiplies every normal's 0.02 (a tiny test model
    raises it so that its layers decide the logits). The selection bias
    is small and NOT zero (HF initialises zeros), so that selection and
    weight differ."""
    k = lambda n: jax.random.fold_in(key, n)
    params = {"embed": init_leaf(k(0), (cfg.vocab, cfg.d_model), 0.02, cfg,
                                 init_scale),
              "head": init_leaf(k(1), (cfg.d_model, cfg.vocab), 0.02, cfg,
                                init_scale),
              "final_norm": jnp.ones((cfg.d_model,))}
    n = 1
    for seg in segments(cfg):
        layers = []
        for kind in seg.period:
            leaves = {}
            for name, (shape, init) in sorted(leaf_shapes(cfg, kind).items()):
                n += 1
                leaves[name] = init_leaf(k(n), (seg.repeats,) + shape, init,
                                         cfg, init_scale)
            layers.append(leaves)
        params[seg.key] = layers[0] if len(layers) == 1 else tuple(layers)
    return params


def cast_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """The tree in ``dtype`` for inference; the norms, the router and the
    recurrence's own parameters stay f32."""
    return kvpage.cast_params(params, dtype, _F32_LEAVES)


# -- the layer functions -----------------------------------------------------


def _w(lp, name, dtype):
    return lp[name].astype(dtype)


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _split_in(cfg: NemotronHConfig, zxd):
    """``x W_in``'s columns: (z [..., C], xBC [..., conv_dim], dt [...,
    H])."""
    C = cfg.d_inner
    return (zxd[..., :C], zxd[..., C:C + cfg.conv_dim],
            zxd[..., C + cfg.conv_dim:])


def _split_conv(cfg: NemotronHConfig, xbc):
    """The conv's output as the recurrence reads it: (x [..., H, P], B,
    C [..., G, N])."""
    C, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :C].reshape(lead + (cfg.mamba_heads,
                                         cfg.mamba_head_dim)),
            xbc[..., C:C + gn].reshape(lead + (cfg.n_groups, cfg.ssm_state)),
            xbc[..., C + gn:].reshape(lead + (cfg.n_groups, cfg.ssm_state)))


def _dt(lp: Params, raw):
    return jax.nn.softplus(raw.astype(F32) + lp["dt_bias"].astype(F32))


def _mixer_out(cfg: NemotronHConfig, lp: Params, y, xs, z, dtype):
    """From the recurrence's ``y`` [..., H, P] f32: ``+ D x``, the gate,
    the grouped RMSNorm and the output projection -> [..., d]."""
    y = y + lp["D"].astype(F32)[:, None] * xs.astype(F32)
    lead = y.shape[:-2]
    y = y.reshape(lead + (cfg.d_inner,)) * jax.nn.silu(z.astype(F32))
    y = y.reshape(lead + (cfg.n_groups, -1))
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    y = y.reshape(lead + (cfg.d_inner,)) * lp["mix_norm"].astype(F32)
    return y.astype(dtype) @ _w(lp, "w_out", dtype)


def _mamba_seq(cfg: NemotronHConfig, lp: Params, x: jax.Array, start,
               last_index, snapshot):
    """``PagedSpec.seq_state``: the Mamba-2 mixer with its residual over
    whole sequences x [B, S, d], from ``start`` = {"conv": [1, taps *
    conv_dim] the conv's inputs at the ``taps = conv_kernel - 1``
    positions before x, "ssm": [1, H, P, N] the recurrence's state
    there} (None: zeros, a sequence's start). Positions past
    ``last_index`` (None: none) are padding and leave the state as it
    was. Returns (x + y, and with ``snapshot`` sequence 0's state after
    every ``snapshot`` tokens, leaves [S // snapshot, ...], and after
    ``last_index``; else None, None)."""
    B, S, _ = x.shape
    taps = cfg.conv_kernel - 1
    z, u, dt = _split_in(cfg, rmsnorm(x, lp["norm1"], cfg.norm_eps)
                         @ _w(lp, "w_in", x.dtype))
    before = (jnp.zeros((B, taps, cfg.conv_dim), x.dtype) if start is None
              else jnp.stack(conv_window(start["conv"].astype(x.dtype),
                                         taps), axis=1))
    us = jnp.concatenate([before, u], axis=1)
    xs, b, c = _split_conv(cfg, conv_taps(
        lp, [us[:, j:j + S] for j in range(taps + 1)]).astype(x.dtype))
    dt = _dt(lp, dt)
    if last_index is not None:
        dt = jnp.where((jnp.arange(S) <= last_index)[None, :, None], dt, 0.0)
    a = -jnp.exp(lp["A_log"].astype(F32))
    h0 = (jnp.zeros((B, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
                    F32) if start is None else start["ssm"])
    scan = ssd.select_ssd(cfg.ssm_kernel)[1]

    def one(xs, dt, b, c, h0):
        return scan(xs, dt, b, c, a, h0, snapshot=snapshot,
                    chunk=cfg.chunk_size)
    y, snaps, end = (tuple(t[None] for t in one(xs[0], dt[0], b[0], c[0],
                                                h0[0]))
                     if B == 1 else jax.vmap(one)(xs, dt, b, c, h0))
    x = x + _mixer_out(cfg, lp, y, xs, z, x.dtype)
    if snapshot is None:
        return x, None, None
    at_ends, at_last = window_cuts(us, taps, last_index, snapshot)
    return (x, {"conv": at_ends, "ssm": snaps[0]},
            {"conv": at_last, "ssm": end[0]})


def _mamba_step(cfg: NemotronHConfig, lp: Params, x: jax.Array, held, at,
                live=None):
    """``PagedSpec.state_op``: one token a slot, x [B, 1, d], against
    the slots' state of ALL the Mamba layers, ``held`` = {"conv": [L, B,
    taps * conv_dim], "ssm": [L, B, H, P, N]}, of which this layer is
    ``at``. The recurrence's state goes to ``ssd_update`` whole and comes
    back updated in place, the rows of the slots that are ``live`` ([B]
    bool; None: all) alone: a dead slot's 4.19 MB stay where they are
    and its read-out is zeros. The window (61 KB a slot) is read and
    written back by XLA's own fusions, every slot's."""
    taps = cfg.conv_kernel - 1
    z, u, dt = _split_in(cfg, rmsnorm(x[:, 0], lp["norm1"], cfg.norm_eps)
                         @ _w(lp, "w_in", x.dtype))
    win = conv_window(lax.dynamic_index_in_dim(held["conv"], at, 0,
                                           keepdims=False), taps)
    xs, b, c = _split_conv(cfg, conv_taps(lp, win + [u]).astype(x.dtype))
    conv = lax.dynamic_update_index_in_dim(
        held["conv"], jnp.concatenate(win[1:] + [u], axis=-1).astype(
            held["conv"].dtype), at, 0)
    # (``live`` by position: ``control_nemotron``'s wrapper of the update
    # hands its arguments on as ``*a``)
    y, h = ssd.select_ssd(cfg.ssm_kernel)[0](
        held["ssm"], at, _dt(lp, dt), xs, b, c,
        -jnp.exp(lp["A_log"].astype(F32)), live)
    out = _mixer_out(cfg, lp, y, xs, z, x.dtype)
    return x + out[:, None], {"conv": conv, "ssm": h}


def _shared_ffn(cfg: NemotronHConfig, lp: Params, u: jax.Array):
    """The shared expert, at the model's width: every token's, whoever
    holds which experts."""
    h = jnp.dot(u, _w(lp, "ws1", u.dtype), preferred_element_type=F32)
    return jnp.dot(_relu2(h).astype(u.dtype), _w(lp, "ws2", u.dtype),
                   preferred_element_type=F32)


def _routed_latent(cfg: NemotronHConfig, lp: Params, u: jax.Array,
                   live=None):
    """(the held experts' part of the routed sum IN THE LATENT [T,
    latent] f32, idx [T, k], kept [T, 1]): what a chip of the deployment
    would hand to the combine before the up-projection."""
    idx, p, kept = moe.route_sigmoid_group_topk(
        u, lp["gate"], lp["bias"], cfg.top_k, 1, 1,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    ul = u @ _w(lp, "w_dn", u.dtype)
    # (with "repeat" the expert matrices are the segment's whole stacks)
    w1, w2 = (_w(lp, n, u.dtype) for n in _EXPERT_STACKS)

    def held(ul, idx, p, live=None):
        return moe.sorted_expert_ffn(ul, w1, None, w2, idx, p,
                                     first=cfg.experts_first,
                                     layer=lp.get("repeat"), live=live,
                                     act=_relu2)
    T, blk = u.shape[0], cfg.moe_block
    rows = (ul, idx, p) if live is None else (ul, idx, p, live)
    if T > blk and T % blk == 0:
        r = lax.map(lambda a: held(*a), tuple(
            a.reshape((T // blk, blk) + a.shape[1:]) for a in rows))
        r = r.reshape(T, -1)
    else:
        r = held(*rows)
    return r, idx, kept


def _moe_ffn(cfg: NemotronHConfig, lp: Params, x: jax.Array, live=None):
    """(x + the held experts' part through the up-projection + the
    shared expert, idx [T, k] the experts chosen, kept [T, 1]: one
    routing group). ``live`` [T] bool: the tokens whose result anybody
    receives (None: all); the others' held experts are not computed (the
    projections and the shared expert are: their weights are read once
    whatever the rows)."""
    u = rmsnorm(x, lp["norm1"], cfg.norm_eps).reshape(-1, cfg.d_model)
    r, idx, kept = _routed_latent(cfg, lp, u, live)
    y = jnp.dot(r.astype(x.dtype), _w(lp, "w_up", x.dtype),
                preferred_element_type=F32) + _shared_ffn(cfg, lp, u)
    return x + y.astype(x.dtype).reshape(x.shape), idx, kept


def _ffn(cfg: NemotronHConfig, lp: Params, x: jax.Array, kind: str,
         live=None):
    assert kind == "moe", kind
    return _moe_ffn(cfg, lp, x, live)


def _head(params: Params, cfg: NemotronHConfig, x: jax.Array):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.dot(x, params["head"].astype(x.dtype),
                   preferred_element_type=F32)


def forward(params: Params, cfg: NemotronHConfig,
            tokens: jax.Array) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): the plain
    whole-sequence pass, no cache."""
    return kvpage.forward(params, cfg, paged_spec(cfg), tokens)


# -- the paged plane's seam --------------------------------------------------


def paged_spec(cfg: NemotronHConfig) -> kvpage.PagedSpec:
    """What ``serve_paged_greedy``'s plane asks of this family: layers
    that are a mixer OR a feed-forward part alone (``LayerKind`` with
    ``"none"`` for the other); pages for the attention layers
    ([L_attn, P, Hkv, head_dim, pt]: ``n_rep`` query heads a K/V head
    through the shared write and walk), for a Mamba-2 layer a state of
    two leaves a slot (the conv's window in the compute type, flat along
    the lanes, and the recurrence's [H, P, N] in float32), a snapshot of
    it with every ``snapshot_every``-th whole prompt page; the router's
    width and which of its experts are held here. int8 pages are not
    wired: the state would want a precision of its own."""
    taps = cfg.conv_kernel - 1
    # (no position enters: a token's and a whole sequence's alike)
    qkv = lambda cfg, lp, x, pos: attention_qkv(cfg, lp, x)  # noqa: E731
    return kvpage.PagedSpec(
        segments=segments(cfg),
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_rep=cfg.n_heads // cfg.n_kv_heads,
        state={"conv": jax.ShapeDtypeStruct((taps * cfg.conv_dim,),
                                            cfg.dtype),
               "ssm": jax.ShapeDtypeStruct(
                   (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
                   F32)},
        snapshot_every=cfg.snapshot_every, state_live=True, kv_int8=False,
        n_experts=cfg.n_experts,
        experts_held=(cfg.experts_first, cfg.n_held),
        moe_whole=_EXPERT_STACKS, moe_row_dim=cfg.moe_latent,
        ffn_built=(("moe", "_shared_ffn+latent:sorted_expert_ffn/"
                    + moe.select_grouped_matmul().__name__),),
        embed=lambda params, cfg, token, pos:
            params["embed"][token][:, None, :].astype(cfg.dtype),
        qkv=qkv, attn_out=attention_out, state_op=_mamba_step, ffn=_ffn,
        head=lambda params, cfg, x: _head(params, cfg, x)[:, 0],
        seq_qkv=qkv, seq_state=_mamba_seq, seq_head=_head)
