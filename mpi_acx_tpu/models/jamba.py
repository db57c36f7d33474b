"""Jamba family (``model_type`` ``jamba``: AI21-Jamba2-3B), pure functional
JAX: Mamba-1 layers with an attention layer every ``attn_layer_period``,
every FFN a dense SwiGLU (``num_experts`` 1).

HF ``modeling_jamba.py``'s equations (``RMSNorm(x) = x * rsqrt(mean(x^2) +
eps) * w`` in float32; no bias but the two named):

* every layer: ``x = x + mixer(norm1(x))``; ``x = x + W_down(silu(W_gate
  h) * W_up h)``, ``h = norm2(x)``; a final RMSNorm, then the tied head.
* attention (layer ``i`` with ``i % period == offset``): q as ``n_heads``
  heads, k and v as ``n_kv_heads`` (one), causal softmax of ``q k^T /
  sqrt(head_dim)``, an output projection. NO positional term of any kind.
* Mamba (the rest): ``[u | z] = W_in h``; ``u = silu(conv1d_causal(u;
  d_conv taps, depthwise, bias))``; ``[dt_r | B | C] = W_x u``, each
  through ITS OWN RMSNorm (Jamba's addition to Mamba-1); ``dt =
  softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``; the selective scan
  (``ops/ssm.py``: its recurrence, ``+ D u`` and the ``silu(z)`` gate);
  ``W_out``. What a token needs of the past is the scan's state ``h``
  (``d_state`` numbers a channel, float32) and the conv's last ``d_conv
  - 1`` inputs: a FIXED-size state a layer a sequence, 9.3 MB a sequence
  at the published widths where a token's K/V is 1 KB.

Precision: weights and matmuls in ``cfg.dtype`` (bfloat16) with float32
accumulation; norms, softplus, ``exp``, the conv's sum, the recurrence
and the carried ``h`` in float32; the conv window in ``cfg.dtype``;
``A_log``, ``D``, ``b_dt`` float32.

Leaves of a layer (``params["seg<i>"]``, stacked by stretch as
``kvpage.compress_layers`` groups the layers; channels LAST wherever a
leaf meets the state, so that it lies along the lanes): ``norm1``,
``norm2`` [d]; ``w_gate``, ``w_up`` [d, ff], ``w_down`` [ff, d];
attention ``wq`` [d, Hq*Dh], ``wk``, ``wv`` [d, Hkv*Dh], ``wo`` [Hq*Dh,
d]; Mamba ``w_in`` [d, 2C] (columns u | z), ``conv_w`` [d_conv, C] (row
j meets u at ``t - (d_conv - 1) + j``), ``conv_b`` [C], ``w_x`` [C, R +
2N] (columns dt_r | B | C), ``dt_norm`` [R], ``b_norm``, ``c_norm`` [N],
``w_dt`` [R, C], ``b_dt`` [C], ``A_log`` [N, C], ``D`` [C], ``w_out``
[C, d].
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mpi_acx_tpu.models import kvpage
from mpi_acx_tpu.models.llama import rmsnorm
from mpi_acx_tpu.ops import ssm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    d_ff: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    norm_eps: float = 1e-6
    max_seq: int = 262144
    # Paged serving: which whole prompt pages keep a snapshot of the
    # Mamba layers' state (kvpage.PagedSpec.snapshot_every): 9.3 MB
    # beside 4 x 128 KB of K/V at the published widths.
    snapshot_every: int = 4
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None     # prefill attention; None = auto
    decode_flash: Optional[bool] = None  # paged decode kernels; None = auto
    ssm_kernel: Optional[bool] = None    # ops/ssm.py's calls; None = auto

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model


def jamba2_3b() -> JambaConfig:
    """AI21-Jamba2-3B as published (28 layers, attention at 7 and 21)."""
    return JambaConfig()


def tiny_jamba(**over) -> JambaConfig:
    """Small config for tests: two periods ``mamba, attn, mamba, mamba``,
    d = 64 (128 channels of 8 numbers), 4 query heads on one K/V head, a
    snapshot every second page."""
    base = dict(vocab=96, d_model=64, n_layers=8, n_heads=4, n_kv_heads=1,
                d_ff=96, attn_layer_period=4, attn_layer_offset=1,
                mamba_d_state=8, mamba_dt_rank=8, max_seq=256,
                snapshot_every=2)
    base.update(over)
    return JambaConfig(**base)


Params = Dict[str, Any]
_F32_LEAVES = ("A_log", "D", "b_dt")


def layer_kinds(cfg: JambaConfig) -> Tuple[kvpage.LayerKind, ...]:
    return tuple(
        kvpage.LayerKind() if i % cfg.attn_layer_period == cfg.attn_layer_offset
        else kvpage.LayerKind(operator="mamba", cache="state")
        for i in range(cfg.n_layers))


def segments(cfg: JambaConfig) -> Tuple[kvpage.Segment, ...]:
    return kvpage.compress_layers(layer_kinds(cfg))


def leaf_shapes(cfg: JambaConfig, kind: kvpage.LayerKind) -> Dict[str, tuple]:
    """One layer's leaves: name -> (shape, init: None = ones, "A" =
    ``log(1..N)`` a channel, "dt" = the bias whose softplus spans
    0.001-0.1 (Mamba's init), else a normal's scale)."""
    d, s = cfg.d_model, 0.02
    out = {"norm1": ((d,), None), "norm2": ((d,), None),
           "w_gate": ((d, cfg.d_ff), s), "w_up": ((d, cfg.d_ff), s),
           "w_down": ((cfg.d_ff, d), s)}
    if kind.operator == "attention":
        hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        out.update(wq=((d, hq), s), wk=((d, hkv), s), wv=((d, hkv), s),
                   wo=((hq, d), s))
    else:
        c, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        out.update(w_in=((d, 2 * c), s), conv_w=((cfg.mamba_d_conv, c), s),
                   conv_b=((c,), s), w_x=((c, r + 2 * n), s),
                   dt_norm=((r,), None), b_norm=((n,), None),
                   c_norm=((n,), None), w_dt=((r, c), s), b_dt=((c,), "dt"),
                   A_log=((n, c), "A"), D=((c,), None), w_out=((c, d), s))
    return out


def init_leaf(key, shape, init, scale: float = 1.0):
    """One leaf in float32 (``shape`` may lead with the repeats)."""
    if init is None:
        return jnp.ones(shape, F32)
    if init == "A":
        n = jnp.arange(1, shape[-2] + 1, dtype=F32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape)
    if init == "dt":
        dt = jnp.exp(jax.random.uniform(key, shape, F32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus's inverse
    return jax.random.normal(key, shape, F32) * (init * scale)


def init_params(key: jax.Array, cfg: JambaConfig,
                init_scale: float = 1.0) -> Params:
    """f32 parameters, stacked by segment; tied embedding and head.
    ``init_scale`` multiplies every normal's 0.02 (a tiny test model
    raises it so that its layers, not the tied embedding's echo, decide
    the logits)."""
    params = {"embed": init_leaf(jax.random.fold_in(key, 0),
                                 (cfg.vocab, cfg.d_model), 0.02, init_scale),
              "final_norm": jnp.ones((cfg.d_model,))}
    n = 0
    for seg in segments(cfg):
        layers = []
        for kind in seg.period:
            leaves = {}
            for name, (shape, init) in sorted(leaf_shapes(cfg, kind).items()):
                n += 1
                leaves[name] = init_leaf(jax.random.fold_in(key, n),
                                         (seg.repeats,) + shape, init,
                                         init_scale)
            layers.append(leaves)
        params[seg.key] = layers[0] if len(layers) == 1 else tuple(layers)
    return params


def cast_params(params: Params, dtype=jnp.bfloat16) -> Params:
    """The tree in ``dtype`` for inference; the norms and the scan's own
    parameters (``A_log``, ``D``, ``b_dt``) stay f32."""
    return kvpage.cast_params(params, dtype, _F32_LEAVES)


# -- the layer functions -----------------------------------------------------


def _w(lp, name, dtype):
    return lp[name].astype(dtype)


def attention_qkv(cfg: JambaConfig, lp: Params, x: jax.Array):
    """q [B, S, Hq, Dh], k, v [B, S, Hkv, Dh]; no position enters."""
    B, S, _ = x.shape
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    dh = cfg.head_dim
    return ((h @ _w(lp, "wq", x.dtype)).reshape(B, S, cfg.n_heads, dh),
            (h @ _w(lp, "wk", x.dtype)).reshape(B, S, cfg.n_kv_heads, dh),
            (h @ _w(lp, "wv", x.dtype)).reshape(B, S, cfg.n_kv_heads, dh))


def attention_out(cfg: JambaConfig, lp: Params, x: jax.Array, o: jax.Array):
    return x + o @ _w(lp, "wo", x.dtype)


def _ffn(cfg: JambaConfig, lp: Params, x: jax.Array, kind: str = "dense"):
    h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    g = jax.nn.silu(h @ _w(lp, "w_gate", x.dtype)) * (h @ _w(lp, "w_up",
                                                          x.dtype))
    return x + g @ _w(lp, "w_down", x.dtype)


def conv_taps(lp: Params, rows):
    """silu(bias + sum_j conv_w[j] * rows[j]) in float32: ``rows`` the
    ``d_conv`` inputs [..., C] a position sees, oldest first."""
    w = lp["conv_w"].astype(F32)
    acc = lp["conv_b"].astype(F32)
    for j, r in enumerate(rows):
        acc = acc + w[j] * r.astype(F32)
    return jax.nn.silu(acc)


def _scan_inputs(cfg: JambaConfig, lp: Params, u: jax.Array):
    """From the conv's output ``u`` [..., C]: (dt [..., C], B, C [...,
    N]) in float32, each of ``W_x u``'s three parts through its own
    RMSNorm, and ``A`` [N, C]."""
    r, n = cfg.mamba_dt_rank, cfg.mamba_d_state
    xdbc = (u @ _w(lp, "w_x", u.dtype)).astype(F32)
    dt_r = rmsnorm(xdbc[..., :r], lp["dt_norm"], cfg.norm_eps)
    b = rmsnorm(xdbc[..., r:r + n], lp["b_norm"], cfg.norm_eps)
    c = rmsnorm(xdbc[..., r + n:], lp["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(
        jnp.dot(dt_r.astype(u.dtype), _w(lp, "w_dt", u.dtype),
                preferred_element_type=F32) + lp["b_dt"].astype(F32))
    return dt, b, c, -jnp.exp(lp["A_log"].astype(F32))


def conv_window(flat, taps: int):
    """A conv window leaf [..., taps * C] as ``taps`` [..., C], oldest
    first (static slices along the lanes: no relayout)."""
    c = flat.shape[-1] // taps
    return [flat[..., j * c:(j + 1) * c] for j in range(taps)]


def window_cuts(us, taps: int, last_index, snapshot):
    """From the conv inputs ``us`` [B, taps + S, C] of a state-space
    layer (those before the sequences, then their own: rows ``t + 1 ..
    t + taps`` are the window after token t) the windows of sequence 0
    that a ``PagedSpec.seq_state`` returns, flat as the state's leaf:
    after every ``snapshot`` tokens ``[S // snapshot, taps * C]`` and
    after ``last_index`` ``[taps * C]``."""
    n = (us.shape[1] - taps) // snapshot
    at_ends = jnp.stack(
        [us[0, (j + 1) * snapshot:(j + 1) * snapshot + taps].reshape(-1)
         for j in range(n)]) if n else jnp.zeros((0, taps * us.shape[2]),
                                                 us.dtype)
    return at_ends, lax.dynamic_slice_in_dim(us[0], last_index + 1, taps,
                                             axis=0).reshape(-1)


def _mamba_seq(cfg: JambaConfig, lp: Params, x: jax.Array, start, last_index,
               snapshot):
    """``PagedSpec.seq_state``: the Mamba mixer with its residual over
    whole sequences x [B, S, d], from ``start`` = {"conv": [1, taps * C]
    the conv's inputs at the ``taps = d_conv - 1`` positions before x,
    "ssm": [1, N, C] the scan's state there} (None: zeros, a sequence's
    start). Positions past ``last_index`` (None: none) are padding and
    leave the scan's state as it was. Returns (x + y, and with
    ``snapshot`` sequence 0's state after every ``snapshot`` tokens,
    leaves [S // snapshot, ...], and after ``last_index``; else None,
    None)."""
    B, S, _ = x.shape
    C, N, taps = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv - 1
    u, z = jnp.split(rmsnorm(x, lp["norm1"], cfg.norm_eps)
                     @ _w(lp, "w_in", x.dtype), 2, axis=-1)
    before = (jnp.zeros((B, taps, C), x.dtype) if start is None
              else jnp.stack(conv_window(start["conv"].astype(x.dtype),
                                         taps), axis=1))
    us = jnp.concatenate([before, u], axis=1)
    uc = conv_taps(lp, [us[:, j:j + S] for j in range(taps + 1)]
                   ).astype(x.dtype)
    dt, b, c, a = _scan_inputs(cfg, lp, uc)
    if last_index is not None:
        dt = jnp.where((jnp.arange(S) <= last_index)[None, :, None], dt, 0.0)
    h0 = jnp.zeros((B, N, C), F32) if start is None else start["ssm"]
    scan = ssm.select_ssm(cfg.ssm_kernel)[1]

    def one(u, dt, z, b, c, h0):
        return scan(u, dt, z, b, c, a, lp["D"], h0, snapshot=snapshot)
    y, snaps, end = (tuple(t[None] for t in one(uc[0], dt[0], z[0], b[0],
                                                c[0], h0[0]))
                     if B == 1 else jax.vmap(one)(uc, dt, z, b, c, h0))
    x = x + y.astype(x.dtype) @ _w(lp, "w_out", x.dtype)
    if snapshot is None:
        return x, None, None
    at_ends, at_last = window_cuts(us, taps, last_index, snapshot)
    return (x, {"conv": at_ends, "ssm": snaps[0]},
            {"conv": at_last, "ssm": end[0]})


def _mamba_step(cfg: JambaConfig, lp: Params, x: jax.Array, held, at,
                live=None):
    """``PagedSpec.state_op``: one token a slot, x [B, 1, d], against
    the slots' state of ALL the Mamba layers, ``held`` = {"conv": [L, B,
    taps * C], "ssm": [L, B, N, C]}, of which this layer is ``at``. The
    scan's state goes to ``ssm_update`` whole and comes back updated in
    place; the window (30 KB a slot) is read and written back by XLA's
    own fusions. ``live`` is not read: every slot's state moves."""
    taps = cfg.mamba_d_conv - 1
    u, z = jnp.split(rmsnorm(x[:, 0], lp["norm1"], cfg.norm_eps)
                     @ _w(lp, "w_in", x.dtype), 2, axis=-1)
    win = conv_window(lax.dynamic_index_in_dim(held["conv"], at, 0,
                                               keepdims=False), taps)
    uc = conv_taps(lp, win + [u]).astype(x.dtype)
    conv = lax.dynamic_update_index_in_dim(
        held["conv"], jnp.concatenate(win[1:] + [u], axis=-1).astype(
            held["conv"].dtype), at, 0)
    dt, b, c, a = _scan_inputs(cfg, lp, uc)
    y, h = ssm.select_ssm(cfg.ssm_kernel)[0](
        held["ssm"], at, dt, uc, z, b, c, a, lp["D"])
    out = y.astype(x.dtype) @ _w(lp, "w_out", x.dtype)
    return x + out[:, None], {"conv": conv, "ssm": h}


def _head(params: Params, cfg: JambaConfig, x: jax.Array):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(x.dtype),
                      preferred_element_type=F32)


def forward(params: Params, cfg: JambaConfig, tokens: jax.Array) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): the plain
    whole-sequence pass, no cache."""
    return kvpage.forward(params, cfg, paged_spec(cfg), tokens)


# -- the paged plane's seam --------------------------------------------------


def paged_spec(cfg: JambaConfig) -> kvpage.PagedSpec:
    """What ``serve_paged_greedy``'s plane asks of this family: pages for
    the attention layers ([L_attn, P, 1, head_dim, pt]: every query head
    on the one K/V head through the shared write and walk), for a Mamba
    layer a state of two leaves a slot (the conv's window in the compute
    type, flat along the lanes, and the scan's [N, C] in float32), a
    snapshot of it with every ``snapshot_every``-th whole prompt page.
    int8 pages are not wired: the state would want a precision of its
    own."""
    taps = cfg.mamba_d_conv - 1
    # (no position enters: a token's and a whole sequence's alike)
    qkv = lambda cfg, lp, x, pos: attention_qkv(cfg, lp, x)  # noqa: E731
    return kvpage.PagedSpec(
        segments=segments(cfg),
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_rep=cfg.n_heads // cfg.n_kv_heads,
        state={"conv": jax.ShapeDtypeStruct((taps * cfg.d_inner,), cfg.dtype),
               "ssm": jax.ShapeDtypeStruct(
                   (cfg.mamba_d_state, cfg.d_inner), F32)},
        snapshot_every=cfg.snapshot_every, kv_int8=False,
        ffn_built=(("dense", "_ffn"),),
        embed=lambda params, cfg, token, pos:
            params["embed"][token][:, None, :].astype(cfg.dtype),
        qkv=qkv, attn_out=attention_out, state_op=_mamba_step, ffn=_ffn,
        head=lambda params, cfg, x: _head(params, cfg, x)[:, 0],
        seq_qkv=qkv, seq_state=_mamba_seq, seq_head=_head)
