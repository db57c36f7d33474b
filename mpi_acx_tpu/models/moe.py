"""Mixture-of-experts layer with expert parallelism over a mesh axis.

Top-k (switch-style k=1 / GShard-style k=2) routing with a static capacity
factor: dispatch and combine are einsums against a one-hot dispatch tensor,
so the whole layer is static-shaped for XLA. Expert parallelism shards the
expert dimension over a mesh axis inside shard_map: tokens travel to their
expert's device through ``lax.all_to_all`` (the EP collective), are
transformed by the local experts, and return the same way.

Training support: :func:`load_balance_loss` (the Switch-Transformer
auxiliary loss that keeps routing uniform) and :func:`router_z_loss`
(logit-magnitude regularizer), both exposed together with the layer output
by :func:`moe_layer_and_aux`, and :func:`make_moe_train_step` — a jitted
expert-parallel SGD step over a 1D 'ep' mesh whose loss and gradients are
validated exactly against the single-device layer (tests/test_moe_train.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int = 128
    d_ff: int = 256
    n_experts: int = 8
    capacity_factor: float = 2.0
    top_k: int = 1     # experts per token (1 = Switch, 2 = GShard-style)


def init_moe_params(key: jax.Array, cfg: MoeConfig) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "gate": jax.random.normal(k1, (d, E), jnp.float32) * 0.02,
        "w1": jax.random.normal(k2, (E, d, ff), jnp.float32) * 0.02,
        "w2": jax.random.normal(k3, (E, ff, d), jnp.float32) * 0.02,
    }


def _dispatch_tensors(gates: jax.Array, capacity: int, k: int = 1):
    """gates [T, E] -> (dispatch [T, E, C] one-hot, combine [T, E, C]).

    Top-k routing with per-expert capacity C: choice rank 0 (every token's
    best expert) claims queue positions first, then rank 1, etc. — the
    standard priority order, so adding second choices never evicts a
    token's first choice. Tokens past an expert's capacity are dropped
    from that expert (their dispatch/combine rows are zero). Combine
    weights are the router's softmax probabilities of the SURVIVING
    choices (not renormalized — the Switch/GShard convention, which also
    keeps the k=1 path bit-identical to a pure argmax router).
    """
    T, E = gates.shape
    probs = jax.nn.softmax(gates, axis=-1)                    # [T, E]
    _, idx = lax.top_k(gates, k)                              # [T, k]
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    counts = jnp.zeros((E,), jnp.float32)   # queue fill from earlier ranks
    for c in range(k):                      # k is static and tiny
        onehot = jax.nn.one_hot(idx[:, c], E, dtype=jnp.float32)  # [T, E]
        # Position of each token within its expert's queue, after the
        # tokens already enqueued by higher-priority choice ranks.
        pos = ((jnp.cumsum(onehot, axis=0) - 1.0) + counts) * onehot
        keep = pos < capacity
        onehot = onehot * keep
        posc = jax.nn.one_hot(
            pos.sum(-1).astype(jnp.int32), capacity)          # [T, C]
        d_c = onehot[:, :, None] * posc[:, None, :]           # [T, E, C]
        prob = jnp.sum(probs * onehot, -1)                    # [T]
        dispatch = dispatch + d_c
        combine = combine + d_c * prob[:, None, None]
        counts = counts + onehot.sum(0)
    return dispatch, combine


def load_balance_loss(gates: jax.Array, k: int = 1) -> jax.Array:
    """Switch-Transformer auxiliary load-balancing loss on router logits
    [T, E]: ``E * sum_e f_e * p_e`` where f_e is the fraction of (token,
    choice) assignments routed to expert e (pre-capacity) and p_e the mean
    router probability. Equals 1.0 at perfectly uniform routing (its
    minimum over f for fixed uniform p), grows as routing collapses."""
    T, E = gates.shape
    probs = jax.nn.softmax(gates, axis=-1)
    _, idx = lax.top_k(gates, k)                              # [T, k]
    f = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1).mean(0)  # [E]
    return E * jnp.sum(f / k * probs.mean(0))


def router_z_loss(gates: jax.Array) -> jax.Array:
    """Mean squared router logsumexp ([T, E] logits) — keeps gate logits
    small so the routing softmax stays in its well-conditioned range
    (the ST-MoE z-loss)."""
    return jnp.mean(jax.nn.logsumexp(gates.astype(jnp.float32), -1) ** 2)


def _route(params, x, cfg: MoeConfig, E: int):
    """Shared routing prologue: (gates [T,E] f32, dispatch, combine, cap).
    THE single source of the capacity formula and dispatch convention —
    every MoE execution path (single-device, sharded-token all_to_all EP,
    replicated-token EP) routes through here, which is what the
    bit-equal-routing guarantees in their docstrings rest on."""
    gates = x.astype(jnp.float32) @ params["gate"]
    cap = int(cfg.capacity_factor * x.shape[0] / E + 1)
    dispatch, combine = _dispatch_tensors(gates, cap, cfg.top_k)
    return gates, dispatch, combine, cap


def _expert_ffn(xin, params):
    """The expert MLP body on [..., E?, C, d] queues (leading axes ride
    einsum ellipses); one definition for every path."""
    h = jax.nn.gelu(jnp.einsum("...ecd,edf->...ecf", xin, params["w1"]))
    return jnp.einsum("...ecf,efd->...ecd", h, params["w2"])


def _moe_forward(params, x, cfg: MoeConfig, ep_axis):
    """Shared forward: returns (y [T, d], gates [T, E] f32 logits)."""
    T, d = x.shape
    e_local = params["w1"].shape[0]
    if ep_axis is None:
        E = e_local
        gates, dispatch, combine, _ = _route(params, x, cfg, E)
        xin = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), dispatch)
        out = _expert_ffn(xin, params)
        return (jnp.einsum("ecd,tec->td", out, combine).astype(x.dtype),
                gates)

    ep = lax.axis_size(ep_axis)
    E = e_local * ep
    # Capacity is per dispatch group (this rank's T tokens) — the GShard
    # convention; with tokens sharded over ep, T here is the local count.
    gates, dispatch, combine, cap = _route(params, x, cfg, E)
    xin = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), dispatch)
    # [E, C, d] -> [ep, E_local, C, d]; all_to_all swaps the ep axis with
    # the device axis so device j holds every sender's slice for ITS
    # experts: afterwards [ep(senders), E_local, C, d].
    xin = xin.reshape(ep, e_local, cap, d)
    xin = lax.all_to_all(xin, ep_axis, split_axis=0, concat_axis=0,
                         tiled=False)
    out = _expert_ffn(xin, params)
    # Route results back to their senders.
    out = lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                         tiled=False)
    out = out.reshape(E, cap, d)
    return jnp.einsum("ecd,tec->td", out, combine).astype(x.dtype), gates


def moe_layer(params: Dict[str, Any], x: jax.Array, cfg: MoeConfig,
              ep_axis: str | None = None) -> jax.Array:
    """x [T, d] -> [T, d].

    With ep_axis set (inside shard_map), the expert dim of params is the
    LOCAL slice [E/ep, d, ff] and tokens are exchanged by all_to_all:
    the dispatched activations [E, C, d] regroup to [ep, E_local, C, d]
    and all_to_all over the leading axis gives each device every sender's
    slice for ITS experts (BASELINE-style EP). x may be the
    rank's exclusive token shard (standard EP: all_to_all then moves real
    token data between devices) or replicated (each rank redundantly
    routes the same tokens).
    """
    y, _ = _moe_forward(params, x, cfg, ep_axis)
    return y


def _moe_forward_replicated(params, x, cfg: MoeConfig, ep_axis):
    """Replicated-token EP forward: returns (y [T, d], gates [T, E] f32)
    — the shared body of :func:`moe_layer_replicated_ep` and its
    aux-returning twin."""
    T, d = x.shape
    e_local = params["w1"].shape[0]
    ep = lax.axis_size(ep_axis)
    E = e_local * ep
    gates, dispatch, combine, _ = _route(params, x, cfg, E)  # [T, E, C]
    e0 = lax.axis_index(ep_axis) * e_local
    disp_l = lax.dynamic_slice_in_dim(dispatch, e0, e_local, axis=1)
    comb_l = lax.dynamic_slice_in_dim(combine, e0, e_local, axis=1)
    xin = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), disp_l)
    out = _expert_ffn(xin, params)
    part = jnp.einsum("ecd,tec->td", out, comb_l)
    return lax.psum(part, ep_axis).astype(x.dtype), gates


def moe_layer_replicated_ep(params: Dict[str, Any], x: jax.Array,
                            cfg: MoeConfig, ep_axis: str) -> jax.Array:
    """Expert parallelism for REPLICATED tokens (per-shard function).

    When every rank already holds the same x [T, d] (the tensor-parallel
    serving path and the flagship train step's blocks), the all_to_all
    exchange is pure overhead: each rank can route all T tokens itself,
    run only its LOCAL expert block, and let ONE psum assemble the
    combined output — 1/ep the expert FLOPs per rank and one collective
    per layer instead of two all_to_alls over redundant copies. The
    dispatch/combine tensors are computed identically to the
    single-device path, so routing (capacity, drops) is bit-equal.

    Use :func:`moe_layer` with ``ep_axis`` when tokens are SHARDED (the
    dp+ep training layout) — there the all_to_all moves real data.
    """
    y, _ = _moe_forward_replicated(params, x, cfg, ep_axis)
    return y


def moe_layer_replicated_ep_and_aux(params: Dict[str, Any], x: jax.Array,
                                    cfg: MoeConfig, ep_axis: str):
    """:func:`moe_layer_replicated_ep` plus the training auxiliaries
    (computed from the full replicated gates, so every rank holds the
    same aux values — gate/contribute them on ONE rank per replication
    group when assembling an exclusive-path loss)."""
    y, gates = _moe_forward_replicated(params, x, cfg, ep_axis)
    return y, {"load_balance": load_balance_loss(gates, cfg.top_k),
               "router_z": router_z_loss(gates)}


def moe_layer_sharded_dispatch(params: Dict[str, Any], x: jax.Array,
                               cfg: MoeConfig, ep_axis: str) -> jax.Array:
    """REAL expert-parallel dispatch for REPLICATED tokens (per-shard
    function): the serving-side counterpart of the training EP path.

    Where :func:`moe_layer_replicated_ep` has every rank route and
    dispatch ALL T tokens (only the expert FLOPs shard), here each rank
    takes its EXCLUSIVE T/ep token slice, routes just those, and the
    capacity-bounded ``all_to_all`` machinery of :func:`moe_layer`
    carries them to their expert's rank and back — per-rank routed token
    counts genuinely shard (router + dispatch/combine einsums drop from
    T to T/ep tokens per rank). One ``all_gather`` re-replicates the
    outputs for the next attention block.

    Capacity is per dispatch group (each rank's T/ep tokens), so in the
    drop-free regime (``capacity_factor >= n_experts``, the serving
    guard) outputs are token-identical to the single-device layer; with
    tight capacity the drop pattern is per-group, exactly like the dp+ep
    training layout. Requires ``T % ep == 0`` (shapes are static — this
    raises at trace time).
    """
    ep = lax.axis_size(ep_axis)
    T, d = x.shape
    if T % ep != 0:
        raise ValueError(
            f"sharded EP dispatch needs tokens ({T}) % ep ({ep}) == 0; "
            f"use moe_layer_replicated_ep for indivisible shapes")
    Tl = T // ep
    r = lax.axis_index(ep_axis)
    xl = lax.dynamic_slice_in_dim(x, r * Tl, Tl, axis=0)
    yl = moe_layer(params, xl, cfg, ep_axis=ep_axis)
    return lax.all_gather(yl, ep_axis, axis=0, tiled=True)


def moe_layer_and_aux(params: Dict[str, Any], x: jax.Array, cfg: MoeConfig,
                      ep_axis: str | None = None):
    """Like :func:`moe_layer` but also returns the training auxiliaries
    computed from this rank's router logits:
    ``(y, {"load_balance": .., "router_z": ..})``."""
    y, gates = _moe_forward(params, x, cfg, ep_axis)
    return y, {"load_balance": load_balance_loss(gates, cfg.top_k),
               "router_z": router_z_loss(gates)}


def make_moe_train_step(cfg: MoeConfig, mesh, ep_axis: str = "ep",
                        lr: float = 0.1, aux_weight: float = 1e-2,
                        z_weight: float = 1e-3):
    """Expert-parallel SGD train step over a 1D ``ep`` mesh.

    Returns a jitted ``step(params, x, targets) -> (loss, new_params)``
    with x/targets [T, d] sharded over ``ep`` (each device routes its own
    token shard; all_to_all carries tokens to their expert's device and
    back), gate replicated, expert weights sharded. Loss = global mean
    squared error + aux_weight * load-balance + z_weight * router-z.

    Gradient construction mirrors mpi_acx_tpu.train.make_loss_and_grads:
    every rank's loss terms cover only its EXCLUSIVE token shard and the
    scalar is assembled by psum, so each parameter cotangent path is
    unique; under check_vma=False the psum transpose uniformly scales all
    cotangents by ep (undone explicitly), after which the replicated gate
    needs one psum and the expert-sharded leaves none.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    ep_n = mesh.shape[ep_axis]
    assert cfg.n_experts % ep_n == 0, (
        f"n_experts ({cfg.n_experts}) must divide by the {ep_axis!r} mesh "
        f"axis ({ep_n})")

    def per_shard(params, x, tgt):
        def loss_fn(params):
            y, aux = moe_layer_and_aux(params, x, cfg, ep_axis=ep_axis)
            se = jnp.sum((y.astype(jnp.float32) -
                          tgt.astype(jnp.float32)) ** 2)
            raw = (se / (x.shape[1] * x.shape[0] * ep_n)
                   + (aux_weight * aux["load_balance"]
                      + z_weight * aux["router_z"]) / ep_n)
            return lax.psum(raw, ep_axis)

        loss, g = jax.value_and_grad(loss_fn)(params)
        g = jax.tree.map(lambda t: t / ep_n, g)   # undo psum seed scaling
        g = dict(g, gate=lax.psum(g["gate"], ep_axis))
        return loss, g

    pspecs = {"gate": P(), "w1": P(ep_axis), "w2": P(ep_axis)}
    grad_fn = shard_map(per_shard, mesh=mesh,
                        in_specs=(pspecs, P(ep_axis), P(ep_axis)),
                        out_specs=(P(), pspecs), check_vma=False)

    @jax.jit
    def step(params, x, tgt):
        loss, g = grad_fn(params, x, tgt)
        return loss, jax.tree.map(lambda p, gg: p - lr * gg, params, g)

    return step


# --------------------------------------------------------------------------
# Drop-free routed experts: sigmoid router, sorted dispatch, grouped matmuls
#
# Beside the capacity layer above (the train steps and moe_transformer
# keep theirs): nothing here has a capacity, so nothing is dropped, and
# no [T, E, C] tensor is made. The holder of the layer is told WHICH
# experts it holds (``first`` and the leading axis of the weights),
# routes over all of them and returns its own experts' part of the
# result; what absent experts would have added is left out (on one
# chip that holds every expert: nothing). No exchange lives here.


def route_sigmoid_topk(x, gate, bias, top_k: int, scale: float = 1.0,
                       normalise: bool = True):
    """The ``lfm2_moe`` router, in f32: ``s = sigmoid(x @ gate)`` [T, E];
    the ``top_k`` of ``s + bias`` are selected (the bias only selects;
    of equal scores the lower index wins, ``lax.top_k``'s rule); the
    weights are ``s`` of the selected, divided by ``sum + 1e-6`` when
    ``normalise``, times ``scale``. Returns (idx [T, k] int32, p [T, k]
    f32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               gate.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
    p = jnp.take_along_axis(s, idx, axis=-1)
    if normalise:
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-6)
    return idx.astype(jnp.int32), p * scale


def route_sigmoid_group_topk(x, gate, bias, top_k: int, n_group: int,
                             topk_group: int, scale: float = 1.0,
                             normalise: bool = True):
    """The ``deepseek_v3`` router (``topk_method`` ``noaux_tc``), in
    f32: ``s = sigmoid(x @ gate)`` [T, E]; ``s' = s + bias`` (the bias
    only selects); the experts lie in ``n_group`` groups of consecutive
    ones, a group's score is the sum of its two largest ``s'``, the
    ``topk_group`` best groups are KEPT and the ``top_k`` largest ``s'``
    inside them are selected (of equal scores the lower index wins, for
    groups and experts alike: ``lax.top_k``'s rule); the weights are
    ``s`` of the selected, divided by ``sum + 1e-20`` when
    ``normalise``, times ``scale``. Returns (idx [T, k] int32, p [T, k]
    f32, kept [T, n_group] bool). HF fills the dropped groups' scores
    with 0.0 where this takes them out (-inf): the same choice unless a
    kept group's ``top_k``-th best ``s'`` is negative."""
    T = x.shape[0]
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               gate.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    sb = (s + bias.astype(jnp.float32)).reshape(T, n_group, -1)
    _, best = lax.top_k(lax.top_k(sb, 2)[0].sum(-1), topk_group)
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], best].set(True)
    _, idx = lax.top_k(jnp.where(kept[:, :, None], sb, -jnp.inf).reshape(
        T, -1), top_k)
    p = jnp.take_along_axis(s, idx, axis=-1)
    if normalise:
        p = p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), p * scale, kept


def ragged_dot_matmul(xs, w, sizes):
    """Rows ``xs`` [M, a], sorted by group, times each group's own
    matrix ``w`` [G, a, b]; ``sizes`` [G] rows a group, rows past their
    sum belong to none. f32 accumulation and result. XLA's own
    ``lax.ragged_dot``: right anywhere, the reference the kernel below
    is held to."""
    return lax.ragged_dot(xs, w, sizes,
                          preferred_element_type=jnp.float32)


def megablox_matmul(xs, w, sizes):
    """:func:`ragged_dot_matmul` by the Pallas grouped matmul that ships
    with JAX (``pallas.ops.tpu.megablox.gmm``): a grid over (group, row
    tile) pairs, so an expert's matrix is read once a row tile its rows
    touch and an expert with no row not at all. Tiling, from the chip
    (PERF.md, PR 31: one layer of 64 experts 2048 x 1536, top 4, ms a
    layer; the weights' read alone is 1.45): row tiles of 128 (a
    (token, expert) pair in 16 is an expert's at a 256-token prefill: a
    taller tile multiplies padding), the whole contraction in one block,
    and the widest block of result columns whose double-buffered tiles
    fit Mosaic's 16 MiB of scoped VMEM, here all of them (one step an
    expert; 15.25 and 15.75 MiB). T = 64: 1.62 (512 columns 1.66, ``ragged_dot``
    2.36); T = 256: 1.87 (row tile 256 x 512 columns 2.07); T = 768:
    2.23 (2.44; ``ragged_dot`` 4.36). Rows are padded to whole tiles
    (the padding in no group). Interpret mode off the chip."""
    import sys

    import jax.experimental.pallas.ops.tpu.megablox  # noqa: F401
    from mpi_acx_tpu import backend
    # (the package rebinds its ``gmm`` attribute to the differentiable
    # wrapper; the plain forward kernel is the module's function)
    gmm = sys.modules["jax.experimental.pallas.ops.tpu.megablox.gmm"].gmm
    m, a = xs.shape
    n = w.shape[2]
    tm = min(128, -(-m // 8) * 8)
    item = xs.dtype.itemsize
    cols = [t for t in range(n, 0, -128) if n % t == 0]
    tn = next((t for t in cols if 2 * (tm * a + a * t) * item
               + 3 * tm * t * 4 <= _GMM_VMEM_BYTES), cols[-1])
    pad = -m % tm
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    out = gmm(xs, w, sizes.astype(jnp.int32),
              preferred_element_type=jnp.float32, tiling=(tm, a, tn),
              interpret=not backend.on_tpu())
    return out[:m] if pad else out


# What the grouped matmul's tiles may take, Mosaic's scoped VMEM: both
# inputs and the f32 result double-buffered, and the accumulator.
_GMM_VMEM_BYTES = 16 * 2 ** 20


def select_grouped_matmul():
    """The grouped matmul an expert layer is built with, in the
    ``select_attention`` idiom: the Pallas kernel on a TPU, XLA's
    ``ragged_dot`` elsewhere (on the CPU it beats an interpreted
    kernel). The chosen function's ``__name__`` is part of what
    ``ServingMetrics.paged_ffn`` records."""
    from mpi_acx_tpu import backend
    return megablox_matmul if backend.on_tpu() else ragged_dot_matmul


def sorted_expert_ffn(x, w1, w3, w2, idx, p, first: int = 0,
                      grouped_matmul=None, layer=None, live=None, act=None):
    """SwiGLU experts over sorted rows, no drop: x [T, d]; ``idx``,
    ``p`` [T, k] the routing over ALL experts; ``w1``, ``w3`` [n, d, f]
    and ``w2`` [n, f, d] the experts ``first .. first + n - 1`` held
    here. Every (token, expert) pair whose expert is held is computed:
    the pairs are sorted by expert (pairs of absent experts last, in no
    group), each expert's rows meet its matrices in one grouped matmul
    a projection, and the rows go back to their tokens weighted by
    ``p``. Returns [T, d] f32: ``sum_i p_i W2_i (silu(W1_i x) * W3_i
    x)`` over the held experts ``i`` of each token. ``grouped_matmul``:
    :func:`select_grouped_matmul`'s unless given.

    ``live`` ([T] bool; None: every token) says whose result anybody
    receives (a decode chunk's slots that can still deliver a token at
    this step: ``kvpage.paged_decode_step``). The pairs of a token that
    is not live are treated as pairs of an absent expert: behind every
    group and in none, so an expert that only such tokens chose has no
    row and its matrices are not read, and the token's result is zeros,
    written by a ``where`` (what a grouped matmul leaves in rows of no
    group is not defined; a call with NO live pair gives it no group
    with a row at all). A live token's result does not depend on it.

    With ``layer`` (a traced scalar is fine) the matrices are STACKS
    over layers, ``[R, n, ...]``, and this call is layer ``layer``'s:
    the stack goes to the grouped matmul whole, as ``R * n`` groups of
    which only this layer's have rows. Slicing the layer out instead
    costs a copy of it in front of every Pallas call (1.2 ms for each
    of the three 403 MB stacks at 64 experts of 2048 x 1536, against
    1.6 ms for the matmuls themselves: PERF.md, PR 31)."""
    grouped_matmul = grouped_matmul or select_grouped_matmul()
    T, d = x.shape
    k, n = idx.shape[1], w1.shape[-3]
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < n)
    if live is not None:
        held = held & jnp.repeat(live, k)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)                  # [T*k]
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    if layer is not None:
        groups = w1.shape[0] * n
        w1, w3, w2 = (w if w is None else w.reshape((groups,) + w.shape[2:])
                      for w in (w1, w3, w2))
        sizes = lax.dynamic_update_slice(jnp.zeros((groups,), jnp.int32),
                                         sizes, (layer * n,))
    xs = x[order // k]
    h = grouped_matmul(xs, w1, sizes)
    if w3 is None:
        h = act(h)
    else:
        g = grouped_matmul(xs, w3, sizes)
        h = jax.nn.silu(h) * g
    y = grouped_matmul(h.astype(x.dtype), w2, sizes)
    w = jnp.where(held, p.reshape(-1), 0.0)[order]
    y = jnp.where(held[order][:, None], y * w[:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    return y[back].reshape(T, k, d).sum(axis=1)


def route_softmax_topk(x, gate, top_k: int, normalise: bool = True):
    """The ``qwen3_moe`` / ``sdar_moe`` router, in f32, beside the two
    sigmoid routers above (at the file's end: the grouped matmul's lines,
    which its kernel's cache key holds, stay where they were): ``g =
    softmax(x @ gate)`` over ALL experts [T, E]; the ``top_k`` largest
    are selected (of equal scores the lower index wins, ``lax.top_k``'s
    rule); the weights are ``g`` of the selected, divided by their sum
    when ``normalise`` (``norm_topk_prob``). No bias, no scale. Returns
    (idx [T, k] int32, p [T, k] f32)."""
    g = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                               gate.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST), axis=-1)
    p, idx = lax.top_k(g, top_k)
    if normalise:
        p = p / jnp.sum(p, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), p
