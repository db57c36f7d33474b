"""Mamba-2's state-space duality (SSD): the decode step's one-token
update of every slot's state and the prefill's CHUNKED scan over a
sequence.

Per token ``t`` and head ``h`` (``P`` values a head, a state of ``N``
numbers a value, kept ``[H, P, N]``: ``N`` on the lanes), with ``B`` and
``C`` shared by the ``H / G`` heads of a group and ``a[h] = -exp(A_log[h])``
ONE number a head::

    h_t[h] = exp(dt_t[h] * a[h]) * h_{t-1}[h] + dt_t[h] * x_t[h] (x) B_t[g(h)]
    y_t[h] = h_t[h] C_t[g(h)]

all float32 (the caller adds ``D x`` and gates: they meet no state). What
Mamba-1's scan (``ops/ssm.py``) is not: the state is 4.19 MB a slot a
layer at the published widths (128 x 64 x 128) where Jamba2's is 0.33,
and because the decay is a scalar a head, a CHUNK of ``Q`` tokens is
matrix products: inside a chunk ``Y = ((C B^T) * L) (dt x)`` with ``L[t,
s] = exp(cum_t - cum_s)`` for ``s <= t``, across chunks ``Y += exp(cum_t)
C h`` and ``h <- exp(cum_Q) h + (w dt x)^T B``, ``w_s = exp(cum_Q -
cum_s)``, the state carried in float32 between chunks.

* :func:`ssd_update`: ONE Pallas call (``%ssd_update`` in a trace) over
  (slot, group) blocks. The slots' state of ALL the layers goes in
  whole, ``[L, B, H, P, N]``, aliased to the result, the layer a
  prefetched scalar in the index maps (``ssm_update``'s lesson: nothing
  slices a layer out of a 2 GB stack). A head's ``P`` values must lie
  down the sublanes to meet its ``[P, N]`` state, so the caller's XLA
  hands them over transposed, heads on the lanes (``[B, 2P, H]``: ``dt
  x`` and under it the decay), the kernel rotates its group's heads to
  the first lanes and broadcasts one lane a head; the read-out ``h C`` is
  one matrix product a group against ``C`` in every row, of which each
  head keeps its own lane. Told which slots can still deliver a token
  (``live``), the grid visits THEIR blocks and then stays where it is:
  a dead slot's state is neither read nor written.
* :func:`ssd_scan`: ONE Pallas call (``%ssd_scan``) over (group, chunk of
  ``chunk`` tokens in order), a group's state resident in VMEM between
  its chunks. It hands out the state after every ``snapshot`` tokens
  (the page ends that keep a snapshot) and at the end. A padded position
  is given ``dt = 0`` by the caller: decay 1 and nothing added leave the
  state exactly as it was, so the end state of a right-padded bucket is
  the state at its last real token (the wrapper pads a sequence to whole
  chunks the same way).

Off the chip the same values come from plain JAX, the recurrence token
by token (:func:`ssd_update_ref`, :func:`ssd_scan_ref`), in the
``ssm.select_ssm`` idiom: :func:`select_ssd`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_acx_tpu import backend

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))       # a @ b^T
_VMEM = 48 << 20


def _per_head(t, heads: int):
    """[..., G, N] -> [..., H, N]: a group's row for each of its heads."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def _token(h, dt, x, b, c, a):
    """One token of one sequence: ``h`` [H, P, N]; ``dt``, ``a`` [H];
    ``x`` [H, P]; ``b``, ``c`` [G, N]. Returns (h, y [H, P])."""
    H = h.shape[0]
    b, c = _per_head(b, H), _per_head(c, H)
    h = (jnp.exp(dt * a)[:, None, None] * h
         + (dt[:, None] * x)[:, :, None] * b[:, None, :])
    return h, jnp.sum(h * c[:, None, :], axis=-1)


def _first_lanes(t, g, hb: int):
    """``t`` [rows, H] with group ``g``'s ``hb`` heads on lanes 0..hb-1."""
    H = t.shape[1]
    return t if H == hb else pltpu.roll(t, lax.rem(H - g * hb, H), 1)


# -- the decode step's update -------------------------------------------------


def ssd_update_ref(h, layer, dt, x, b, c, a, live=None):
    """``h`` [L, B, H, P, N] f32, the slots' state of every layer;
    ``dt`` [B, H]; ``x`` [B, H, P]; ``b``, ``c`` [B, G, N]; ``a`` [H]:
    one token a slot through layer ``layer``. Returns (y [B, H, P] f32,
    ``h`` with that layer's rows replaced). ``live`` ([B] bool; None:
    every slot): a slot that is not keeps its rows of ``h`` as they
    were, whatever they hold, and its ``y`` is zeros. Plain JAX: it
    slices the layer out and puts it back."""
    was = lax.dynamic_index_in_dim(h, layer, 0, keepdims=False)
    hl, y = jax.vmap(_token, in_axes=(0, 0, 0, 0, 0, None))(
        was, dt.astype(F32), x.astype(F32), b.astype(F32), c.astype(F32),
        a.astype(F32))
    if live is not None:
        y = jnp.where(live[:, None, None], y, 0.0)
        hl = jnp.where(live[:, None, None, None], hl, was)
    return y, lax.dynamic_update_index_in_dim(h, hl, layer, 0)


def _visit(live, B: int):
    """[B + 1] int32, what the update's grid is told of ``live`` ([B]
    bool; None: every slot): the slot whose blocks step ``i`` of axis 0
    holds, and last how many are live. The live slots come first, in
    their order; every later step is given the LAST live slot again, so
    that it changes no block index and the pipeline copies nothing in
    or out for it (slot 0 where none lives)."""
    slots = jnp.arange(B, dtype=jnp.int32)
    if live is None:
        return jnp.append(slots, jnp.int32(B))
    # a live slot's place among the live (two small fusions a call: a
    # cumulative sum is five)
    place = jnp.sum(live[None, :] & (slots[None, :] < slots[:, None]),
                    axis=1, dtype=jnp.int32)
    n = jnp.sum(live, dtype=jnp.int32)
    held = live[None, :] & (place[None, :]
                            == jnp.minimum(slots, n - 1)[:, None])
    return jnp.append(jnp.sum(jnp.where(held, slots[None, :], 0), axis=1), n)


def _update_kernel(layer_ref, visit_ref, xt_ref, b_ref, c_ref, h_ref, yt_ref,
                   out_ref):
    i, g = pl.program_id(0), pl.program_id(1)
    n_live = visit_ref[visit_ref.shape[0] - 1]
    hb, P, N = h_ref.shape
    H = xt_ref.shape[1]

    @pl.when(i < n_live)
    def _():
        xr = _first_lanes(xt_ref[...], g, hb)   # [2P, H]: dt x; the decay
        bg, cg = b_ref[pl.ds(g, 1), :], c_ref[pl.ds(g, 1), :]   # [1, N]
        for j in range(hb):
            out_ref[j] = (xr[P:, j:j + 1] * h_ref[j] + xr[:P, j:j + 1] * bg)
        # h C for the whole group: C in every row, so that every lane of
        # row (head, p) holds y[head, p]; a head keeps its own lane.
        res = lax.dot_general(out_ref[...].reshape(hb * P, N),
                              jnp.broadcast_to(cg, (H, N)), _NT,
                              precision=_HI, preferred_element_type=F32
                              ).reshape(hb, P, H)
        mine = (lax.broadcasted_iota(jnp.int32, (hb, P, H), 2)
                == lax.broadcasted_iota(jnp.int32, (hb, P, H), 0))
        part = jnp.sum(jnp.where(mine, res, 0.0), axis=0)       # [P, H]
        if H != hb:
            part = pltpu.roll(part, g * hb, 1)

        @pl.when(g == 0)
        def _():
            yt_ref[...] = part

        @pl.when(g > 0)
        def _():
            yt_ref[...] += part

    # No slot lives: the grid holds ONE pair of state blocks from its
    # first step to its last, and the result block goes back behind it.
    # What goes back is what came.
    @pl.when((n_live == 0) & (i == 0) & (g == 0))
    def _():
        out_ref[...] = h_ref[...]


def ssd_update(h, layer, dt, x, b, c, a, live=None):
    """:func:`ssd_update_ref` as one Pallas call: ``h`` whole and
    aliased to its result, ``(layer, slot, group)`` addressed by the
    index maps. The grid stays ``(slots, groups)`` whatever ``live``
    says: its first steps visit the live slots, the others hold the
    last of those blocks and run no body (:func:`_visit`), so a dead
    slot's rows of ``h`` are not touched and its row of ``y``, which
    nobody wrote, is made zeros behind the call. Jitted on its own so
    that it is traced once a process (``flash_decode.paged_kv_write``'s
    note)."""
    return _ssd_update(h, layer, dt, x, b, c, a, live,
                       interpret=not backend.on_tpu())


@functools.partial(jax.jit, static_argnames="interpret")
def _ssd_update(h, layer, dt, x, b, c, a, live, interpret):
    _, B, H, P, N = h.shape
    G = b.shape[1]
    hb = H // G
    dt = dt.astype(F32)
    # [B, 2P, H]: (dt x)^T, and under it the decay in every row
    xt = jnp.concatenate(
        [jnp.swapaxes(dt[:, :, None] * x.astype(F32), 1, 2),
         jnp.broadcast_to(jnp.exp(dt * a.astype(F32))[:, None, :],
                          (B, P, H))], axis=1)

    def slot(i, g, lyr, visit):
        return visit[i], 0, 0

    def block(i, g, lyr, visit):
        # behind the live slots: the last group of the last of them
        return (lyr[0], visit[i], jnp.where(i < visit[B], g, G - 1), 0, 0)

    rows = pl.BlockSpec((None, 2 * P, H), slot)
    group = pl.BlockSpec((None, G, N), slot)
    state = pl.BlockSpec((None, None, hb, P, N), block)
    yt, h = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, G),
            in_specs=[rows, group, group, state],
            out_specs=[pl.BlockSpec((None, P, H), slot), state]),
        out_shape=[jax.ShapeDtypeStruct((B, P, H), F32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # Operand numbers count the prefetched scalars.
        input_output_aliases={5: 1},
        # (a result block that steps of axis 0 come back to: in order)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssd_update",              # what the device trace prints
    )(jnp.asarray(layer, jnp.int32).reshape(1), _visit(live, B), xt,
      b.astype(F32), c.astype(F32), h)
    y = jnp.swapaxes(yt, 1, 2)
    return (y if live is None else jnp.where(live[:, None, None], y, 0.0)), h


# -- the prefill's scan -------------------------------------------------------


def ssd_scan_ref(x, dt, b, c, a, h0, snapshot=None, chunk=None):
    """One sequence: ``x`` [S, H, P]; ``dt`` [S, H]; ``b``, ``c`` [S, G,
    N]; ``a`` [H]; ``h0`` [H, P, N] the state before its first token.
    Returns (y [S, H, P] f32, the state after every ``snapshot`` tokens
    [S // snapshot, H, P, N] (None: none), the state after the last
    token). A plain ``lax.scan`` over the tokens (``chunk`` is the
    kernel's and means nothing here)."""
    S = x.shape[0]
    a = a.astype(F32)
    xs = tuple(t.astype(F32) for t in (dt, x, b, c))

    def step(h, t):
        return _token(h, *t, a)

    def stretch(h, t):              # ``snapshot`` tokens, the state after
        h, y = lax.scan(step, h, t)
        return h, (y, h)

    n = S // snapshot if snapshot else 0
    head = n * (snapshot or 0)
    h, (y, snaps) = lax.scan(stretch, h0.astype(F32), tuple(
        t[:head].reshape((n, snapshot or 1) + t.shape[1:]) for t in xs))
    h, rest = lax.scan(step, h, tuple(t[head:] for t in xs))
    return jnp.concatenate([y.reshape((head,) + y.shape[2:]), rest]), snaps, h


def _scan_kernel(x_ref, xt_ref, dt_ref, cumr_ref, cumc_ref, tot_ref, b_ref,
                 c_ref, h0_ref, y_ref, *out):
    """One chunk of tokens (grid axis 1, in order) of one group of heads
    (axis 0): the group's state [hb, P, N] stays in VMEM over its
    chunks."""
    *snaps_ref, end_ref, h_ref = out
    g, i = pl.program_id(0), pl.program_id(1)
    hb, P, N = h_ref.shape
    Q = x_ref.shape[0]

    @pl.when(i == 0)
    def _():
        h_ref[...] = h0_ref[...]

    bm, cm = b_ref[...].astype(F32), c_ref[...].astype(F32)     # [Q, N]
    # scores[t, s] = C_t . B_s, the group's own; a head scales them
    scores = lax.dot_general(cm, bm, _NT, precision=_HI,
                             preferred_element_type=F32)
    causal = (lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    cum_t = _first_lanes(cumc_ref[...], g, hb)                  # [Q, H]
    total = _first_lanes(tot_ref[...], g, hb)                   # [P, H]
    ys = []
    for j in range(hb):
        col, row = cum_t[:, j:j + 1], cumr_ref[j:j + 1, :]    # [Q,1] [1,Q]
        dt = dt_ref[j:j + 1, :]                                 # [1, Q]
        hj = h_ref[j]
        decay = jnp.exp(jnp.where(causal, col - row, -1e30))
        y = jnp.dot(scores * decay * dt,
                    x_ref[:, j * P:(j + 1) * P], precision=_HI,
                    preferred_element_type=F32)
        y += jnp.exp(col) * lax.dot_general(
            cm, hj, _NT, precision=_HI, preferred_element_type=F32)
        ys.append(y)
        # what of each token is left at the chunk's end
        w = jnp.exp(row[:, Q - 1:] - row) * dt
        h_ref[j] = total[:, j:j + 1] * hj + jnp.dot(
            xt_ref[j * P:(j + 1) * P, :] * w, bm, precision=_HI,
            preferred_element_type=F32)
    y_ref[...] = ys[0] if hb == 1 else jnp.concatenate(ys, axis=1)
    # Every visit of an output block writes it; the last visit stays:
    # the end state's is the last chunk, a snapshot's the chunk that
    # ends its stretch (the index maps' business).
    end_ref[...] = h_ref[...]
    for ref in snaps_ref:
        ref[...] = h_ref[...]


def ssd_scan(x, dt, b, c, a, h0, snapshot=None, chunk=128):
    """:func:`ssd_scan_ref` as one Pallas call over a grid of (groups of
    heads, chunks of ``chunk`` tokens in order) (``snapshot`` a multiple
    of ``chunk``; a sequence is padded to whole chunks with ``dt = 0``).
    Jitted on its own, as the update is."""
    return _ssd_scan(x, dt, b, c, a, h0, snapshot=snapshot, chunk=chunk,
                     interpret=not backend.on_tpu())


@functools.partial(jax.jit,
                   static_argnames=("snapshot", "chunk", "interpret"))
def _ssd_scan(x, dt, b, c, a, h0, snapshot, chunk, interpret):
    S0, H, P = x.shape
    G, N = b.shape[1:]
    hb, Q = H // G, chunk
    n_snap = S0 // snapshot if snapshot else 0
    assert not n_snap or snapshot % Q == 0, (snapshot, Q)
    every = snapshot // Q if n_snap else 0
    pad = -S0 % Q
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                       for t in (x, dt, b, c))
    S = S0 + pad
    nc = S // Q
    dt = dt.astype(F32)
    # the log decay summed from a chunk's start to each of its tokens
    cum = jnp.cumsum((dt * a.astype(F32)).reshape(nc, Q, H), axis=1)
    xf = x.astype(F32).reshape(S, H * P)
    tokens = lambda w: pl.BlockSpec((Q, w), lambda g, i: (i, g))
    heads = lambda w: pl.BlockSpec((w, Q), lambda g, i: (g, i))
    state = pl.BlockSpec((hb, P, N), lambda g, i: (g, 0, 0))
    out_specs = [tokens(hb * P)]
    out_shape = [jax.ShapeDtypeStruct((S, H * P), F32)]
    if n_snap:
        # Snapshot k's block is visited by the chunks of its stretch,
        # last by the one that ends it; the chunks behind the last
        # snapshot visit a row of their own, cut off below.
        out_specs.append(pl.BlockSpec(
            (None, hb, P, N),
            lambda g, i: (jnp.minimum(i // every, n_snap), g, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n_snap + 1, H, P, N), F32))
    out_specs.append(state)
    out_shape.append(jax.ShapeDtypeStruct((H, P, N), F32))
    got = pl.pallas_call(
        _scan_kernel,
        grid=(G, nc),
        in_specs=[tokens(hb * P), heads(hb * P), heads(hb), heads(hb),
                  pl.BlockSpec((Q, H), lambda g, i: (i, 0)),
                  pl.BlockSpec((None, P, H), lambda g, i: (i, 0, 0)),
                  tokens(N), tokens(N), state],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, P, N), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssd_scan",                # what the device trace prints
    )(xf, xf.T, dt.T, cum.reshape(S, H).T, cum.reshape(S, H),
      jnp.broadcast_to(jnp.exp(cum[:, -1])[:, None, :], (nc, P, H)),
      b.reshape(S, G * N), c.reshape(S, G * N), h0.astype(F32))
    y, end = got[0][:S0].reshape(S0, H, P), got[-1]
    return y, (got[1][:n_snap] if n_snap
               else jnp.zeros((0, H, P, N), F32)), end


def select_ssd(use_kernel):
    """(update, scan) for a config's ``ssm_kernel`` field, the
    ``select_attention`` idiom: ``None`` -> the Pallas calls on a TPU
    and plain JAX elsewhere, ``True`` -> the calls (interpret mode off
    the chip), ``False`` -> plain JAX."""
    if use_kernel is None:
        use_kernel = backend.on_tpu()
    return ((ssd_update, ssd_scan) if use_kernel
            else (ssd_update_ref, ssd_scan_ref))
