"""Mamba-1's selective scan: the decode step's one-token update of every
slot's state and the prefill's scan over a sequence.

Per token ``t`` and channel ``c``, with the state ``h`` a row of ``N``
numbers a channel, kept ``[N, C]`` (channels on lanes)::

    h_t[:, c] = exp(dt_t[c] * A[:, c]) * h_{t-1}[:, c] + dt_t[c] * u_t[c] * B_t
    y_t[c]    = (h_t[:, c] . C_t + D[c] * u_t[c]) * silu(z_t[c])

everything float32: a bfloat16 ``h`` rounds at every one of a sequence's
steps. There is no matrix product in it; what bounds it is the state's
bytes in a decode step (every slot's ``[N, C]`` read and written once a
layer) and the vector unit in a prefill (the state stays in registers
over a block of tokens).

* :func:`ssm_update`: ONE Pallas call (``%ssm_update`` in a trace) over
  blocks of slots. The slots' state of ALL the layers goes in whole,
  ``[L, B, N, C]``, aliased to the result, and the layer is a prefetched
  scalar in the index maps: nothing slices a layer out of the stack
  (1.09 GB at 128 slots of Jamba2-3B), the kernel moves ``(layer,
  slot)`` in and out in place.
* :func:`ssm_scan`: ONE Pallas call (``%ssm_scan``) over blocks of
  ``block`` tokens in order (and, inside a block, stretches of 512
  channels), the state resident in VMEM between them. It
  hands out the state after every ``snapshot`` tokens (the page ends
  that keep a snapshot) and at the end. A padded position is given
  ``dt = 0`` by the caller: ``exp(0) = 1`` and ``0 * u * B = 0`` leave
  the state exactly as it was, so the end state of a right-padded bucket
  is the state at its last real token.

Off the chip the same values come from plain ``lax.scan``
(:func:`ssm_update_ref`, :func:`ssm_scan_ref`), as
``flash_decode.select_paged_kv_write`` does it: :func:`select_ssm`.
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
_SLOTS = 8            # slots a grid step of the update (f32 sublanes)
_GROUP = 8            # tokens loaded at a time by the scan
_LANES = 512          # channels whose state the scan keeps in registers
_VMEM = 32 << 20      # the update's blocks: 8 slots' states in and out, twice


def _token(h, dt, u, z, b, c, a, d):
    """One token of one sequence on a stretch of channels: ``h``, ``a``
    [N, C]; ``dt``, ``u``, ``z``, ``d`` [1, C]; ``b``, ``c`` [N, 1].
    Returns (h, y [1, C])."""
    h = jnp.exp(dt * a) * h + (dt * u) * b
    y = jnp.sum(h * c, axis=0, keepdims=True) + d * u
    return h, y * (z * jax.nn.sigmoid(z))


# -- the decode step's update -------------------------------------------------


def ssm_update_ref(h, layer, dt, u, z, b, c, a, d):
    """``h`` [L, B, N, C] f32, the slots' state of every layer; ``dt``,
    ``u``, ``z`` [B, C]; ``b``, ``c`` [B, N]; ``a`` [N, C], ``d`` [C]:
    one token a slot through layer ``layer``. Returns (y [B, C] f32,
    ``h`` with that layer's rows replaced). Plain JAX: it slices the
    layer out and puts it back."""
    dt, u, z = (t.astype(F32)[:, None, :] for t in (dt, u, z))
    hl = lax.dynamic_index_in_dim(h, layer, 0, keepdims=False)
    hl, y = jax.vmap(_token, in_axes=(0, 0, 0, 0, 0, 0, None, None))(
        hl, dt, u, z, b.astype(F32)[:, :, None], c.astype(F32)[:, :, None],
        a.astype(F32), d.astype(F32)[None])
    return y[:, 0], lax.dynamic_update_index_in_dim(h, hl, layer, 0)


def _update_kernel(layer_ref, dt_ref, u_ref, z_ref, b_ref, c_ref, a_ref,
                   d_ref, h_ref, y_ref, out_ref):
    a, d = a_ref[...], d_ref[...]
    ys = []
    for s in range(h_ref.shape[0]):
        h, y = _token(h_ref[s], dt_ref[s:s + 1], u_ref[s:s + 1],
                      z_ref[s:s + 1], b_ref[s], c_ref[s], a, d)
        out_ref[s] = h
        ys.append(y)
    y_ref[...] = jnp.concatenate(ys, axis=0)


def ssm_update(h, layer, dt, u, z, b, c, a, d):
    """:func:`ssm_update_ref` as one Pallas call: ``h`` whole and
    aliased to its result, ``(layer, slots)`` addressed by the index
    maps, ``_SLOTS`` slots a grid step. Jitted on its own so that it is
    traced once a process (``flash_decode.paged_kv_write``'s note)."""
    return _ssm_update(h, layer, dt, u, z, b, c, a, d,
                       interpret=not backend.on_tpu())


@functools.partial(jax.jit, static_argnames="interpret")
def _ssm_update(h, layer, dt, u, z, b, c, a, d, interpret):
    _, B, N, C = h.shape
    bs = _SLOTS if B % _SLOTS == 0 else B
    dt, u, z = (t.astype(F32) for t in (dt, u, z))
    # [B, N, 1]: a slot's N numbers down the sublanes, ready to be
    # broadcast along the channels' lanes.
    b, c = (t.astype(F32)[:, :, None] for t in (b, c))
    rows = pl.BlockSpec((bs, C), lambda i, _: (i, 0))
    cols = pl.BlockSpec((bs, N, 1), lambda i, _: (i, 0, 0))
    state = pl.BlockSpec((None, bs, N, C), lambda i, lyr: (lyr[0], i, 0, 0))
    y, h = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B // bs,),
            in_specs=[rows, rows, rows, cols, cols,
                      pl.BlockSpec((N, C), lambda i, _: (0, 0)),
                      pl.BlockSpec((1, C), lambda i, _: (0, 0)), state],
            out_specs=[rows, state]),
        out_shape=[jax.ShapeDtypeStruct((B, C), F32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        # Operand numbers count the prefetched scalar.
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssm_update",              # what the device trace prints
    )(jnp.asarray(layer, jnp.int32).reshape(1), dt, u, z, b, c,
      a.astype(F32), d.astype(F32)[None], h)
    return y, h


# -- the prefill's scan -------------------------------------------------------


def ssm_scan_ref(u, dt, z, b, c, a, d, h0, snapshot=None, block=None):
    """One sequence: ``u``, ``dt``, ``z`` [S, C]; ``b``, ``c`` [S, N];
    ``a`` [N, C], ``d`` [C]; ``h0`` [N, C] the state before its first
    token. Returns (y [S, C] f32, the state after every ``snapshot``
    tokens [S // snapshot, N, C] (None: [0, N, C]), the state after the
    last token [N, C]). A plain ``lax.scan`` over the tokens (``block``
    is the kernel's and means nothing here)."""
    S = u.shape[0]
    a, d = a.astype(F32), d.astype(F32)[None]
    xs = tuple(t.astype(F32) for t in (dt, u, z, b, c))

    def step(h, x):
        dt_t, u_t, z_t, b_t, c_t = x
        h, y = _token(h, dt_t[None], u_t[None], z_t[None], b_t[:, None],
                      c_t[:, None], a, d)
        return h, y[0]

    def stretch(h, x):              # ``snapshot`` tokens, the state after
        h, y = lax.scan(step, h, x)
        return h, (y, h)

    n = S // snapshot if snapshot else 0
    head = n * (snapshot or 0)
    h, (y, snaps) = lax.scan(stretch, h0.astype(F32), tuple(
        t[:head].reshape((n, snapshot or 1) + t.shape[1:]) for t in xs))
    h, rest = lax.scan(step, h, tuple(t[head:] for t in xs))
    return jnp.concatenate([y.reshape((head,) + y.shape[2:]), rest]), snaps, h


def _scan_kernel(u_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, *out):
    """One block of tokens (grid axis 0, in order) of one stretch of
    channels (axis 1): the stretch's state [N, lanes] stays in registers
    over the block's tokens, loaded in aligned groups of eight rows."""
    *snaps_ref, end_ref, h_ref = out
    i, c = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        h_ref[c] = h0_ref[...]

    a, d = a_ref[...], d_ref[...]

    def group(g, h):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        rows = pl.ds(t0, _GROUP)
        u, dt, z = u_ref[rows, :], dt_ref[rows, :], z_ref[rows, :]
        ys = []
        for r in range(_GROUP):
            h, y = _token(h, dt[r:r + 1], u[r:r + 1], z[r:r + 1],
                          b_ref[t0 + r], c_ref[t0 + r], a, d)
            ys.append(y)
        y_ref[rows, :] = jnp.concatenate(ys, axis=0)
        return h

    h = lax.fori_loop(0, u_ref.shape[0] // _GROUP, group, h_ref[c])
    # Every visit of an output block writes it; the last visit stays:
    # the end state's is the last block of tokens, a snapshot's the
    # block that ends its stretch (the index maps' business).
    h_ref[c] = h
    end_ref[...] = h
    for ref in snaps_ref:
        ref[0] = h


def ssm_scan(u, dt, z, b, c, a, d, h0, snapshot=None, block=128):
    """:func:`ssm_scan_ref` as one Pallas call over a grid of (blocks of
    ``block`` tokens in order, stretches of ``_LANES`` channels)
    (``snapshot`` a multiple of ``block``; a shorter sequence is one
    block). Jitted on its own, as the update is."""
    return _ssm_scan(u, dt, z, b, c, a, d, h0, snapshot=snapshot,
                     block=block, interpret=not backend.on_tpu())


@functools.partial(jax.jit,
                   static_argnames=("snapshot", "block", "interpret"))
def _ssm_scan(u, dt, z, b, c, a, d, h0, snapshot, block, interpret):
    S, C = u.shape
    N = a.shape[0]
    T = min(block, S)
    assert S % T == 0 and T % _GROUP == 0, (S, T)
    n_snap = S // snapshot if snapshot else 0
    every = snapshot // T if n_snap else 0
    assert not n_snap or snapshot % T == 0, (snapshot, T)
    lanes = _LANES if C % _LANES == 0 else C
    u, dt, z = (t.astype(F32) for t in (u, dt, z))
    b, c = (t.astype(F32)[:, :, None] for t in (b, c))
    rows = pl.BlockSpec((T, lanes), lambda i, j: (i, j))
    # (the same block over a block of tokens' stretches: fetched once)
    cols = pl.BlockSpec((T, N, 1), lambda i, j: (i, 0, 0))
    state = pl.BlockSpec((N, lanes), lambda i, j: (0, j))
    out_specs = [rows]
    out_shape = [jax.ShapeDtypeStruct((S, C), F32)]
    if n_snap:
        # Snapshot k's block is visited by the blocks of tokens of its
        # stretch, last by the one that ends it; the blocks behind the
        # last snapshot visit a row of their own, cut off below.
        out_specs.append(pl.BlockSpec(
            (1, N, lanes), lambda i, j: (jnp.minimum(i // every, n_snap),
                                         0, j)))
        out_shape.append(jax.ShapeDtypeStruct((n_snap + 1, N, C), F32))
    out_specs.append(state)
    out_shape.append(jax.ShapeDtypeStruct((N, C), F32))
    got = pl.pallas_call(
        _scan_kernel,
        grid=(S // T, C // lanes),
        in_specs=[rows, rows, rows, cols, cols, state,
                  pl.BlockSpec((1, lanes), lambda i, j: (0, j)), state],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((C // lanes, N, lanes), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_scan",                # what the device trace prints
    )(u, dt, z, b, c, a.astype(F32), d.astype(F32)[None], h0.astype(F32))
    y, end = got[0], got[-1]
    return y, (got[1][:n_snap] if n_snap else jnp.zeros((0, N, C), F32)), end


def select_ssm(use_kernel):
    """(update, scan) for a config's ``ssm_kernel`` field, the
    ``select_attention`` idiom: ``None`` -> the Pallas calls on a TPU
    and plain ``lax.scan`` elsewhere, ``True`` -> the calls (interpret
    mode off the chip), ``False`` -> plain JAX."""
    if use_kernel is None:
        use_kernel = backend.on_tpu()
    return ((ssm_update, ssm_scan) if use_kernel
            else (ssm_update_ref, ssm_scan_ref))
