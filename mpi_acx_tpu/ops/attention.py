"""Blockwise-causal flash attention as a Pallas TPU kernel.

The MXU hot op of every model family in :mod:`mpi_acx_tpu.models`. Online
softmax over key/value blocks (never materializes the [S, S] score matrix),
f32 accumulators, bf16-friendly matmuls with ``preferred_element_type`` so
both dots land on the MXU at full rate. Causal blocks above the diagonal
are skipped entirely (the inner loop's trip count is ``i + 1``), so the
kernel does ~half the FLOPs of the dense-mask reference implementation and
O(S) memory instead of O(S^2).

Differentiable: the ``jax.custom_vjp`` backward is a Pallas kernel too
(:func:`_flash_bwd_kernel`): one program per (batch, head) holds the
head's q, k, v and dO in VMEM, recomputes each block pair's
probabilities from the forward's row logsumexp and applies the standard
flash-backward formulas (dS = P * (dP - rowsum(dO*O))) with the
forward's conventions (MXU operands in the input dtype, f32
accumulation, causal blocks above the diagonal skipped by trip count).
Nothing of size [S, S] or [block, block] reaches HBM. Under
differentiation plain :func:`flash_attention` runs the lse-emitting
forward (no lse sweep in the backward; the inference forward is
untouched) and NAMES its two residuals only the kernel can produce, o
and lse (:data:`FLASH_RESIDUALS`), so that a remat layer's policy can
keep them and run no second forward. Past the resident kernel's lengths
the backward is :func:`_flash_bwd_blockwise`, two loops in plain JAX.

:func:`flash_attention_lse` is the variant that DOES emit the row
logsumexp — packed into one extra lane column of a single output — and
its backward reuses the emitted lse and folds the lse cotangent into dS
(``rowsum(dO*O) - dLSE`` is the one row term the kernel subtracts).
It is the single-chip building block of
:func:`mpi_acx_tpu.parallel.ring_attention.ring_attention`: ring
attention rotates K/V shards around the mesh while each step runs exactly
this kernel on the resident shard and merges blocks by logaddexp.

Runs compiled on TPU and in Pallas interpret mode elsewhere (the CPU test
mesh). Interpret mode checks the math; whether the chip's compiler takes
the kernel is checked by tests/test_tpu_compile.py.

Two kernel variants share the math:
* resident (default below S=16384): whole K/V in VMEM per (batch, head)
  program — fastest at moderate S, but VMEM caps it near S=8192 on v5e;
* streaming (``streaming=True`` / auto at S>=16384): a fourth,
  sequential grid dimension feeds ONE double-buffered K/V tile per step
  with the online-softmax state in VMEM scratch — bit-identical output
  (verified on-chip), bounded by HBM instead of VMEM (S=32768 measured
  at 60 ms on v5e where the resident kernel cannot compile).
Beyond one chip, the sequence-parallel strategies (ring attention /
Ulysses) shard S across devices and call these kernels per shard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_acx_tpu import backend

_NEG_INF = -1e30
# From this length on the forward streams K/V tiles (a head no longer fits
# VMEM whole) and the backward runs blockwise in plain JAX.
_STREAMING_MIN = 16384


def attention_reference(q, k, v, causal: bool = True):
    """Dense-mask reference attention, [B, S, H, D] layout; f32 softmax.
    Ground truth for the kernel's numerics tests."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d)
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def auto_attention(q, k, v, causal: bool = True):
    """[B, S, H, D] attention with the kernel picked per shape: the Pallas
    flash kernel past its measured v5e crossover (S >= 1024; dense wins
    below — grid overhead), dense elsewhere. THE single definition of the
    flash/dense policy — the model layer and the sequence-parallel
    strategies all route through here."""
    S = q.shape[1]
    if backend.on_tpu() and S >= 1024 and S % 128 == 0:
        return flash_attention(q, k, v, causal=causal)
    return attention_reference(q, k, v, causal=causal)


def select_attention(use_flash):
    """THE single flash/dense dispatch for a ``use_flash`` config field
    (both model families route here so the policy can't drift):
    ``None`` -> per-shape auto policy, ``True`` -> Pallas flash kernel,
    ``False`` -> dense reference. All returned callables take
    ``(q, k, v, causal=True)`` on [B, S, H, D]."""
    if use_flash is None:
        return auto_attention
    return flash_attention if use_flash else attention_reference


def _online_softmax_step(q, kb, vb, m, l, acc, row0, col0, masked, prec):
    """One flash block update, shared by the resident and streaming
    kernels: scaled-q x K^T logits, optional causal mask with absolute
    row/col offsets, and the rescale-and-accumulate of the
    online-softmax state. Returns (m, l, acc)."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)  # [BQ, BK] f32
    if masked:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = corr * acc + jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec)
    return m_new, l_new, acc_new


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k,
                  scale, causal, emit_lse=False):
    """One (batch, head, q-block) program: online softmax over k blocks.

    Causal masking is only evaluated on the blocks that straddle the
    diagonal; the (majority) fully-below-diagonal blocks run the unmasked
    fast loop. Dots run in the input dtype with f32 accumulation; for f32
    inputs the MXU is asked for HIGHEST precision (its default f32 path is
    bf16-pass multiplication, ~1e-2 absolute error — measured on v5e).

    With ``emit_lse`` the out block is f32 [block_q, D+1]: the normalized
    output in lanes [0, D) and the row logsumexp in lane D, packed into
    ONE output."""
    i = pl.program_id(2)
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    # Pre-scale q once instead of scaling every [BQ, BK] logit block.
    q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_ref.dtype)

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    def step(j, carry, masked):
        m, l, acc = carry
        kb = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        return _online_softmax_step(q, kb, vb, m, l, acc, i * block_q,
                                    j * block_k, masked, prec)

    if causal:
        # K/V blocks [0, n_full) lie strictly below the diagonal for every
        # row of this q block; blocks [n_full, n_diag) straddle it.
        q_end = (i + 1) * block_q                        # first masked col
        n_full = i * block_q // block_k
        n_diag = (q_end + block_k - 1) // block_k
        carry = jax.lax.fori_loop(
            0, n_full, lambda j, c: step(j, c, masked=False), (m0, l0, acc0))
        m, l, acc = jax.lax.fori_loop(
            n_full, n_diag, lambda j, c: step(j, c, masked=True), carry)
    else:
        n_kv = k_ref.shape[2] // block_k
        m, l, acc = jax.lax.fori_loop(
            0, n_kv, lambda j, c: step(j, c, masked=False), (m0, l0, acc0))
    if emit_lse:
        lse = m + jnp.log(l)                             # [BQ, 1] f32
        o_ref[0, 0] = jnp.concatenate([acc / l, lse], axis=-1).astype(
            o_ref.dtype)
    else:
        o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _out_struct(shape, dtype, *operands):
    """ShapeDtypeStruct for a pallas_call output, carrying the union of the
    operands' varying-mesh-axes so the kernel can run inside a shard_map
    with check_vma=True (e.g. as ring attention's block primitive)."""
    try:
        vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    except Exception:
        vma = frozenset()
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _fit_blocks(S, block_q, block_k):
    """Shrink the requested block sizes to divisors of S. Returns
    ``(block_q, block_k)``, or ``None`` when S has no usable 128-multiple
    divisor (e.g. S=648) — callers fall back to the dense reference
    instead of crashing the model call."""
    def fit(block):
        b = min(block, S)
        while b > 128 and S % b:
            b -= 128
        return b

    bq, bk = fit(block_q), fit(block_k)
    if S % bq or S % bk:
        return None
    return bq, bk


_fallback_warned: set = set()


def _warn_dense_fallback(S, Sk):
    """One-time (per shape) warning that the flash kernel can't tile this
    sequence length and the dense reference is used instead."""
    key = (S, Sk)
    if key not in _fallback_warned:
        _fallback_warned.add(key)
        import warnings

        warnings.warn(
            f"flash_attention: no block size divides S={S}/Sk={Sk}; "
            "falling back to the dense reference for this shape",
            RuntimeWarning, stacklevel=3)


def _reference_lse(q, k, v, causal: bool = True):
    """Dense fallback for :func:`flash_attention_lse`: same contract
    (o [B, S, H, D], lse [B, H, S] f32), materialized logits."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d)
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)   # [B, H, S] f32
    p = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


def _flash_fwd_impl(qt, kt, vt, causal, block_q, block_k):
    """Raw pallas call on [B, H, Sq, D] / [B, H, Sk, D] operands ->
    o [B, H, Sq, D] (Sk may differ from Sq in the non-causal case)."""
    B, H, S, D = qt.shape
    Sk = kt.shape[2]
    assert not causal or S == Sk, (S, Sk)
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct((B, H, S, D), qt.dtype, qt, kt, vt),
        interpret=not backend.on_tpu(),
    )(qt, kt, vt)


def _flash_stream_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, block_q, block_k, scale, causal, n_k):
    """One (batch, head, q-block, K-BLOCK) grid step of the STREAMING
    kernel: K/V arrive one [block_k, D] tile per step (Mosaic
    double-buffers the tile DMA against compute), and the online-softmax
    state (m, l, acc) lives in VMEM scratch across the sequential k
    dimension. Unlike _flash_kernel, VMEM holds only one K/V tile — no
    whole-sequence residency, so S is bounded by HBM, not VMEM."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: K blocks strictly above the diagonal contribute nothing —
    # skip their FLOPs entirely (their DMA is pipelined regardless).
    visible = (jnp.bool_(True) if not causal
               else j * block_k <= i * block_q + block_q - 1)

    @pl.when(visible)
    def _compute():
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_ref.dtype)
        m_new, l_new, acc_new = _online_softmax_step(
            q, k_ref[0, 0], v_ref[0, 0], m_scr[:], l_scr[:], acc_scr[:],
            i * block_q, j * block_k, causal, prec)
        m_scr[:] = m_new
        l_scr[:] = l_new
        acc_scr[:] = acc_new

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


def _flash_stream_fwd_impl(qt, kt, vt, causal, block_q, block_k):
    """Raw streaming pallas call on [B, H, S, D] operands."""
    B, H, S, D = qt.shape
    Sk = kt.shape[2]
    assert not causal or S == Sk, (S, Sk)
    n_k = Sk // block_k
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_flash_stream_kernel, block_q=block_q,
                               block_k=block_k, scale=scale, causal=causal,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct((B, H, S, D), qt.dtype, qt, kt, vt),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=not backend.on_tpu(),
    )(qt, kt, vt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(qt, kt, vt, causal, block_q, block_k, streaming=False):
    if streaming:
        return _flash_stream_fwd_impl(qt, kt, vt, causal, block_q, block_k)
    return _flash_fwd_impl(qt, kt, vt, causal, block_q, block_k)


def _flash_vjp_fwd(qt, kt, vt, causal, block_q, block_k, streaming=False):
    # Runs only under differentiation: the resident forward then emits
    # its lse (no lse sweep in the backward; the streaming kernel emits
    # none). What only the kernel can produce is named for a remat policy.
    if streaming:
        o = _flash_stream_fwd_impl(qt, kt, vt, causal, block_q, block_k)
        return _fwd_named(qt, kt, vt, o)
    o, lse = _flash_lse_fwd_impl(qt, kt, vt, causal, block_q, block_k)
    return _fwd_named(qt, kt, vt, o, lse)


def _flash_bwd_blockwise(qt, kt, vt, o, do, causal, block_q, block_k,
                         lse=None, dlse=None):
    """Blockwise flash backward in pure JAX ([B, H, S, D] operands).

    Outer scan over q blocks; for each, an inner fori_loop over exactly
    the k blocks at-or-below the diagonal (causal skips the rest, like the
    forward kernel) first rebuilds that q block's row logsumexp (skipped
    when the forward emitted ``lse`` [B, H, S]), then applies the standard
    flash-backward formulas:
      dV_j += P_j^T dO;  dP_j = dO V_j^T;  D = rowsum(dO * O)
      dS_j = P_j * (dP_j - D + dLSE) * scale;  dQ += dS_j K_j;  dK_j += dS_j^T Q
    (the dLSE term is the cotangent of an emitted lse output: d lse_i /
    d s_ij = P_ij). Peak extra memory is [B, H, block_q, block_k] per step.
    """
    B, H, S, Dh = qt.shape
    Sk = kt.shape[2]
    scale = 1.0 / (Dh ** 0.5)
    k32 = kt.astype(jnp.float32)
    v32 = vt.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    Drow = jnp.sum(do32 * o.astype(jnp.float32), axis=-1)      # [B,H,S]

    def qblock(carry, i):
        dk_acc, dv_acc = carry
        q0 = i * block_q
        qb = jax.lax.dynamic_slice_in_dim(
            qt, q0, block_q, axis=2).astype(jnp.float32)       # [B,H,bq,D]
        dob = jax.lax.dynamic_slice_in_dim(do32, q0, block_q, axis=2)
        Db = jax.lax.dynamic_slice_in_dim(Drow, q0, block_q, axis=2)
        rows = q0 + jnp.arange(block_q)[:, None]               # [bq, 1]
        if causal:
            # k blocks [0, n_kv) contain at least one unmasked column for
            # this q block (same bound as the forward kernel's n_diag).
            n_kv = (q0 + block_q + block_k - 1) // block_k
        else:
            n_kv = Sk // block_k

        def logits(j):
            kb = jax.lax.dynamic_slice_in_dim(k32, j * block_k, block_k,
                                              axis=2)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb) * scale
            if causal:
                cols = j * block_k + jnp.arange(block_k)[None, :]
                s = jnp.where((rows >= cols)[None, None], s, _NEG_INF)
            return s, kb

        if lse is not None:
            lse_b = jax.lax.dynamic_slice_in_dim(lse, q0, block_q, axis=2)
        else:
            def lse_step(j, carry):
                m, l = carry
                s, _ = logits(j)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                l = l * jnp.exp(m - m_new) + jnp.sum(
                    jnp.exp(s - m_new[..., None]), axis=-1)
                return m_new, l

            m0 = jnp.full((B, H, block_q), _NEG_INF, jnp.float32)
            m, l = jax.lax.fori_loop(0, n_kv, lse_step,
                                     (m0, jnp.zeros_like(m0)))
            lse_b = m + jnp.log(l)                             # [B,H,bq]

        rowterm = Db[..., None]
        if dlse is not None:
            dlse_b = jax.lax.dynamic_slice_in_dim(
                dlse.astype(jnp.float32), q0, block_q, axis=2)
            rowterm = rowterm - dlse_b[..., None]

        def grad_step(j, carry):
            dq_b, dk_acc, dv_acc = carry
            s, kb = logits(j)
            p = jnp.exp(s - lse_b[..., None])                  # [B,H,bq,bk]
            vb = jax.lax.dynamic_slice_in_dim(v32, j * block_k, block_k,
                                              axis=2)
            dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, dob)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vb)
            ds = p * (dp - rowterm) * scale
            dq_b = dq_b + jnp.einsum("bhqk,bhkd->bhqd", ds, kb)
            dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qb)

            def acc(a, upd, j=j):
                cur = jax.lax.dynamic_slice_in_dim(a, j * block_k, block_k,
                                                   axis=2)
                return jax.lax.dynamic_update_slice_in_dim(
                    a, cur + upd, j * block_k, axis=2)

            return dq_b, acc(dk_acc, dk_j), acc(dv_acc, dv_j)

        dq_b0 = jnp.zeros((B, H, block_q, Dh), jnp.float32)
        dq_b, dk_acc, dv_acc = jax.lax.fori_loop(
            0, n_kv, grad_step, (dq_b0, dk_acc, dv_acc))
        return (dk_acc, dv_acc), dq_b

    zeros = jnp.zeros((B, H, Sk, Dh), jnp.float32)
    (dk, dv), dq_blocks = jax.lax.scan(qblock, (zeros, zeros),
                                       jnp.arange(S // block_q))
    # [n_q, B, H, bq, D] -> [B, H, S, D]
    dq = jnp.moveaxis(dq_blocks, 0, 2).reshape(B, H, S, Dh)
    return dq.astype(qt.dtype), dk.astype(kt.dtype), dv.astype(vt.dtype)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, row_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *, block_q, block_k,
                      scale, causal):
    """One (batch, head) program of the backward: all of the head's q, k,
    v and dO resident in VMEM, an outer loop over k blocks that carries
    that block's dK and dV, an inner loop over the q blocks at or below
    the diagonal, dQ summed into an f32 VMEM scratch. Every block pair is
    visited ONCE: five block products, one pass over the probabilities.

    The pair is held TRANSPOSED, ``[block_k, block_q]``: the per-row
    statistics (``lse`` and ``row = rowsum(dO * O) - dLSE``) then lie
    along lanes, as they come from HBM (``[.., n_q, block_q]`` rows; a
    ``[.., S, 1]`` column is padded to 128 lanes there), and dV and dK
    are plain products; only dQ contracts over the block's rows.

    The forward's conventions: q pre-scaled in the operand dtype, MXU
    operands in the operand dtype with f32 accumulation (HIGHEST for f32
    operands), ``p`` and ``ds`` cast to the operand dtype before their
    products; the mask is evaluated only on blocks that straddle the
    diagonal, blocks above it are skipped by trip count."""
    dtype = q_ref.dtype
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    n_q = q_ref.shape[2] // block_q
    n_k = k_ref.shape[2] // block_k
    D = q_ref.shape[3]

    def dot(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=prec)

    dq_acc[...] = jnp.zeros_like(dq_acc)

    def kblock(j, _):
        k0 = pl.multiple_of(j * block_k, block_k)
        kb = k_ref[0, 0, pl.ds(k0, block_k), :]
        vb = v_ref[0, 0, pl.ds(k0, block_k), :]

        def pair(i, carry, masked):
            dk, dv = carry
            q0 = pl.multiple_of(i * block_q, block_q)
            qb = q_ref[0, 0, pl.ds(q0, block_q), :]
            dob = do_ref[0, 0, pl.ds(q0, block_q), :]
            qs = (qb.astype(jnp.float32) * scale).astype(dtype)
            st = dot(kb, qs, ((1,), (1,)))               # [BK, BQ] f32
            if masked:
                keys = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
                rows = q0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
                st = jnp.where(rows >= keys, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, 0, pl.ds(i, 1), :])
            dv = dv + dot(pt.astype(dtype), dob, ((1,), (0,)))
            dpt = dot(vb, dob, ((1,), (1,)))             # [BK, BQ] f32
            dst = (pt * (dpt - row_ref[0, 0, pl.ds(i, 1), :])).astype(dtype)
            dk = dk + dot(dst, qb, ((1,), (0,)))
            dq_acc[pl.ds(q0, block_q), :] += dot(dst, kb, ((0,), (0,)))
            return dk, dv

        zero = jnp.zeros((block_k, D), jnp.float32)
        if causal:
            # q blocks [first, n_diag) straddle the diagonal of this k
            # block, [n_diag, n_q) lie wholly below it.
            first = k0 // block_q
            n_diag = (k0 + block_k + block_q - 1) // block_q
            carry = jax.lax.fori_loop(
                first, n_diag, lambda i, c: pair(i, c, masked=True),
                (zero, zero))
            dk, dv = jax.lax.fori_loop(
                n_diag, n_q, lambda i, c: pair(i, c, masked=False), carry)
        else:
            dk, dv = jax.lax.fori_loop(
                0, n_q, lambda i, c: pair(i, c, masked=False), (zero, zero))
        dk_ref[0, 0, pl.ds(k0, block_k), :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, pl.ds(k0, block_k), :] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n_k, kblock, 0)
    dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_impl(qt, kt, vt, do, lse, row, causal, block_q, block_k):
    """The backward pallas call on [B, H, S, D] operands; ``lse`` and
    ``row`` [B, H, Sq] f32. Returns (dq, dk, dv) in the operand dtypes.
    Named apart from the forward (the compiled program would otherwise
    name it after the jitted function around it, which is the name the
    benchmark's forward reader counts)."""
    B, H, S, D = qt.shape
    Sk = kt.shape[2]
    assert not causal or S == Sk, (S, Sk)
    kernel = functools.partial(_flash_bwd_kernel, block_q=block_q,
                               block_k=block_k, scale=1.0 / (D ** 0.5),
                               causal=causal)

    def head(s):
        return pl.BlockSpec((1, 1, s, D), lambda b, h: (b, h, 0, 0),
                            memory_space=pltpu.VMEM)

    lanes = (B, H, S // block_q, block_q)       # lse and row: S on lanes
    stats = pl.BlockSpec((1, 1) + lanes[2:], lambda b, h: (b, h, 0, 0),
                         memory_space=pltpu.VMEM)
    # What a program holds: q, dO, dQ over S and k, v, dK, dV over Sk,
    # double-buffered and padded to 128 lanes, the f32 dQ scratch, and
    # the block pair's f32 temporaries.
    padded = -(-D // 128) * 128
    vmem = (2 * 3 * (S + Sk) * padded * qt.dtype.itemsize
            + S * padded * 4 + 8 * block_q * block_k * 4)
    return pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=[head(S), head(Sk), head(Sk), head(S), stats, stats],
        out_specs=[head(S), head(Sk), head(Sk)],
        out_shape=[_out_struct(x.shape, x.dtype, qt, kt, vt, do)
                   for x in (qt, kt, vt)],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(32 << 20, vmem)),
        name="attn_bwd",
        interpret=not backend.on_tpu(),
    )(qt, kt, vt, do, lse.reshape(lanes), row.reshape(lanes))


def _flash_bwd(qt, kt, vt, o, do, causal, block_q, block_k, lse=None,
               dlse=None):
    """The backward rule of both custom VJPs, chosen by shape: the Pallas
    kernel wherever a head is resident (below the streaming forward's
    lengths) and the forward emitted its lse, else the blockwise path."""
    if lse is None or max(qt.shape[2], kt.shape[2]) >= _STREAMING_MIN:
        return _flash_bwd_blockwise(qt, kt, vt, o, do, causal, block_q,
                                    block_k, lse=lse, dlse=dlse)
    row = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        row = row - dlse.astype(jnp.float32)
    return _flash_bwd_impl(qt, kt, vt, do, lse, row, causal, block_q,
                           block_k)


def _flash_vjp_bwd(causal, block_q, block_k, streaming, res, do):
    qt, kt, vt, o, lse = res
    return _flash_bwd(qt, kt, vt, o, do, causal, block_q, block_k, lse=lse)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# -- LSE-emitting variant (the building block of ring attention) -----------


def _flash_lse_fwd_impl(qt, kt, vt, causal, block_q, block_k):
    """Packed pallas call on [B, H, Sq, D] / [B, H, Sk, D] operands ->
    f32 [B, H, Sq, D+1] (normalized output ‖ row logsumexp). Sk may differ
    from Sq in the non-causal case (ring/cross blocks)."""
    B, H, S, D = qt.shape
    Sk = kt.shape[2]
    assert not causal or S == Sk, (S, Sk)
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, scale=scale, causal=causal,
                               emit_lse=True)
    packed = pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D + 1),
                               lambda b, h, i: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct((B, H, S, D + 1), jnp.float32, qt, kt, vt),
        interpret=not backend.on_tpu(),
    )(qt, kt, vt)
    return packed[..., :D].astype(qt.dtype), packed[..., D]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_lse(qt, kt, vt, causal, block_q, block_k):
    return _flash_lse_fwd_impl(qt, kt, vt, causal, block_q, block_k)


def _flash_lse_vjp_fwd(qt, kt, vt, causal, block_q, block_k):
    o, lse = _flash_lse_fwd_impl(qt, kt, vt, causal, block_q, block_k)
    return (o, lse), (qt, kt, vt, o, lse)


def _flash_lse_vjp_bwd(causal, block_q, block_k, res, cts):
    do, dlse = cts
    qt, kt, vt, o, lse = res
    return _flash_bwd(qt, kt, vt, o, do, causal, block_q, block_k, lse=lse,
                      dlse=dlse)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "streaming"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, streaming: bool | None = None):
    """Flash attention, [B, S, H, D] in / [B, S, H, D] out. Differentiable
    (custom VJP; see module docstring).

    D rides the lane dimension as-is (Mosaic handles sub-128 lane widths;
    padding to 128 would double both FLOPs and HBM traffic for the common
    D=64). Block sizes shrink to the largest divisor of S when S isn't a
    multiple of the requested block. On a real TPU, S must be a multiple
    of 128 (Mosaic tiling; ``auto_attention`` guards this) — interpret
    mode (any non-TPU backend) accepts any S that divides by 8.

    ``streaming`` selects the k-grid kernel that holds only ONE K/V tile
    in VMEM (double-buffered DMA) instead of the whole K/V — required
    past the resident kernel's ~S=8k VMEM ceiling. ``None`` -> auto: on
    for S >= 16384.
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if streaming is None:
        streaming = Sk >= _STREAMING_MIN
    fit_q = _fit_blocks(S, block_q, block_k)
    fit_k = _fit_blocks(Sk, block_q, block_k)
    if fit_q is None or fit_k is None:
        _warn_dense_fallback(S, Sk)
        return attention_reference(q, k, v, causal=causal)
    block_q, block_k = fit_q[0], fit_k[1]

    def to_bhsd(x):
        return jnp.transpose(x, (0, 2, 1, 3))            # [B, H, S, D]

    out = _flash(to_bhsd(q), to_bhsd(k), to_bhsd(v), causal, block_q,
                 block_k, streaming)
    return jnp.transpose(out, (0, 2, 1, 3))              # [B, S, H, D]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_lse(q, k, v, causal: bool = True, block_q: int = 512,
                        block_k: int = 512):
    """Flash attention that also returns the row logsumexp — the merge
    state sequence-parallel strategies need. [B, S, H, D] in; returns
    ``(o [B, S, H, D], lse [B, H, S] f32)`` where ``lse[b,h,s] =
    log sum_k exp(q·k/sqrt(D))`` over the visible keys. Two partial
    results merge exactly:
      ``lse = logaddexp(lse1, lse2); o = o1*exp(lse1-lse) + o2*exp(lse2-lse)``
    Differentiable in both outputs (custom VJP; the backward reuses the
    emitted lse instead of recomputing it, and folds the lse cotangent
    into dS — see _flash_bwd). Same shape rules as
    :func:`flash_attention`, except K/V sequence length may differ from
    Q's in the non-causal case (ring/cross attention blocks).
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    fit_q = _fit_blocks(S, block_q, block_k)
    fit_k = _fit_blocks(Sk, block_q, block_k)
    if fit_q is None or fit_k is None:
        _warn_dense_fallback(S, Sk)
        return _reference_lse(q, k, v, causal=causal)
    block_q, block_k = fit_q[0], fit_k[1]

    def to_bhsd(x):
        return jnp.transpose(x, (0, 2, 1, 3))            # [B, H, S, D]

    o, lse = _flash_lse(to_bhsd(q), to_bhsd(k), to_bhsd(v), causal,
                        block_q, block_k)
    return jnp.transpose(o, (0, 2, 1, 3)), lse           # [B, S, H, D]


# --------------------------------------------------------------------------
# Rows of a longer sequence against all of its keys (appended: the lines
# above, which the kernels' cache keys hold, stay where they were)


def _flash_rows_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                       block_q, block_k, scale, q_offset):
    """:func:`_flash_stream_kernel` for queries that are rows
    ``q_offset ..`` of the keys' sequence: grid step ``(head, q block,
    K block)``, causal on ABSOLUTE positions (row ``q_offset + r`` sees
    columns ``<= q_offset + r``), K blocks wholly above a q block's
    last row skipped, only those that straddle its diagonal masked."""
    i, j = pl.program_id(1), pl.program_id(2)
    row0 = q_offset + i * block_q

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def fold(masked):
        q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
        m_scr[:], l_scr[:], acc_scr[:] = _online_softmax_step(
            q, k_ref[0], v_ref[0], m_scr[:], l_scr[:], acc_scr[:], row0,
            j * block_k, masked, jax.lax.Precision.DEFAULT)

    below = (j + 1) * block_k - 1 <= row0       # every column seen by all
    pl.when(below)(lambda: fold(False))
    pl.when(jnp.logical_not(below)
            & (j * block_k <= row0 + block_q - 1))(lambda: fold(True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_offset", "scale", "block_q",
                                             "block_k"))
def flash_rows_attention(q, k, v, q_offset: int = 0, scale=None,
                         block_q: int = 512, block_k: int = 512):
    """Causal attention of the rows ``q_offset .. q_offset + S - 1`` of a
    sequence against ALL of its keys: q ``[H, S, D]``, k ``[H, Sk, D]``,
    v ``[H, Sk, Dv]`` (heads first, one sequence; ``Sk >= q_offset +
    S``; key columns past a row's own position, padding among them, are
    masked) -> ``[H, S, Dv]``. A whole-sequence prefill is ``q_offset``
    0 with ``S == Sk``; a prefill behind cached history is the suffix's
    rows against history + suffix. K/V stream a ``[block_k, D]`` tile a
    grid step (no whole-head residency: a head of 8192 x 192 does not
    fit beside its double buffer), so the length is bounded by HBM.
    ``scale``: the scores' factor, ``1 / sqrt(D)`` unless given. S and
    Sk must divide into the blocks (multiples of 128 on the chip, of 8
    in interpret mode): the caller pads."""
    H, S, D = q.shape
    Sk, Dv = k.shape[1], v.shape[2]
    fit = _fit_blocks(S, block_q, block_q), _fit_blocks(Sk, block_k, block_k)
    assert fit[0] and fit[1] and Sk >= q_offset + S, (S, Sk, q_offset)
    block_q, block_k = fit[0][0], fit[1][1]
    kernel = functools.partial(
        _flash_rows_kernel, block_q=block_q, block_k=block_k,
        scale=1.0 / D ** 0.5 if scale is None else scale, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=(H, S // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), lambda h, i, j: (h, i, 0)),
        out_shape=_out_struct((H, S, Dv), q.dtype, q, k, v),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=not backend.on_tpu(),
        name="flash_rows_attention",    # what the device trace prints
    )(q, k, v)



# --------------------------------------------------------------------------
# Block-causal attention (appended, as the rows kernel above: the lines
# before it stay where they were)


def block_causal_reference(q, k, v, block: int):
    """Dense-mask attention, [B, S, H, D] layout, f32 softmax: row ``i``
    sees column ``j`` where ``j // block <= i // block`` (every earlier
    position and the WHOLE of its own block: generation by diffusion
    over blocks denoises a block's positions together)."""
    S, d = q.shape[1], q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    at = jnp.arange(S) // block
    logits = jnp.where((at[:, None] >= at[None, :])[None, None],
                       logits / jnp.sqrt(d), _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def block_causal_flash(q, k, v, block: int):
    """:func:`block_causal_reference` through the causal flash kernel:
    the block-causal mask is the causal one and, inside each block, the
    columns AFTER a row (at most ``block - 1`` of them). The kernel gives
    the causal part with its row logsumexp (:func:`flash_attention_lse`),
    the rest is a ``[block, block]`` product a block, and the two merge
    exactly by their logsumexps (that function's docstring). No kernel
    of its own: a trace shows ``flash_attention_lse``."""
    B, S, H, D = q.shape
    assert S % block == 0, (S, block)
    o1, lse1 = flash_attention_lse(q, k, v, causal=True)
    n = S // block
    qb, kb, vb = (t.reshape(B, n, block, H, D) for t in (q, k, v))
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kb).astype(
        jnp.float32) / jnp.sqrt(D)
    after = jnp.arange(block)[:, None] < jnp.arange(block)[None, :]
    s = jnp.where(after, s, _NEG_INF)
    lse2 = jax.scipy.special.logsumexp(s, axis=-1)        # [B, n, H, blk]
    o2 = jnp.einsum("bnhqk,bnkhd->bnqhd",
                    jnp.exp(s - lse2[..., None]), vb.astype(jnp.float32))
    lse2 = lse2.transpose(0, 2, 1, 3).reshape(B, H, S)
    lse = jnp.logaddexp(lse1, lse2)

    def weight(part):                                     # [B, S, H, 1]
        return jnp.exp(part - lse).transpose(0, 2, 1)[..., None]
    # (a block's last row has no column after it: its ``lse2`` is the
    # mask's -1e30 and its weight 0.0 on a finite ``o2``)
    o = (o1.astype(jnp.float32) * weight(lse1)
         + o2.reshape(B, S, H, D) * weight(lse2))
    return o.astype(q.dtype)


def select_block_attention(use_flash, block: int):
    """:func:`select_attention` for the block-causal mask: ``None`` ->
    the flash kernel where :func:`auto_attention` takes it (on a TPU, S
    >= 1024 and a multiple of 128), ``True`` -> always, ``False`` -> the
    dense reference. The returned callable takes ``(q, k, v)``."""
    def auto(q, k, v):
        S = q.shape[1]
        flash = backend.on_tpu() and S >= 1024 and S % 128 == 0
        return (block_causal_flash if flash
                else block_causal_reference)(q, k, v, block)
    if use_flash is None:
        return auto
    return functools.partial(
        block_causal_flash if use_flash else block_causal_reference,
        block=block)


# The two residuals of plain flash attention's backward that only the
# forward kernel can produce, as a checkpoint policy names them
# (``jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)``:
# train.make_stage_fn). Down here with its counter so that no kernel
# above moves (a Mosaic program's cache key holds its line numbers).
FLASH_RESIDUALS = ("flash_o", "flash_lse")

_residuals_named = [0]


def flash_residuals_named_traced() -> int:
    """How many times this process has TRACED :func:`flash_attention`'s
    forward rule, which names its residuals (:data:`FLASH_RESIDUALS`):
    none under inference, and once for all the differentiated calls of
    one shape (``jit`` keeps the trace): all or nothing per program."""
    return _residuals_named[0]


def _fwd_named(qt, kt, vt, o, lse=None):
    """What :func:`_flash_vjp_fwd` returns, ``o`` and ``lse`` under their
    names: ``o`` AFTER the cut from the packed output (the packed
    float32 array is not what a policy keeps) and as ``[B, S, H*D]``
    rows, the layout the caller reads it in (as ``[B, H, S, D]`` a scan
    stacks a 64-wide head padded to 128 lanes, twice the bytes; the
    transposes here cancel against :func:`flash_attention`'s own);
    ``lse`` f32 ``[B, H, S]`` or None (streaming). The primal output is
    the NAMED ``o``, or a remat pass would run the kernel again for it.
    Outside a ``jax.checkpoint`` a name is the identity."""
    from jax.ad_checkpoint import checkpoint_name
    _residuals_named[0] += 1
    B, H, S, D = o.shape
    rows = checkpoint_name(o.transpose(0, 2, 1, 3).reshape(B, S, H * D),
                           FLASH_RESIDUALS[0])
    o = rows.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    if lse is not None:
        lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return o, (qt, kt, vt, o, lse)
