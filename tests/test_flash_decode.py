"""Interpret-mode parity for the Pallas flash-decode kernel.

ops/flash_decode.py runs interpreted on CPU (pallas_call interpret
mode), so these tests pin the kernel's MATH — GQA rows, window masking,
per-slot positions, int8 scale-on-scores — against
:func:`dense_decode_attend`, the dense reference. Whether the chip's
compiler takes the kernel is tests/test_tpu_compile.py's business.
``block_k=32`` on a 96-long cache forces multiple K/V blocks so the
live-block bound and the straddle mask are actually exercised (the
default block_k would cover the toy cache with one block).

The block-skip test is the length-aware claim itself: tail blocks past
``pos + W`` are filled with NaN — if the kernel read them, the online
softmax would poison every output lane.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_acx_tpu import backend
from mpi_acx_tpu.models.decoding import (dense_decode_attend,
                                         grouped_decode_attend,
                                         to_cache_layout)
from mpi_acx_tpu.ops import attention
from mpi_acx_tpu.ops.flash_decode import (_fit_block_k, auto_decode_attend,
                                          flash_decode_attend,
                                          select_decode_attend)
from mpi_acx_tpu.ops.kvquant import kv_quant

B, Hkv, D, MAX_LEN, BLOCK_K = 3, 2, 16, 96, 32


def _case(n_rep, W, kind, seed=0):
    """(q, kc, vc, tol): bf16 arrays or f32 q + (codes, scales) caches,
    in cache layout [B, Hkv, D, MAX_LEN] (scales [B, Hkv, 1, MAX_LEN])."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, W, Hkv * n_rep, D))
    kc = rng.standard_normal((B, MAX_LEN, Hkv, D))
    vc = rng.standard_normal((B, MAX_LEN, Hkv, D))
    if kind == "int8":
        # f32 q against (int8 codes, f32 scales) tuple caches; both
        # paths dequantize exactly, tolerance is accumulation order.
        q = jnp.asarray(q, jnp.float32)
        kc = tuple(map(to_cache_layout,
                       kv_quant(jnp.asarray(kc, jnp.float32))))
        vc = tuple(map(to_cache_layout,
                       kv_quant(jnp.asarray(vc, jnp.float32))))
        return q, kc, vc, 2e-4
    q = jnp.asarray(q, jnp.bfloat16)
    kc = to_cache_layout(jnp.asarray(kc, jnp.bfloat16))
    vc = to_cache_layout(jnp.asarray(vc, jnp.bfloat16))
    return q, kc, vc, 4e-2


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("posmode", ["scalar", "vector"])
@pytest.mark.parametrize("n_rep", [1, 4])
@pytest.mark.parametrize("W", [1, 4])
def test_flash_matches_dense(W, n_rep, posmode, kind):
    q, kc, vc, tol = _case(n_rep, W, kind)
    if posmode == "scalar":
        pos = 41                                  # mid-straddle-block
    else:
        # Slot 0 empty-but-self, slot at a block edge, slot at the end.
        pos = jnp.array([0, 63, MAX_LEN - W], jnp.int32)
    ref = dense_decode_attend(q, kc, vc, pos, MAX_LEN, n_rep)
    out = flash_decode_attend(q, kc, vc, pos, MAX_LEN, n_rep,
                              block_k=BLOCK_K)
    assert out.shape == ref.shape == (B, W, Hkv * n_rep * D)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_block_skip_ignores_dead_tail(kind):
    """Cache rows past pos+W never cross the DMA: NaN-poison them and
    the output must be bit-identical to the clean cache's."""
    W, n_rep, pos = 2, 2, 40                      # live rows: 0..41
    q, kc, vc, tol = _case(n_rep, W, kind)
    live = 64                                     # first dead BLOCK col

    def poison(c):
        if isinstance(c, tuple):
            codes, scales = c
            codes = codes.at[..., live:].set(127)
            scales = scales.at[..., live:].set(jnp.nan)
            return codes, scales
        return c.at[..., live:].set(jnp.nan)

    clean = flash_decode_attend(q, kc, vc, pos, MAX_LEN, n_rep,
                                block_k=BLOCK_K)
    dirty = flash_decode_attend(q, poison(kc), poison(vc), pos, MAX_LEN,
                                n_rep, block_k=BLOCK_K)
    assert not np.isnan(np.asarray(dirty, np.float32)).any()
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(dirty, np.float32))


def test_per_slot_positions_match_solo_runs():
    """Vector-pos output for slot b equals a scalar-pos run at pos[b] —
    the continuous-batching contract (serving.py's bit-equality claim
    rides on it)."""
    q, kc, vc, tol = _case(2, 1, "bf16")
    pos = jnp.array([5, 50, 90], jnp.int32)
    batched = flash_decode_attend(q, kc, vc, pos, MAX_LEN, 2,
                                  block_k=BLOCK_K)
    for b in range(B):
        solo = flash_decode_attend(q[b:b + 1], kc[b:b + 1], vc[b:b + 1],
                                   int(pos[b]), MAX_LEN, 2,
                                   block_k=BLOCK_K)
        np.testing.assert_array_equal(np.asarray(batched[b:b + 1]),
                                      np.asarray(solo))


def test_select_decode_attend_dispatch():
    """The select_attention idiom: False -> dense, True -> kernel,
    None -> auto (dense on CPU — interpret overhead loses there)."""
    assert select_decode_attend(False) is dense_decode_attend
    assert select_decode_attend(True) is flash_decode_attend
    assert select_decode_attend(None) is auto_decode_attend
    q, kc, vc, _ = _case(1, 1, "bf16")
    np.testing.assert_array_equal(
        np.asarray(grouped_decode_attend(q, kc, vc, 7, MAX_LEN, 1),
                   np.float32),
        np.asarray(dense_decode_attend(q, kc, vc, 7, MAX_LEN, 1),
                   np.float32))


def test_fit_block_k_prefers_mosaic_tiles():
    assert _fit_block_k(4096, 256) == 256
    assert _fit_block_k(384, 256) == 128          # 128-multiple beats 192
    assert _fit_block_k(96, 256) == 96
    assert _fit_block_k(96, 32) == 32


def test_fit_blocks_fallback_warns_once_and_matches_reference():
    """S=648 has no 128-multiple divisor: flash_attention must fall back
    to the dense reference with ONE warning, not crash (the old
    AssertionError path)."""
    attention._fallback_warned.clear()
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 648, 2, 16)),
                           jnp.float32) for _ in range(3))
    with pytest.warns(RuntimeWarning, match="dense reference"):
        out = attention.flash_attention(q, k, v)
    ref = attention.attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    attention._fallback_warned.clear()            # shared one-time set
    with pytest.warns(RuntimeWarning, match="dense reference"):
        o_lse, lse = attention.flash_attention_lse(q, k, v)
    np.testing.assert_allclose(np.asarray(o_lse), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert lse.shape == (1, 2, 648)

    # One-time: the same shape does not warn again.
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        attention.flash_attention(q, k, v)
