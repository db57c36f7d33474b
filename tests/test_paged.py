"""Paged KV cache + radix prefix sharing + page-pressure scheduling
(models/kvpage.py, serve_paged_greedy, the paged flash-decode arm —
docs/DESIGN.md §19).

The load-bearing claim is BIT-equality: a slot whose pages hold the
fixed cache's rows must attend identically (paged_gather_attend
reshapes into the exact dense layout; the paged Pallas kernel at
``block_k == page_tokens`` runs the fixed kernel's FLOP sequence), and
``serve_paged_greedy`` must reproduce fixed-slot ``serve_greedy``
token for token — including across a page-pressure preemption, whose
replay re-lands on the same deterministic page placement. Prefix-hit
prefills use different tensor shapes than cold ones, so the sharing
tests assert determinism and page *reuse* (the HBM claim), not
bitwise identity with the cold path.

Everything runs on CPU: the gather path is plain jnp, the Pallas
kernel runs in interpret mode (the same discipline as
tests/test_flash_decode.py).
"""

import functools

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_acx_tpu.models import kvpage
from mpi_acx_tpu.models import serving
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.models.decoding import (dense_decode_attend,
                                         to_cache_layout)
from mpi_acx_tpu import backend, profiling
from mpi_acx_tpu.ops import flash_decode
from mpi_acx_tpu.ops.flash_decode import (flash_decode_attend,
                                          paged_flash_decode_attend,
                                          paged_gather_attend,
                                          paged_kv_write,
                                          paged_kv_write_dense,
                                          paged_kv_write_runs,
                                          select_paged_decode_attend,
                                          select_paged_kv_write)
from mpi_acx_tpu.ops.kvquant import kv_quant

B, Hkv, D, MAX_LEN, PT = 3, 2, 16, 96, 32       # max_pages = 3


# --------------------------------------------------------------------------
# kernel-level parity: paged attend vs the fixed-cache references


def _fixed_case(n_rep, W, kind, seed=0):
    """(q, kc, vc): the fixed-slot [B, Hkv, D, MAX_LEN] caches of
    tests/test_flash_decode.py, bf16 or (int8 codes, f32 scales)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, W, Hkv * n_rep, D))
    kc = rng.standard_normal((B, MAX_LEN, Hkv, D))
    vc = rng.standard_normal((B, MAX_LEN, Hkv, D))
    if kind == "int8":
        q = jnp.asarray(q, jnp.float32)
        kc = tuple(map(to_cache_layout,
                       kv_quant(jnp.asarray(kc, jnp.float32))))
        vc = tuple(map(to_cache_layout,
                       kv_quant(jnp.asarray(vc, jnp.float32))))
        return q, kc, vc
    return (jnp.asarray(q, jnp.bfloat16),
            to_cache_layout(jnp.asarray(kc, jnp.bfloat16)),
            to_cache_layout(jnp.asarray(vc, jnp.bfloat16)))


def _paginate(kc, vc, shared_prefix=False):
    """Slice fixed caches into a page pool + block table holding the
    SAME rows. ``shared_prefix=True`` makes every slot's first page one
    aliased pool page (their row contents are first made identical) —
    the layout a radix-cache hit produces."""
    def split(c):
        # [B, Hkv, *, MAX_LEN] -> [B*max_pages, Hkv, *, PT]
        c = c.reshape(*c.shape[:3], MAX_LEN // PT, PT)
        return jnp.moveaxis(c, 3, 1).reshape(
            B * (MAX_LEN // PT), *c.shape[1:3], PT)

    max_pages = MAX_LEN // PT
    table = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    if shared_prefix:
        if isinstance(kc, tuple):
            kc = (kc[0].at[..., :PT].set(kc[0][0, ..., :PT]),
                  kc[1].at[..., :PT].set(kc[1][0, ..., :PT]))
            vc = (vc[0].at[..., :PT].set(vc[0][0, ..., :PT]),
                  vc[1].at[..., :PT].set(vc[1][0, ..., :PT]))
        else:
            kc = kc.at[..., :PT].set(kc[0, ..., :PT])
            vc = vc.at[..., :PT].set(vc[0, ..., :PT])
        table[:, 0] = 0                           # alias slot 0's page
    pk = ((split(kc[0]), split(kc[1])) if isinstance(kc, tuple)
          else split(kc))
    pv = ((split(vc[0]), split(vc[1])) if isinstance(vc, tuple)
          else split(vc))
    return kc, vc, pk, pv, jnp.asarray(table)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("posmode", ["scalar", "vector"])
@pytest.mark.parametrize("shared", [False, True],
                         ids=["prefix-miss", "prefix-hit"])
def test_paged_gather_bit_equals_dense(kind, posmode, shared):
    """paged_gather_attend over pages holding the fixed cache's rows is
    BIT-equal to dense_decode_attend on the fixed cache — private pages
    (cold/miss) and an aliased shared first page (hit) alike. This is
    the anchor the whole §19 equality chain hangs from."""
    q, kc, vc = _fixed_case(n_rep=2, W=1, kind=kind)
    kc, vc, pk, pv, table = _paginate(kc, vc, shared_prefix=shared)
    pos = 41 if posmode == "scalar" else jnp.array([33, 63, MAX_LEN - 1],
                                                   jnp.int32)
    ref = dense_decode_attend(q, kc, vc, pos, MAX_LEN, 2)
    out = paged_gather_attend(q, pk, pv, table, pos, PT, 2)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("posmode", ["scalar", "vector"])
# The prefix-hit variants differ only in page aliasing, which the cheap
# gather grid above already pins; keep the kernel leg of the tier-1
# sweep to the miss grid and run the full cross in `make paged-check`.
@pytest.mark.parametrize("shared", [
    False,
    pytest.param(True, marks=pytest.mark.slow),
], ids=["prefix-miss", "prefix-hit"])
def test_paged_flash_bit_equals_fixed_flash(kind, posmode, shared):
    """The paged Pallas kernel at block size == page size runs the
    fixed kernel's exact FLOP sequence — outputs are BIT-equal to
    flash_decode_attend(block_k=PT) on the same rows (interpret mode
    on CPU, same discipline as test_flash_decode.py)."""
    q, kc, vc = _fixed_case(n_rep=2, W=1, kind=kind, seed=7)
    kc, vc, pk, pv, table = _paginate(kc, vc, shared_prefix=shared)
    pos = 50 if posmode == "scalar" else jnp.array([0, 41, 77], jnp.int32)
    ref = flash_decode_attend(q, kc, vc, pos, MAX_LEN, 2, block_k=PT)
    out = paged_flash_decode_attend(q, pk, pv, table, pos, PT, 2)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_select_paged_decode_attend_dispatch():
    """Same contract as select_decode_attend: None -> auto, True ->
    kernel, False -> gather reference."""
    assert select_paged_decode_attend(True, PT) is paged_flash_decode_attend
    assert select_paged_decode_attend(False, PT) is paged_gather_attend
    auto = select_paged_decode_attend(None, PT)
    assert auto is paged_gather_attend                   # off the chip
    q, kc, vc = _fixed_case(n_rep=1, W=1, kind="bf16")
    _, _, pk, pv, table = _paginate(kc, vc)
    out = auto(q, pk, pv, table, 10, PT, 1)
    assert out.shape == (B, 1, Hkv * D)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("posmode", ["scalar", "vector"])
def test_whole_pool_attend_bit_equals_per_layer(kind, posmode):
    """Handed the WHOLE pool [L, P, ...] and a layer index (traced, as
    in the layer scan), both paged attends read that layer's pages and
    no other's: bit-equal to the same call on the layer alone."""
    q, kc, vc = _fixed_case(n_rep=2, W=1, kind=kind, seed=11)
    _, _, pk, pv, table = _paginate(kc, vc)
    pos = 50 if posmode == "scalar" else jnp.array([0, 41, 77], jnp.int32)
    rng = np.random.default_rng(5)

    def whole(layer_pool, at):
        """3 layers, ``layer_pool`` at index ``at``, noise elsewhere."""
        def stack(p):
            noise = jnp.asarray(rng.integers(-100, 100, (3,) + p.shape),
                                p.dtype)
            return noise.at[at].set(p)
        return jax.tree.map(stack, layer_pool)

    for attend in (paged_flash_decode_attend, paged_gather_attend):
        ref = attend(q, pk, pv, table, pos, PT, 2)
        got = jax.jit(lambda k, v, i: attend(q, k, v, table, pos, PT, 2,
                                             layer=i))(
            whole(pk, 1), whole(pv, 1), jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32),
                                      err_msg=attend.__name__)


# --------------------------------------------------------------------------
# the live-page walk: one pass over each slot's pages, copied out of the
# pool by the kernel itself, the next slot's first page in flight while
# the last page of this one is folded

WALK_PAGES = 8
# Slot by slot: an idle slot at pos 0 whose whole table row is its
# parking page; 1 page then all 8 then 1 again (the cross-slot
# prefetch, both ways round); a horizon that ends ON a page boundary
# (pos + 1 == 2 pages exactly) and one a token past it (one visible
# column of page 4); the last position of the cache.
WALK_POS = [0, 5, WALK_PAGES * PT - 2, PT - 1, 2 * PT - 1, 3 * PT,
            WALK_PAGES * PT - 1]
WALK_IDLE = 0


def _walk_case(kind, n_rep, with_layer, seed=3):
    """(q, pools k and v, table, pos, layer, fixed caches): random
    pools [L, P, Hkv, *, PT] and a ragged table over them; every
    table column past a slot's live pages points at a NaN page (bf16)
    or a page of extreme codes (int8), which a walk that touched a dead
    page would fold in. The fixed caches hold the same rows, gathered
    page by page."""
    rng = np.random.default_rng(seed)
    nb, L = len(WALK_POS), 3 if with_layer else 1
    P = nb * WALK_PAGES + nb + 1                 # + parking + the dead page
    dead, park = P - 1, nb * WALK_PAGES + WALK_IDLE
    shape = (L, P, Hkv, D, PT)
    if kind == "int8":
        def pool():
            codes = rng.integers(-127, 128, shape)
            codes[:, dead] = 127
            scales = rng.random((L, P, Hkv, 1, PT), np.float32) + 0.01
            scales[:, dead] = 1e30
            return jnp.asarray(codes, jnp.int8), jnp.asarray(scales)
        q = jnp.asarray(rng.standard_normal((nb, 1, Hkv * n_rep, D)),
                        jnp.float32)
    else:
        def pool():
            vals = rng.standard_normal(shape).astype(np.float32)
            vals[:, dead] = np.nan
            return jnp.asarray(vals, jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((nb, 1, Hkv * n_rep, D)),
                        jnp.bfloat16)
    kp, vp = pool(), pool()
    pos = np.asarray(WALK_POS, np.int32)
    table = rng.permutation(nb * WALK_PAGES).reshape(
        nb, WALK_PAGES).astype(np.int32)
    table[WALK_IDLE] = park
    live = pos // PT + 1
    for b in range(nb):
        if b != WALK_IDLE:
            table[b, live[b]:] = dead
    layer = 1 if with_layer else None

    def fixed(pool):
        lyr = pool[layer if with_layer else 0]
        t = jnp.moveaxis(jnp.take(lyr, jnp.asarray(table), axis=0), 1, 3)
        return t.reshape(t.shape[:3] + (WALK_PAGES * PT,))

    if not with_layer:
        kp, vp = jax.tree.map(lambda p: p[0], (kp, vp))
    caches = tuple(jax.tree.map(fixed, p if with_layer else
                                jax.tree.map(lambda a: a[None], p))
                   for p in (kp, vp))
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos), layer, caches


@pytest.mark.parametrize("with_layer", [False, True],
                         ids=["one-layer", "whole-pool"])
@pytest.mark.parametrize("n_rep", [1, 2])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_live_page_walk_bit_equals_the_block_grid(kind, n_rep, with_layer):
    """The paged kernel's walk (a grid step a slot, the slot's live
    pages copied out of the pool two buffers deep) against the
    contiguous kernel's ``(B, n_k)`` grid at ``block_k == page_tokens``
    on the same values: one fold, the same pages in the same order, so
    every row is BIT-equal — idle, one page, all pages, page-boundary
    horizons alike — and no dead page is folded (they hold NaN). And
    against the gather-dense reference on live rows of finite pages."""
    q, kp, vp, table, pos, layer, (kc, vc) = _walk_case(kind, n_rep,
                                                        with_layer)
    got = paged_flash_decode_attend(q, kp, vp, table, pos, PT, n_rep,
                                    layer=layer)
    assert got.shape == (len(WALK_POS), 1, Hkv * n_rep * D)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    ref = flash_decode_attend(q, kc, vc, pos, WALK_PAGES * PT, n_rep,
                              block_k=PT)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))
    # The dense reference multiplies the dead pages' NaN by 0: compare
    # it on a table whose dead columns repeat the slot's first page.
    alive = jnp.where(jnp.arange(WALK_PAGES)[None] <= (pos // PT)[:, None],
                      table, table[:, :1])
    dense = paged_gather_attend(q, kp, vp, alive, pos, PT, n_rep,
                                layer=layer)
    tol = 2e-4 if kind == "int8" else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=tol, atol=tol)


def test_live_page_walk_takes_a_speculative_window():
    """W > 1 (the speculative window passes): the walk's horizon is
    ``pos + W`` and each row masks on its own position, as the block
    grid's does; bit-equal to it."""
    W, n_rep = 3, 2
    q, kc, vc = _fixed_case(n_rep=n_rep, W=W, kind="bf16", seed=13)
    kc, vc, pk, pv, table = _paginate(kc, vc)
    pos = jnp.array([0, PT - 2, MAX_LEN - W], jnp.int32)   # straddles a page
    ref = flash_decode_attend(q, kc, vc, pos, MAX_LEN, n_rep, block_k=PT)
    got = paged_flash_decode_attend(q, pk, pv, table, pos, PT, n_rep)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))


# --------------------------------------------------------------------------
# the in-place page write vs the dense scatter

N_LAYERS, MAX_PAGES = 3, MAX_LEN // PT
PARK = B * MAX_PAGES                      # slot b parks on page PARK + b

# name -> (pos [B], slots that are idle: their table row is all parking)
WRITE_CASES = {
    "uniform-off-0": ([PT, PT, PT], ()),
    "uniform-off-last": ([2 * PT - 1] * 3, ()),
    "per-slot-pos": ([0, PT + 7, 2 * PT - 1], ()),
    "idle-slot-parked": ([5, 3 * PT + 9, PT], (1,)),   # idle pos walks on
    "last-table-column": ([MAX_LEN - 1, 2 * PT, 4], ()),
}


def _write_case(kind, case, seed=0):
    """(pools, fresh, write_page, off) as paged_decode_step makes them:
    random pools [L, P, H, *, PT] (+ scale pages when int8), one fresh
    vector a slot, the slot's page and lane from its table row."""
    rng = np.random.default_rng(seed)
    pos, idle = WRITE_CASES[case]
    P = PARK + B
    shape = (N_LAYERS, P, Hkv, D, PT)
    if kind == "int8":
        def pool():
            return jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        def scales(*lead):
            return jnp.asarray(rng.random(lead, np.float32) + 0.01)
        pools = (pool(), pool(), scales(N_LAYERS, P, Hkv, 1, PT),
                 scales(N_LAYERS, P, Hkv, 1, PT))
        fk, fks = kv_quant(jnp.asarray(
            rng.standard_normal((B, 1, Hkv, D)), jnp.float32))
        fv, fvs = kv_quant(jnp.asarray(
            rng.standard_normal((B, 1, Hkv, D)), jnp.float32))
        fresh = (fk, fv, fks, fvs)
    else:
        pools = tuple(jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                      for _ in range(2))
        fresh = tuple(jnp.asarray(rng.standard_normal((B, 1, Hkv, D)),
                                  jnp.bfloat16).at[0, 0, 0, 0].set(-0.0)
                      for _ in range(2))
    table = np.arange(PARK, dtype=np.int32).reshape(B, MAX_PAGES)
    for b in idle:
        table[b] = PARK + b
    pos = jnp.asarray(pos, jnp.int32)
    write_page = jnp.take_along_axis(
        jnp.asarray(table), jnp.minimum(pos // PT, MAX_PAGES - 1)[:, None],
        axis=1)[:, 0]
    return pools, fresh, write_page, pos % PT


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("case", list(WRITE_CASES))
def test_paged_kv_write_bit_equals_dense_and_touches_one_lane(kind, case):
    """The page-write kernel (interpret mode) leaves every pool bit-equal
    to the dense ``.at[].set`` write, and differs from the pool it was
    given in nothing but lane ``off[b]`` of page ``(layer,
    write_page[b])``, which holds slot b's fresh vector."""
    pools, fresh, write_page, off = _write_case(kind, case)
    layer = 1
    ref = paged_kv_write_dense(pools, fresh, jnp.int32(layer), write_page,
                               off)
    got = jax.jit(paged_kv_write)(pools, fresh, jnp.int32(layer),
                                  write_page, off)
    assert len(got) == len(pools)
    for name, before, r, g, f in zip("k v ks vs".split(), pools, ref, got,
                                     fresh):
        assert g.dtype == before.dtype and g.shape == before.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32),
                                      err_msg=name)
        np.testing.assert_array_equal(                  # a -0.0 stays one
            np.signbit(np.asarray(g, np.float32)),
            np.signbit(np.asarray(r, np.float32)), err_msg=name)
        want = np.array(before, np.float32)        # a copy: writable
        for b in range(B):
            want[layer, int(write_page[b]), :, :, int(off[b])] = \
                np.asarray(f[b, 0], np.float32)
        np.testing.assert_array_equal(np.asarray(g, np.float32), want,
                                      err_msg=name + ": another page or "
                                      "lane changed")
    if case == "idle-slot-parked":
        assert int(write_page[1]) == PARK + 1
    if case == "last-table-column":
        assert int(write_page[0]) == MAX_PAGES - 1


# name -> (pos [B] at the chunk's start, idle slots): what a chunk's flush
# has to get right, at PT = 32 lanes a page
CHUNK_CASES = {
    "lane-0": ([PT, 0, 2 * PT], ()),
    "last-lane": ([PT - 1, 2 * PT - 1, PT - 1], ()),       # crosses at once
    "per-slot-pos": ([3, PT + 20, 2 * PT + 31], ()),
    "idle-slot-parked": ([5, 3 * PT + 9, PT + 30], (1,)),   # idle pos walks on
    "row-end-clipped": ([MAX_LEN - 3, 2 * PT, MAX_LEN + 40], ()),
}


def _chunk_case(kind, case, n, seed=0):
    """(pools, fresh [B, n, H, *], table, pos): ``n`` tokens a slot."""
    rng = np.random.default_rng(seed)
    pos, idle = CHUNK_CASES[case]
    pools, _, _, _ = _write_case(kind, "uniform-off-0", seed)
    x = rng.standard_normal((2, B, n, Hkv, D))
    if kind == "int8":
        (fk, fks), (fv, fvs) = (kv_quant(jnp.asarray(a, jnp.float32))
                                for a in x)
        fresh = (fk, fv, fks, fvs)
    else:
        fresh = tuple(jnp.asarray(a, jnp.bfloat16).at[0, 0, 0, 0].set(-0.0)
                      for a in x)
    table = np.arange(PARK, dtype=np.int32).reshape(B, MAX_PAGES)
    for b in idle:
        table[b] = PARK + b
    return pools, fresh, jnp.asarray(table), jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("n", [1, 5, PT, PT + 1, 2 * PT + 7])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_paged_kv_write_of_a_chunk_equals_its_tokens_one_at_a_time(kind, case,
                                                                   n):
    """``paged_kv_write`` with ``n`` tokens a slot (a decode chunk's
    flush; interpret mode) and its dense twin leave every pool bit-equal
    to ``n`` one-token writes at ``pos, pos + 1, ...``: across a page
    boundary, from lane 0 and from the last lane, into an idle slot's
    parking page only, both halves into the last page of a table row
    that ends, a -0.0 kept; no other page or lane changes. More tokens
    than a page go in runs of a page (``paged_kv_write_runs``): three
    or more pages a slot, and where runs share a page (parking, a
    clipped row end) the later token stays."""
    pools, fresh, table, pos = _chunk_case(kind, case, n)
    layer = jnp.int32(1)
    want = pools
    for t in range(n):
        at = pos + t
        page = jnp.take_along_axis(
            table, jnp.minimum(at // PT, MAX_PAGES - 1)[:, None], axis=1)
        want = paged_kv_write_dense(want, [f[:, t:t + 1] for f in fresh],
                                    layer, page[:, 0], at % PT)
    pages = jnp.concatenate([
        flash_decode.chunk_write_pages(table, pos + s, min(PT, n - s), PT)
        for s in range(0, n, PT)], axis=1)
    assert pages.shape == (B, 2 * (n // PT) + min(n % PT, 2))   # two a run
    for write in (paged_kv_write, paged_kv_write_dense):
        if n <= PT:
            got = jax.jit(write)(pools, fresh, layer, pages, pos % PT)
        else:
            got = jax.jit(functools.partial(
                paged_kv_write_runs, write, page_tokens=PT))(
                    pools, fresh, layer, table, pos)
        for name, g, w in zip("k v ks vs".split(), got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            np.testing.assert_array_equal(g, w, err_msg=name)
            np.testing.assert_array_equal(np.signbit(g), np.signbit(w),
                                          err_msg=name)
    # what the one-token writes left alone the flush never saw: pages
    # outside ``pages``, and of an idle slot all but its parking page
    touched = set(np.asarray(pages).ravel().tolist())
    for b in CHUNK_CASES[case][1]:
        assert set(np.asarray(pages[b]).tolist()) == {PARK + b}
    for g, before in zip(got, pools):
        for p in set(range(PARK + B)) - touched:
            np.testing.assert_array_equal(
                np.asarray(g[:, p], np.float32),
                np.asarray(before[:, p], np.float32))


# (Hkv, D, n_rep): GPT-2's heads, GQA at four query heads a K/V head,
# ONE K/V head of 128 under many query heads
STAGE_SHAPES = {"mha": (2, 16, 1), "gqa4": (2, 16, 4), "mqa128": (1, 128, 5)}


@pytest.mark.parametrize("chunk", [5, 19])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("shape", list(STAGE_SHAPES))
def test_staged_attend_reads_the_pool_then_the_stage(kind, shape, chunk):
    """The attend with a chunk's stage: pool + stage, neither written
    into the other. The gather reference lays the staged tokens over
    its gathered rows and is BIT-equal to the same attend after
    one-token dense writes; the live-page walk (interpret mode) masks
    the pool at the slot's position at the chunk's start and folds the
    stage as one more block, equal to numerics. Slots start at lane 0,
    cross a page boundary, and one idles on its parking page; a chunk
    of 19 at pages of 8 spans three pages a slot and walks the idle
    slot past its row's end."""
    Hk, Dh, n_rep = STAGE_SHAPES[shape]
    rng = np.random.default_rng(5)
    L, pt, layer = 2, 8, jnp.int32(1)
    max_pages = 4
    P = B * max_pages + B
    table = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    table[1] = B * max_pages + 1                         # idle: parked
    table = jnp.asarray(table)
    pos0 = jnp.asarray([0, 19, 7], jnp.int32)
    if kind == "int8":
        pools = tuple(jnp.asarray(rng.integers(-127, 128, (L, P, Hk, Dh, pt)),
                                  jnp.int8) for _ in range(2)) + tuple(
            jnp.asarray(rng.random((L, P, Hk, 1, pt), np.float32) * 0.02
                        + 0.01) for _ in range(2))
        qdt, tol = jnp.float32, 2e-5
    else:
        pools = tuple(jnp.asarray(rng.standard_normal((L, P, Hk, Dh, pt)),
                                  jnp.bfloat16) for _ in range(2))
        qdt, tol = jnp.bfloat16, 2e-2

    def kv(ps):
        return (((ps[0], ps[2]), (ps[1], ps[3])) if kind == "int8"
                else (ps[0], ps[1]))

    stage = flash_decode.new_kv_stage(pools, B, chunk)
    assert stage[0].shape == (L, B, chunk, Hk, 2 * Dh)   # a token: V then K
    assert len(stage) == (3 if kind == "int8" else 1)
    written = pools
    for step in range(chunk):
        x = rng.standard_normal((2, B, 1, Hk, Dh))
        if kind == "int8":
            (k, ks), (v, vs) = (kv_quant(jnp.asarray(a, jnp.float32))
                                for a in x)
            fresh = (k, v, ks, vs)
        else:
            fresh = tuple(jnp.asarray(a, jnp.bfloat16) for a in x)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * n_rep, Dh)), qdt)
        pos, at = pos0 + step, jnp.int32(step)
        stage = flash_decode.stage_put(stage, fresh, layer, at)
        page = jnp.take_along_axis(
            table, jnp.minimum(pos // pt, max_pages - 1)[:, None],
            axis=1)[:, 0]
        written = paged_kv_write_dense(written, fresh, layer, page, pos % pt)
        want = paged_gather_attend(q, *kv(written), table, pos, pt, n_rep,
                                   layer=layer)
        dense = paged_gather_attend(q, *kv(pools), table, pos, pt, n_rep,
                                    layer=layer, stage=(stage, at))
        np.testing.assert_array_equal(np.asarray(dense, np.float32),
                                      np.asarray(want, np.float32))
        walk = paged_flash_decode_attend(q, *kv(pools), table, pos, pt,
                                         n_rep, layer=layer,
                                         stage=(stage, at))
        np.testing.assert_allclose(                      # the owning slots
            np.asarray(walk, np.float32)[[0, 2]],
            np.asarray(want, np.float32)[[0, 2]], atol=tol, rtol=tol)


# name -> left [n slots] for an attend at chunk step ``step``: a slot is
# live iff ``step < left[b]`` (None: no ``left`` at all)
LEFT_CASES = {
    "first-dead": lambda n, step: [0] + [step + 9] * (n - 1),
    "last-dead": lambda n, step: [step + 9] * (n - 1) + [0],
    "two-in-a-row": lambda n, step: [step + 1, 0, step] + [step + 1] * (n - 3),
    "all-but-one": lambda n, step: [0] * (n - 2) + [step + 1, step],
    "about-to-die": lambda n, step: [step + 1, step] * (n // 2) + [step] * (
        n % 2),
    "all-dead": lambda n, step: [0] * n,
    "none-dead": lambda n, step: [step + 1] * n,
    "absent": lambda n, step: None,
}


def _planted(arrays, where, axis):
    """``arrays`` with NaN at ``where`` along ``axis`` of every array
    that can hold one (an int8 cache's codes cannot; its scales do)."""
    idx = (slice(None),) * axis + (np.asarray(where, np.int32),)
    return tuple(a.at[idx].set(jnp.nan)
                 if jnp.issubdtype(a.dtype, jnp.floating) and len(where)
                 else a for a in arrays)


@pytest.mark.parametrize("case", list(LEFT_CASES))
@pytest.mark.parametrize("staged", [False, True], ids=["no-stage", "stage"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_dead_slots_cost_the_walk_nothing_and_read_zero(kind, staged, case):
    """The attend told how many tokens each slot still owes: a slot
    with ``step >= left[b]`` is DEAD. The walk (interpret mode) copies
    and folds nothing for it: every page of its table row and its block
    of the stage hold NaN here (NaN scales where the codes are int8)
    and no row of the result is non-finite; its row is exactly 0.0;
    every live row is BIT-equal to the same call without ``left`` on
    clean data; and the gather reference, given the same ``left``,
    zeroes the same rows and leaves its live rows bit-equal to its own
    without. First slot dead (the call's first copy is the first LIVE
    slot's), last slot dead, dead slots in a row (the prefetch behind a
    slot's last page hops them), one slot alive, none, all; a slot on
    its last token and one just past it; no ``left`` at all."""
    n_rep = 2
    q, kp, vp, table, pos, layer, _ = _walk_case(kind, n_rep, True)
    nb, quant = len(WALK_POS), kind == "int8"
    pools = (kp[0], vp[0], kp[1], vp[1]) if quant else (kp, vp)

    def kv(ps):
        return ((ps[0], ps[2]), (ps[1], ps[3])) if quant else ps

    step, stage = 0, None
    if staged:
        rng = np.random.default_rng(11)
        step = 2
        arrays = flash_decode.new_kv_stage(pools, nb, 5)
        for t in range(step + 1):
            x = rng.standard_normal((2, nb, 1, Hkv, D))
            if quant:
                (k, ks), (v, vs) = (kv_quant(jnp.asarray(a, jnp.float32))
                                    for a in x)
                fresh = (k, v, ks, vs)
            else:
                fresh = tuple(jnp.asarray(a, jnp.bfloat16) for a in x)
            arrays = flash_decode.stage_put(arrays, fresh, jnp.int32(layer),
                                            jnp.int32(t))
        stage = (arrays, jnp.int32(step))
        pos = pos + step
    left = LEFT_CASES[case](nb, step)
    live = (np.ones(nb, bool) if left is None
            else step < np.asarray(left))
    assert case in ("absent", "none-dead") or not live.all()
    # the gather reference multiplies what it gathered by 0: give it a
    # table whose dead COLUMNS repeat the slot's first page (the walk
    # never reads them: they are NaN pages)
    alive = jnp.where(jnp.arange(WALK_PAGES)[None]
                      <= (jnp.asarray(WALK_POS) // PT)[:, None],
                      table, table[:, :1])
    kw = dict(layer=layer, stage=stage)
    base = np.asarray(paged_flash_decode_attend(
        q, *kv(pools), table, pos, PT, n_rep, **kw), np.float32)
    dense = np.asarray(paged_gather_attend(
        q, *kv(pools), alive, pos, PT, n_rep, **kw), np.float32)
    assert np.isfinite(base).all() and np.isfinite(dense).all()

    dead = np.flatnonzero(~live)
    bad = _planted(pools, np.asarray(table)[dead].ravel(), 1)
    if staged:
        kw["stage"] = (_planted(stage[0], dead, 1), stage[1])
    if left is not None:
        kw["left"] = jnp.asarray(left, jnp.int32)
    got = np.asarray(paged_flash_decode_attend(
        q, *kv(bad), table, pos, PT, n_rep, **kw), np.float32)
    np.testing.assert_array_equal(got[live], base[live])
    assert (got[~live] == 0.0).all() and not np.signbit(got[~live]).any()
    ref = np.asarray(paged_gather_attend(
        q, *kv(bad), alive, pos, PT, n_rep, **kw), np.float32)
    np.testing.assert_array_equal(ref[live], dense[live])
    assert (ref[~live] == 0.0).all()
    # walk against gather, where the stage's tokens stay inside the
    # slot's last live page (the aliased table has no next one to take
    # them; test_staged_attend_reads_the_pool_then_the_stage crosses)
    fits = np.asarray(WALK_POS) % PT + step < PT
    tol = 2e-4 if quant else 4e-2
    np.testing.assert_allclose(got[fits], ref[fits], rtol=tol, atol=tol)


@pytest.mark.parametrize("decode_flash", [None, True],
                         ids=["dense", "kernels"])
def test_a_dead_slot_puts_nothing_non_finite_into_a_pool(decode_flash):
    """A whole ``paged_decode_chunk`` with ``left``: slot 2 owns no
    request and its parking page holds NaN, slot 1's request ends two
    steps into the chunk. Told so, the attend gives both zeros from
    then on, so what the chunk stages for them and flushes into their
    pages is FINITE in every layer (without ``left`` the idle slot
    reads its NaN page, its row is NaN, and the second layer's K/V of
    every one of its tokens is NaN: pages that go back to the pool). No
    page a live slot owns holds a non-finite value, and the tokens the
    loop keeps (a slot's first ``left``) are those of the same chunk
    told nothing, on a clean pool."""
    pt, chunk = 8, 5
    cfg, params, pkv = _chunk_setup(False, pt, chunk,
                                    decode_flash=decode_flash)
    tok = jnp.asarray([4, 9, 0, 17], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 4)
    step = kvpage.make_paged_step_fn(params, cfg, tfm, chunk, pt)
    clean = pkv.device_state()
    _, want, _ = step(jax.tree.map(jnp.copy, clean), tok, keys)
    park = 14 + 2                               # slot 2's parking page
    left = np.asarray([chunk, 2, 0, chunk], np.int32)
    state = pkv.device_state(left)
    for key in ("k", "v"):
        state[key] = state[key].at[:, park].set(jnp.nan)
    for told in (True, False):
        s = jax.tree.map(jnp.copy, state)
        if not told:
            del s["left"]
        out, toks, _ = step(s, tok, keys)
        fresh = np.asarray(out["k"], np.float32)[:, park, :, :, :chunk]
        assert np.isfinite(fresh[0]).all()          # K/V of the embedding
        assert np.isfinite(fresh[1]).all() == told, told
    out, toks, _ = step(jax.tree.map(jnp.copy, state), tok, keys)
    assert sorted(out) == sorted(state)             # ``left`` rides along
    np.testing.assert_array_equal(np.asarray(out["left"]), left)
    owned = sorted(p for b in (0, 1, 3) for p in pkv.pages[b])
    for key in ("k", "v"):
        assert np.isfinite(np.asarray(out[key], np.float32)[:, owned]).all()
    toks, want = np.asarray(toks), np.asarray(want)
    for b, n in enumerate(left):
        np.testing.assert_array_equal(toks[:n, b], want[:n, b],
                                      err_msg=f"slot {b}")


def _chunk_setup(kv_int8, pt, chunk, decode_flash=None, seed=4):
    """A small GPT-2 on four slots at ``pt`` tokens a page: slot 0 at
    lane 0 of its second page, slot 1 at a page's last lane, slot 2
    idle, slot 3 three tokens before its table row's end."""
    import dataclasses
    cfg = tfm.tiny_config(vocab=61, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_seq=4 * pt + chunk)
    cfg = dataclasses.replace(cfg, decode_flash=decode_flash)
    params = tfm.init_params(jax.random.key(seed), cfg)
    pkv = kvpage.PagedKV(cfg, tfm, n_slots=4, max_len=4 * pt, page_tokens=pt,
                         n_pages=14, kv_int8=kv_int8)
    rng = np.random.default_rng(seed)
    pkv.pool = {k: jnp.asarray(rng.integers(-3, 4, v.shape), v.dtype)
                if k in "kv" else jnp.asarray(rng.random(v.shape) + 0.5,
                                              v.dtype)
                for k, v in pkv.pool.items()}
    pkv.seat(0, [], pkv.alloc_evicting(3), new_pos=pt)
    pkv.seat(1, [], pkv.alloc_evicting(4), new_pos=2 * pt - 1)
    pkv.seat(3, [], pkv.alloc_evicting(4), new_pos=4 * pt - 3)
    return cfg, params, pkv


def _chunk_and_steps(cfg, params, pkv, chunk, pt):
    """(the staged chunk's state and tokens, the same steps one at a
    time through ``paged_decode_step``'s own write)."""
    tok = jnp.asarray([4, 9, 0, 17], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 4)
    step = kvpage.make_paged_step_fn(params, cfg, tfm, chunk, pt)
    state = pkv.device_state()
    ref, ref_toks, t = state, [], tok
    one = jax.jit(lambda p, s, t: kvpage.paged_decode_step(p, cfg, s, t, pt))
    for _ in range(chunk):
        logits, ref = one(params, ref, t)
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ref_toks.append(np.asarray(t))
    out, toks, _ = step(jax.tree.map(jnp.copy, state), tok, keys)
    return out, np.asarray(toks), ref, np.stack(ref_toks)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("pt,chunk", [(128, 32), (128, 1), (8, 5), (8, 8),
                                      (8, 16), (8, 19)])
def test_staged_chunk_bit_equals_steps_with_the_dense_write(kv_int8, pt,
                                                            chunk):
    """``paged_decode_chunk`` (stage, attend over pool + stage, one
    flush) against ``paged_decode_step`` iterated a token at a time
    with its own write, off the chip (the dense pair): tokens equal,
    every pool and scale pool bit-equal, ``pos`` advanced by the chunk,
    no stage left in the state, and every page that no slot's tokens
    land in untouched. Pages of 128 with the serving chunk of 32 and a
    chunk of 1; small pages that a chunk crosses and fills; chunks of
    two pages and more (the flush goes in runs of a page)."""
    cfg, params, pkv = _chunk_setup(kv_int8, pt, chunk)
    before = pkv.device_state()
    out, toks, ref, ref_toks = _chunk_and_steps(cfg, params, pkv, chunk, pt)
    np.testing.assert_array_equal(toks, ref_toks)
    assert sorted(out) == sorted(ref) and "stage" not in out
    for key in out:
        np.testing.assert_array_equal(np.asarray(out[key], np.float32),
                                      np.asarray(ref[key], np.float32),
                                      err_msg=key)
    np.testing.assert_array_equal(np.asarray(out["pos"]),
                                  np.asarray(before["pos"]) + chunk)
    cols = np.minimum((np.asarray(before["pos"])[:, None]
                       + np.arange(chunk)) // pt, 3)
    touched = set(np.take_along_axis(np.asarray(before["table"]), cols,
                                     axis=1).ravel().tolist())
    assert 14 + 2 in touched                     # the idle slot's parking page
    for key in ("k", "v", "ks", "vs"):
        if key in out:
            for p in set(range(14 + 4)) - touched:
                np.testing.assert_array_equal(
                    np.asarray(out[key][:, p], np.float32),
                    np.asarray(before[key][:, p], np.float32), err_msg=key)


@pytest.mark.parametrize("chunk", [5, 16])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8kv"])
def test_staged_chunk_with_the_kernels_serves_the_dense_tokens(kv_int8,
                                                               chunk):
    """The same chunk at ``decode_flash=True`` (the staged walk and the
    n-token write as Pallas kernels, interpret mode) against the dense
    pair: the owning slots' tokens equal, their pages equal to numerics
    (the fold's block boundaries moved: pool to the chunk's start, then
    the stage), and in the FIRST layer, whose K/V no attend feeds,
    bit-equal. A chunk of 16 at pages of 8 flushes in two runs."""
    pt = 8
    cfg, params, pkv = _chunk_setup(kv_int8, pt, chunk, decode_flash=True)
    assert select_paged_kv_write(True, pt) is paged_kv_write
    out, toks, ref, ref_toks = _chunk_and_steps(cfg, params, pkv, chunk, pt)
    owning = [0, 1]                 # slot 3 runs past its row: not a request
    np.testing.assert_array_equal(toks[:, owning], ref_toks[:, owning])
    mine = sorted(p for b in owning for p in pkv.pages[b])
    for key in ("k", "v", "ks", "vs"):
        if key in out:
            got = np.asarray(out[key], np.float32)[:, mine]
            want = np.asarray(ref[key], np.float32)[:, mine]
            np.testing.assert_array_equal(got[0], want[0], err_msg=key)
            np.testing.assert_allclose(got, want, atol=1.01, rtol=0.05,
                                       err_msg=key)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8kv"])
def test_paged_step_same_with_either_write(kv_int8, monkeypatch):
    """One whole paged_decode_step at decode_flash=True (the kernel
    write and the whole-pool kernel attend, interpret mode) against the
    same step with the dense write swapped in: logits and every pool
    bit-equal, slots at different positions, one of them idle."""
    import dataclasses
    cfg, params = _pool_cfg()
    cfg = dataclasses.replace(cfg, decode_flash=True)
    pkv = kvpage.PagedKV(cfg, tfm, n_slots=3, max_len=32, page_tokens=8,
                         n_pages=9, kv_int8=kv_int8)
    rng = np.random.default_rng(2)
    pkv.pool = {k: jnp.asarray(rng.integers(-3, 4, v.shape), v.dtype)
                for k, v in pkv.pool.items()}
    pkv.seat(0, [], pkv.alloc_evicting(2), new_pos=8)      # off 0
    pkv.seat(2, [], pkv.alloc_evicting(3), new_pos=23)     # off last
    tok = jnp.asarray([4, 0, 9], jnp.int32)

    def step():
        return jax.jit(lambda p, s, t: kvpage.paged_decode_step(
            p, cfg, s, t, 8))(params, pkv.device_state(), tok)

    assert select_paged_kv_write(True, 8) is paged_kv_write
    logits, out = step()
    monkeypatch.setattr(flash_decode, "select_paged_kv_write",
                        lambda *_: paged_kv_write_dense)
    ref_logits, ref = step()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert sorted(out) == sorted(ref)
    for key in out:
        np.testing.assert_array_equal(np.asarray(out[key], np.float32),
                                      np.asarray(ref[key], np.float32),
                                      err_msg=key)


@pytest.mark.parametrize("on_tpu,page_tokens,decode_flash,kernel", [
    (False, 128, None, False),        # off the chip: the dense pair
    (True, 128, None, True),          # the cells' geometry on the chip
    (True, 96, None, False),          # a page Mosaic cannot tile
    (True, 256, None, True),
    (False, 32, True, True),          # explicit: interpret mode off-TPU
    (True, 128, False, False),
])
def test_select_paged_kv_write_follows_the_attend(on_tpu, page_tokens,
                                                  decode_flash, kernel,
                                                  monkeypatch):
    """The write is chosen as the attend is: auto takes the Pallas pair
    only on a TPU at a page of whole lane tiles; True / False force it."""
    monkeypatch.setattr(backend, "on_tpu", lambda: on_tpu)
    write = select_paged_kv_write(decode_flash, page_tokens)
    assert write is (paged_kv_write if kernel else paged_kv_write_dense)
    if decode_flash is None:
        assert flash_decode._paged_kernels_fit(page_tokens) is kernel
    # ... and the attend a serve call builds and records is its partner
    assert select_paged_decode_attend(decode_flash, page_tokens) is (
        paged_flash_decode_attend if kernel else paged_gather_attend)


# --------------------------------------------------------------------------
# allocator / trie / PagedKV units


@pytest.mark.parametrize("pos,chunk,left,want", [
    ([0, 0, 0], 1, None, 3),                  # idle: the parking page each
    ([0, 0, 0], 8, None, 24),
    ([7, 8, 31], 1, None, 1 + 1 + 4),         # the pages below pos
    ([7, 0, 0], 2, None, (1 + 1 + 1) * 2),    # the chunk's own: the stage
    ([31, 40, 100], 4, None, 3 * 4 * 4),      # never past the table row
    # told what each slot owes: a slot reads in its live steps alone
    ([0, 0, 0], 8, [0, 0, 0], 0),             # nobody owns a request
    ([7, 8, 31], 4, [9, 4, 5], (1 + 1 + 4) * 4),      # all live throughout
    ([7, 8, 31], 4, [1, 0, 3], 1 * 1 + 0 + 4 * 3),    # ends mid-chunk, idle
    ([31, 40, 100], 4, [2, 0, 7], 4 * 2 + 0 + 4 * 4),
])
def test_live_pages_counts_what_the_walk_fetches(pos, chunk, left, want):
    """PagedKV.live_pages: a layer's attends over the next chunk, a
    slot reading the pool up to its pos at the chunk's start in every
    step in which it can still deliver a token: all of them when told
    nothing (ServingMetrics.attend_pages_walked +
    attend_pages_dead), the first ``left[b]`` when told
    (attend_pages_walked)."""
    cfg = tfm.tiny_config(vocab=31, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_seq=32)
    pkv = kvpage.PagedKV(cfg, tfm, n_slots=3, max_len=32, page_tokens=8,
                         n_pages=12)
    pkv.pos[:] = pos
    assert pkv.live_pages(
        chunk, None if left is None else np.asarray(left)) == want
    assert pkv.live_pages(chunk, np.full(3, chunk)) == pkv.live_pages(chunk)


def test_allocator_deterministic_and_refcounted():
    a = kvpage.PageAllocator(6)
    assert a.alloc(3) == [0, 1, 2]                # lowest ids first
    assert a.alloc(4) is None                     # all-or-nothing
    assert a.free_count == 3
    a.incref(1)
    assert a.shared_count() == 1
    assert not a.decref(1)                        # still referenced
    assert a.decref(1)                            # refcount 0 -> reclaimed
    assert a.decref(0) and a.decref(2)
    assert a.free_count == 6
    # Reclaim re-sorts: the next alloc hands back the lowest ids again.
    assert a.alloc(2) == [0, 1]


def _pool_cfg(kv_int8=False):
    cfg = tfm.tiny_config(vocab=61, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_seq=96)
    return cfg, tfm.init_params(jax.random.key(0), cfg)


def test_cow_on_divergence():
    """ensure_writable on a shared page copies it: the slot gets a
    private page with identical bytes, the shared original keeps its
    other reference, refcounts land right. (Unreachable under the
    full-page-adoption policy — this pins the defensive guard.)"""
    cfg, _ = _pool_cfg()
    pkv = kvpage.PagedKV(cfg, tfm, n_slots=2, max_len=32, page_tokens=8,
                         n_pages=6)
    pages = pkv.alloc_evicting(2)
    pkv.pool["k"] = pkv.pool["k"].at[:, pages[0]].set(1.5)
    pkv.alloc.incref(pages[0])                    # simulate a trie share
    pkv.seat(0, [pages[0]], [pages[1]], new_pos=10)
    assert pkv.alloc.refcount(pages[0]) == 2
    assert pkv.ensure_writable(0, 0)              # shared -> copies
    new_page = pkv.pages[0][0]
    assert new_page != pages[0]
    assert pkv.alloc.refcount(pages[0]) == 1
    assert pkv.alloc.refcount(new_page) == 1
    np.testing.assert_array_equal(
        np.asarray(pkv.pool["k"][:, new_page]),
        np.asarray(pkv.pool["k"][:, pages[0]]))
    assert pkv.table[0, 0] == new_page
    assert not pkv.ensure_writable(0, 0)          # now private: no-op


def test_release_reclaims_to_zero_and_parks():
    cfg, _ = _pool_cfg()
    pkv = kvpage.PagedKV(cfg, tfm, n_slots=2, max_len=32, page_tokens=8,
                         n_pages=8)
    pages = pkv.alloc_evicting(3)
    pkv.seat(1, [], pages, new_pos=20)
    assert pkv.alloc.used_count == 3
    pkv.release(1)
    assert pkv.alloc.used_count == 0
    assert pkv.pos[1] == 0
    # Parked: every table entry points at the slot's own parking page.
    assert (pkv.table[1] == pkv.n_pages + 1).all()


def test_radix_trie_match_caps_and_full_page_adoption():
    """A match never swallows the whole prompt (the suffix keeps >= 1
    token) and insert adopts only FULL pages."""
    alloc = kvpage.PageAllocator(8)
    trie = kvpage.RadixPrefixCache(alloc, page_tokens=4)
    prompt = np.arange(10, dtype=np.int32)        # 2 full pages + 2 tail
    pages = alloc.alloc(3)
    assert trie.insert(prompt, pages) == 2        # 10 // 4 full pages
    assert alloc.refcount(pages[0]) == 2          # trie holds a ref
    assert alloc.refcount(pages[2]) == 1          # tail page not adopted
    # Exact same prompt: depth cap (len-1)//4 = 2 -> both full pages hit.
    hit = trie.match(prompt)
    assert hit == pages[:2]
    assert trie.hits == 1
    for p in hit:
        alloc.decref(p)
    # An 8-token prompt may only match 1 page ((8-1)//4) even though
    # its first 8 tokens are 2 cached pages: the seated request must
    # own the page its write cursor starts in.
    hit = trie.match(prompt[:8])
    assert hit == pages[:1]
    for p in hit:
        alloc.decref(p)


# --------------------------------------------------------------------------
# serving parity: serve_paged_greedy vs serve_greedy


def _serve_setup():
    cfg = tfm.tiny_config(vocab=61, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_seq=96)
    params = tfm.init_params(jax.random.key(0), cfg)
    ks = jax.random.split(jax.random.key(3), 7)
    prompts = [np.asarray(jax.random.randint(ks[i], (l,), 0, cfg.vocab),
                          np.int32)
               for i, l in enumerate([5, 9, 3, 12, 7, 6, 10])]
    return cfg, params, prompts


# Tier-1 (`-m 'not slow'`) keeps ONE end-to-end serve parity case
# ([1-int8kv], the disagg-relevant configuration); the other three
# variants and the serving-heavy tests below run in `make paged-check`,
# which invokes this file unfiltered. Each full serve jit-compiles its
# own step functions (~4-7s on this box), and the tier-1 sweep runs
# against a hard wall-clock budget.
RAGGED = [6, 3, 9, 2, 5, 7, 4]       # new tokens a request of _serve_setup


@pytest.mark.parametrize("kv_int8", [
    pytest.param(False, marks=pytest.mark.slow),
    True,
], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("chunk,n_new", [
    (1, 6),
    pytest.param(4, 6, marks=pytest.mark.slow),
    (4, RAGGED),
], ids=["1-even", "4-even", "4-ragged"])
def test_serve_paged_bit_equals_fixed(kv_int8, chunk, n_new):
    """The §19 acceptance bar: on identical schedules the paged server
    reproduces fixed-slot serve_greedy BIT for BIT — bf16 and int8
    caches, chunked dispatch included; and with outputs of 2 to 9
    tokens against a chunk of 4, where requests end mid-chunk and the
    last ones drain beside empty slots: slot-steps the loop tells the
    chunk are dead (``state['left']``), whose rows the attend zeroes
    and the loop never reads."""
    cfg, params, prompts = _serve_setup()
    fixed = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=3,
                                 max_len=32, family=tfm, chunk=chunk,
                                 kv_int8=kv_int8)
    paged = serving.serve_paged_greedy(params, cfg, prompts, n_new, n_slots=3,
                                       max_len=32, family=tfm, chunk=chunk,
                                       kv_int8=kv_int8, page_tokens=8)
    for i, (f, p) in enumerate(zip(fixed, paged)):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p),
                                      err_msg=f"request {i}")
    assert paged.metrics.preemptions == 0
    # The HBM claim in miniature: 7 staggered requests through 3 slots
    # peak well under the fixed-equivalent 12 pages (3 slots * 4 pages).
    assert 0 < paged.metrics.pages_hwm < 12
    # seven requests through three slots drain beside empty slots, and
    # the ragged ones end mid-chunk: dead slot-steps, whatever the chunk
    assert paged.metrics.attend_pages_dead > 0


def _moe_serve_setup(name):
    """A small MoE family as its own test file has it: (family, cfg,
    the benchmark's weights at the raised ``init_scale``, so that the
    layers and not the embedding decide the tokens)."""
    import importlib
    t = importlib.import_module("test_" + name)
    make = getattr(getattr(t, "weights_" + name), "make_" + name)
    return getattr(t, name), t.CFG, make(t.C, 7, jnp.float32)


@pytest.mark.parametrize("family,decode_flash", [
    ("gpt2", None), ("gpt2", True), ("lfm2", None), ("gigachat", None),
], ids=["dense", "kernels", "lfm2", "gigachat"])
def test_the_loop_tells_the_chunk_what_each_slot_owes(family, decode_flash,
                                                      monkeypatch):
    """``serve_paged_greedy`` hands every chunk ``RequestBook.left``:
    for an owner its ``n_new`` less what it has emitted (at least 1: a
    finished request was retired before the chunk), 0 for a slot that
    owns none. The tokens a request gets do not depend on it: the same
    call with every slot said to be live throughout (today's walk)
    serves the same tokens bit for bit, with the dense pair and with
    the kernels (interpret mode); the second call traces nothing (one
    program, ``left`` a plain operand); and what the two calls count
    adds up: walked + dead told is walked untold. The same of the two
    families with expert layers, whose dead slot-steps route no pair:
    the pairs counted told are those of the tokens delivered, the rest
    of ``top_k x slots`` a MoE layer-step are counted as left out, and
    untold nothing is left out."""
    import dataclasses
    cfg, params, prompts = _serve_setup()
    if family == "gpt2":
        family, top_k = tfm, 0
        cfg = dataclasses.replace(cfg, decode_flash=decode_flash)
    else:
        family, cfg, params = _moe_serve_setup(family)
        top_k = cfg.top_k
    chunk, n_slots, handed = 4, 3, []
    device_state = kvpage.PagedKV.device_state

    def recording(self, left=None):
        handed.append(None if left is None else np.array(left))
        return device_state(self, left)

    monkeypatch.setattr(kvpage.PagedKV, "device_state", recording)

    def serve():
        return serving.serve_paged_greedy(
            params, cfg, prompts, RAGGED, n_slots=n_slots, max_len=32,
            family=family, chunk=chunk, page_tokens=8)

    told = serve()
    assert len(handed) == told.metrics.steps
    assert all(h is not None and h.dtype == np.int32
               and h.shape == (n_slots,) for h in handed)
    # the first chunk: requests 0-2 seated, one token each from prefill
    np.testing.assert_array_equal(handed[0], [5, 2, 8])
    assert all((h >= 0).all() and h.max() >= 1 for h in handed)
    assert any((h == 0).any() for h in handed)          # the draining tail
    # a slot-step delivers a token iff it is live
    assert told.metrics.decode_tokens == sum(
        int(np.minimum(h, chunk).sum()) for h in handed)
    monkeypatch.setattr(serving.RequestBook, "left",
                        lambda self: np.full(self.n_slots, self.chunk,
                                             np.int32))
    untold = serve()
    assert untold.metrics.programs_traced == 0
    for i, (a, b) in enumerate(zip(told, untold)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"request {i}")
    assert untold.metrics.attend_pages_dead == 0
    assert 0 < told.metrics.attend_pages_dead == (
        untold.metrics.attend_pages_walked - told.metrics.attend_pages_walked)
    t, u = told.metrics, untold.metrics
    moe_layers = t.moe_layer_steps // (chunk * t.steps)
    assert t.moe_assignments == top_k * moe_layers * t.decode_tokens
    assert sum(c[0] for c in t.moe_by_chunk) == t.moe_assignments
    assert (t.moe_assignments + t.moe_pairs_dead
            == top_k * n_slots * t.moe_layer_steps)
    assert u.moe_pairs_dead == 0
    if top_k:
        assert t.moe_pairs_dead > 0 and moe_layers > 0
        assert u.moe_assignments > t.moe_assignments


@pytest.mark.slow
def test_preempt_then_resume_byte_exact():
    """A pool too small for three live requests forces a page-pressure
    preemption; the victim requeues UNCHARGED and replays onto the same
    deterministic page placement — outputs stay bit-equal to the
    unpressured fixed-slot run."""
    cfg, params, prompts = _serve_setup()
    fixed = serving.serve_greedy(params, cfg, prompts, 6, n_slots=3,
                                 max_len=32, family=tfm)
    paged = serving.serve_paged_greedy(params, cfg, prompts, 6, n_slots=3,
                                       max_len=32, family=tfm,
                                       page_tokens=8, n_pages=6)
    for i, (f, p) in enumerate(zip(fixed, paged)):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p),
                                      err_msg=f"request {i}")
    assert paged.metrics.preemptions >= 1
    assert paged.metrics.requeues == 0            # preemption != failure


@pytest.mark.slow
def test_pool_drains_to_zero_after_serving():
    cfg, params, prompts = _serve_setup()
    out = serving.serve_paged_greedy(params, cfg, prompts, 4, n_slots=2,
                                     max_len=32, family=tfm, page_tokens=8,
                                     return_paged_state=True)
    assert out.paged_state.alloc.used_count == 0
    assert out.paged_state.alloc.free_count == out.paged_state.n_pages


@pytest.mark.parametrize("which", ["fixed", "paged"])
@pytest.mark.slow
def test_typed_rejection_replaces_assert(which):
    """Satellite: an over-long request degrades to RequestRejected at
    its output index (reason exceeds_max_len) in BOTH servers; the
    other requests are served normally and stay path-equal."""
    cfg, params, prompts = _serve_setup()
    prompts = [prompts[0],
               np.zeros((30,), np.int32),         # 30 + 6 + 1 > 32
               prompts[1]]
    serve = (serving.serve_greedy if which == "fixed"
             else serving.serve_paged_greedy)
    out = serve(params, cfg, prompts, 6, n_slots=2, max_len=32, family=tfm)
    assert isinstance(out[1], serving.RequestRejected)
    assert out[1].reason == "exceeds_max_len"
    assert out.metrics.rejections == 1
    assert out.metrics.rejection_reasons == {"exceeds_max_len": 1}
    want = serving.serve_greedy(params, cfg, [prompts[0], prompts[2]], 6,
                                n_slots=2, max_len=32, family=tfm)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(want[1]))


def test_page_budget_rejection():
    """The paged-only admission bound: a request whose page need
    exceeds the whole pool is rejected up front (it could never be
    seated even alone), not preempt-looped."""
    cfg, params, prompts = _serve_setup()
    out = serving.serve_paged_greedy(params, cfg, [prompts[3]], 6,
                                     n_slots=1, max_len=32, family=tfm,
                                     page_tokens=8, n_pages=2)
    assert isinstance(out[0], serving.RequestRejected)
    assert out[0].reason == "exceeds_page_budget"


@pytest.mark.slow
def test_streaming_on_token_matches_outputs():
    """on_token fires per consumed token, prefill token included; the
    concatenated stream equals the returned output's generated tail."""
    cfg, params, prompts = _serve_setup()
    streams = {}
    out = serving.serve_paged_greedy(
        params, cfg, prompts[:4], 5, n_slots=2, max_len=32, family=tfm,
        page_tokens=8,
        on_token=lambda rid, tok: streams.setdefault(rid, []).append(tok))
    for rid in range(4):
        got = np.asarray(out[rid])[len(prompts[rid]):]
        np.testing.assert_array_equal(np.asarray(streams[rid], np.int32),
                                      got)


# --------------------------------------------------------------------------
# radix prefix sharing end to end


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.slow
def test_prefix_hit_reuses_shared_pages(kv_int8):
    """The acceptance assertion: requests sharing a long system prompt
    re-use >= the shared prefix's full-page count from the radix cache,
    and the hit-path outputs are deterministic (two identical serves
    agree bit for bit)."""
    cfg, params, _ = _serve_setup()
    rng = np.random.default_rng(11)
    system = rng.integers(0, cfg.vocab, 20).astype(np.int32)  # 2 full pages
    prompts = [np.concatenate([system,
                               rng.integers(0, cfg.vocab, 4 + i)
                               .astype(np.int32)])
               for i in range(3)]

    def serve():
        return serving.serve_paged_greedy(
            params, cfg, prompts, 4, n_slots=1, max_len=40, family=tfm,
            page_tokens=8, kv_int8=kv_int8, prefix_cache=True)

    out = serve()
    # 1 slot -> strictly sequential: requests 1 and 2 both hit the
    # system prefix request 0 inserted. 20 tokens / 8 = 2 full pages.
    assert out.metrics.prefix_hits >= 2
    assert out.metrics.prefix_pages_reused >= 2 * 2
    again = serve()
    for a, b in zip(out, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_prefix_cold_path_unchanged():
    """prefix_cache=True with no shareable history (distinct prompts,
    first pass) must not change cold outputs: still bit-equal to the
    fixed-slot server."""
    cfg, params, prompts = _serve_setup()
    fixed = serving.serve_greedy(params, cfg, prompts[:4], 5, n_slots=2,
                                 max_len=32, family=tfm)
    paged = serving.serve_paged_greedy(params, cfg, prompts[:4], 5,
                                       n_slots=2, max_len=32, family=tfm,
                                       page_tokens=8, prefix_cache=True)
    assert paged.metrics.prefix_hits == 0
    for f, p in zip(fixed, paged):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p))


@pytest.mark.slow
def test_slo_gate_off_by_default_and_defers_under_target(monkeypatch):
    """Unset knobs = no gate (bit-equal schedules, asserted throughout
    this file); an impossible TTFT target defers refills but never
    starves an empty server, so the batch still completes."""
    cfg, params, prompts = _serve_setup()
    assert serving._slo_admit_targets(None) == (None, None)
    monkeypatch.setenv("ACX_SERVE_ADMIT_TTFT_MS", "0.000001")
    out = serving.serve_paged_greedy(params, cfg, prompts[:4], 4,
                                     n_slots=2, max_len=32, family=tfm,
                                     page_tokens=8)
    want = serving.serve_greedy(params, cfg, prompts[:4], 4, n_slots=2,
                                max_len=32, family=tfm)
    for f, p in zip(want, out):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p))
    assert out.metrics.slo_deferrals >= 1


# --------------------------------------------------------------------------
# phase spans and counters of the serve loop (profiling.Phases)

PHASES = ("serve.setup", "refill.match", "refill.prefill", "refill.scatter",
          "refill.seat", "chunk.grow", "chunk.upload", "chunk.step",
          "chunk.deliver", "chunk.retire", "loop.other")


def test_serve_paged_phases_cover_the_call_and_count_the_decode_work():
    """Seven requests through three slots, outputs of mixed length, so
    four wait for a retire: every span of the docstring's table is
    there, their self times sum to the call, the per-request queue wait
    and prefill sit inside its TTFT, the decode counters equal a hand
    count, and the outputs are still serve_greedy's bit for bit."""
    cfg, params, prompts = _serve_setup()
    n_new = [6, 3, 9, 2, 5, 7, 4]
    chunk, n_slots = 4, 3
    fixed = serving.serve_greedy(params, cfg, prompts, n_new,
                                 n_slots=n_slots, max_len=32, family=tfm,
                                 chunk=chunk)
    streamed = []
    paged = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=n_slots, max_len=32,
        family=tfm, chunk=chunk, page_tokens=8,
        on_token=lambda rid, tok: streamed.append(rid))
    for i, (f, p) in enumerate(zip(fixed, paged)):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(p),
                                      err_msg=f"request {i}")
    m = paged.metrics
    assert m.paged_kv_write == "paged_kv_write_dense"    # off the chip
    assert m.paged_decode_attend == "paged_gather_attend"
    # the live-page walk's work, whichever attend ran: max_pages = 4
    assert m.attend_pages_grid == m.steps * chunk * n_slots * 4
    # a slot-step that delivers a token fetches 1-3 pages (prompts of
    # 3-12, outputs to 9); one that cannot, none: the loop told the chunk
    # (``left``). What it would have fetched is counted beside
    assert m.decode_tokens <= m.attend_pages_walked <= 3 * m.decode_tokens
    every = m.attend_pages_walked + m.attend_pages_dead
    assert n_slots * chunk * m.steps <= every <= m.attend_pages_grid
    assert m.attend_dead_share == m.attend_pages_dead / every
    assert 0.25 < m.attend_dead_share < 0.75   # mid-chunk ends and a tail
    assert m.attend_live_share == m.attend_pages_walked / m.attend_pages_grid
    assert 0.1 <= m.attend_live_share < 0.5
    assert set(m.phase_s) == set(m.phase_n) == set(PHASES)
    assert all(v >= 0 for v in m.phase_s.values())
    # between two spans the clock is not read: ~8 us of a span's own
    # book-keeping, which a call that traces nothing (10 ms here, once
    # an earlier test has served this configuration) no longer hides
    assert abs(sum(m.phase_s.values()) - m.call_s) <= (
        0.02 * m.call_s + 30e-6 * sum(m.phase_n.values()))
    assert m.call_s >= m.wall_s + 0.9 * m.phase_s["serve.setup"]
    # one span per event: a refill's four, a chunk's four, one set-up
    for name in ("refill.match", "refill.prefill", "refill.scatter",
                 "refill.seat", "chunk.retire"):
        assert m.phase_n[name] == len(prompts), name
    for name in ("chunk.grow", "chunk.upload", "chunk.step",
                 "chunk.deliver"):
        assert m.phase_n[name] == m.steps, name
    assert m.phase_n["serve.setup"] == 1
    assert m.phase_n["loop.other"] == m.steps + 1      # + the tail

    # decode work, by hand: every chunk offers chunk x slots steps; a
    # request's tokens beyond its first (the prefill's) come from them
    assert m.decode_slot_steps == m.steps * chunk * n_slots
    assert m.decode_tokens == sum(n_new) - len(prompts) == 29
    assert m.decode_tokens == len(streamed) - len(prompts)
    assert m.step_utilization == 29 / m.decode_slot_steps
    assert 0 < m.step_utilization < m.slot_occupancy_mean   # mid-chunk ends

    setup = m.phase_s["serve.setup"]
    by_rid = {r.rid: r for r in m.per_request}
    for r in m.per_request:
        assert r.prefill_s > 0 and r.refill_host_s > 0
        assert r.queue_wait_s + r.prefill_s <= r.ttft_s + setup
    assert setup <= by_rid[0].queue_wait_s <= setup + 0.05
    # the first three are seated at once, the rest by a refill on retire
    assert all(by_rid[i].queue_wait_s > by_rid[0].queue_wait_s
               for i in range(1, len(prompts)))
    assert all(by_rid[i].queue_wait_s > by_rid[2].queue_wait_s
               + by_rid[2].prefill_s for i in range(3, len(prompts)))
    assert abs(sum(r.prefill_s for r in m.per_request)
               - m.phase_s["refill.prefill"]) < 1e-6
    assert abs(sum(r.refill_host_s for r in m.per_request)
               - (m.phase_s["refill.match"] + m.phase_s["refill.scatter"]
                  + m.phase_s["refill.seat"])) < 1e-6


_PHASES = profiling.Phases


def _serve_recorded(monkeypatch=None, jump_after=None):
    """The seven requests of the phases test through three slots at a
    chunk of 4; with ``monkeypatch``, on a clock that advances by one at
    every reading, and by 100 more once, after reading ``jump_after``."""
    cfg, params, prompts = _serve_setup()
    if monkeypatch is not None:
        readings = [0]

        def clock():
            readings[0] += 1
            late = jump_after is not None and readings[0] > jump_after
            return float(readings[0]) + (100.0 if late else 0.0)
        monkeypatch.setattr(profiling, "Phases",
                            lambda: _PHASES(clock=clock))
    return serving.serve_paged_greedy(
        params, cfg, prompts, [6, 3, 9, 2, 5, 7, 4], n_slots=3, max_len=32,
        family=tfm, chunk=4, page_tokens=8).metrics


def test_serve_paged_record_tiles_the_call_and_carries_its_ids():
    """``metrics.spans``: every span of the call in order of opening;
    the top-level ones follow one another from the call's entry to its
    end, a child lies inside its parent, and the ids make a request's
    spans one request's."""
    m = _serve_recorded()
    spans = m.spans
    assert [s.index for s in spans] == list(range(len(spans)))
    assert {s.name for s in spans} == set(PHASES)
    assert {n: sum(s.name == n for s in spans) for n in PHASES} == m.phase_n
    top = [s for s in spans if s.parent is None]
    assert top[0].name == "serve.setup" and top[-1].name == "loop.other"
    assert m.call_s == top[-1].t1 - top[0].t0
    assert all(a.t1 <= b.t0 for a, b in zip(top, top[1:]))
    assert abs(sum(s.seconds for s in top) - m.call_s) <= (
        0.02 * m.call_s + 30e-6 * len(spans))
    for s in spans:
        if s.parent is not None:
            assert spans[s.parent].t0 <= s.t0 <= s.t1 <= spans[s.parent].t1
    # self times from the record are the counters', to the digit
    own = {}
    for s in spans:
        own[s.name] = own.get(s.name, 0.0) + s.seconds
        if s.parent is not None:
            own[spans[s.parent].name] -= s.seconds
    assert own == pytest.approx(m.phase_s, abs=1e-9)
    # ids: a refill's four spans and the retire name their request, the
    # prefill its bucket, a chunk its number and the step its owners
    for rid, bucket in enumerate([8, 16, 8, 16, 8, 8, 16]):
        mine = [s.name for s in spans if s.ids.get("rid") == rid]
        assert mine == ["refill.match", "refill.prefill", "refill.scatter",
                        "refill.seat", "chunk.retire"], rid
        pre, = [s for s in spans if s.name == "refill.prefill"
                and s.ids["rid"] == rid]
        assert (pre.ids["bucket"], pre.ids["hit_pages"]) == (bucket, 0)
        assert pre.t0 <= pre.handed <= pre.t1
    steps = [s for s in spans if s.name == "chunk.step"]
    assert [s.ids["step"] for s in steps] == list(range(1, m.steps + 1))
    assert steps[0].ids["rid"] == (0, 1, 2)
    assert all(len(s.ids["rid"]) == 3 and s.t0 <= s.handed <= s.t1
               for s in steps)
    assert all(s.handed is None for s in spans
               if s.name not in ("refill.prefill", "chunk.step"))
    # the call's first refill.prefill loaded its program inside the span
    # or an earlier test did: either way nothing was loaded anywhere else
    assert all(s.programs == () or s.name in (
        "refill.prefill", "refill.scatter", "refill.seat", "chunk.upload",
        "chunk.step", "chunk.grow", "serve.setup") for s in spans)


def test_serve_paged_request_paths_from_the_record(monkeypatch):
    """On a clock that advances by one at every reading: a request's
    chunks equal a hand count, its wait is one reading, and what of its
    decode interval lay under others' refills and its own chunks is what
    a walk over the record gives; nothing stalls on an even clock."""
    m = _serve_recorded(monkeypatch)
    spans = m.spans
    by_rid = {r.rid: r for r in m.per_request}
    # n_new - 1 decode tokens at 4 a chunk
    assert [by_rid[i].chunks for i in range(7)] == [2, 1, 2, 1, 1, 2, 1]
    assert sum(r.chunks for r in m.per_request) == sum(
        rid >= 0 for s in spans if s.name == "chunk.step"
        for rid in s.ids["rid"])
    entry = spans[0].t0
    for r in m.per_request:
        mine = {s.name: s for s in spans if s.ids.get("rid") == r.rid}
        seat = mine["refill.seat"]
        assert r.prefill_wait_s == 1.0 and r.prefill_s == 2.0
        assert r.queue_wait_s == mine["refill.match"].t0 - entry
        assert r.refill_host_s == sum(
            mine[n].seconds for n in ("refill.match", "refill.scatter",
                                      "refill.seat"))
        owned = [s for s in spans if s.name == "chunk.step"
                 and r.rid in s.ids["rid"]]
        delivers = [s for s in spans if s.name == "chunk.deliver"
                    and s.ids["step"] == owned[-1].ids["step"]]
        end = delivers[0].t1
        assert r.decode_s == end - seat.t1 > 0
        others = sum(s.seconds for s in spans
                     if s.name.startswith("refill.")
                     and s.ids["rid"] != r.rid and seat.t1 <= s.t0 < end)
        chunks = sum(s.seconds for s in spans
                     if s.name in ("chunk.upload", "chunk.step")
                     and s.ids["step"] in [o.ids["step"] for o in owned])
        assert r.decode_in_refill_s == others
        assert r.decode_in_chunk_s == chunks == 3.0 * r.chunks
        assert r.decode_in_refill_s + r.decode_in_chunk_s <= r.decode_s
    # requests 0-2 are seated at once: 1 and 2 are prefilled inside 0's
    # decode interval; the last request seated sees nobody's refill
    assert by_rid[0].decode_in_refill_s > by_rid[2].decode_in_refill_s > 0
    assert by_rid[6].decode_in_refill_s == 0.0
    assert (m.stalls, m.stall_s) == (0, 0.0)
    assert serving.stalled_spans(spans) == []


def test_serve_paged_one_long_prefill_is_one_stall_of_its_excess(
        monkeypatch):
    """The same call, the clock jumping by 100 while request 3's
    prefill waits: one stall, of the 100 above its bucket's median,
    named by the record; the requests decoding meanwhile carry it in
    ``decode_in_refill_s``."""
    even = _serve_recorded(monkeypatch)
    pre, = [s for s in even.spans if s.name == "refill.prefill"
            and s.ids["rid"] == 3]
    assert pre.handed == pre.t0 + 1     # readings ARE the clock's values
    m = _serve_recorded(monkeypatch, jump_after=int(pre.handed))
    (span, mid), = serving.stalled_spans(m.spans)
    assert (span.name, span.ids["rid"], span.ids["bucket"]) == (
        "refill.prefill", 3, 16)
    assert (span.seconds, mid) == (102.0, 2.0)
    assert (m.stalls, m.stall_s) == (1, 100.0)
    by_rid = {r.rid: r for r in m.per_request}
    assert by_rid[3].prefill_wait_s == 101.0
    seat_end = {s.ids["rid"]: s.t1 for s in m.spans
                if s.name == "refill.seat"}
    for r in m.per_request:     # whoever was decoding meanwhile
        around = (seat_end[r.rid] <= span.t0
                  and span.t1 <= seat_end[r.rid] + r.decode_s)
        assert (r.decode_in_refill_s >= 102.0) == around, r.rid
    # request 1 has retired, 0 and 2 sit through it before their second
    # chunk
    assert [r.rid for r in m.per_request
            if r.decode_in_refill_s >= 102.0] == [0, 2]
    # the call's FIRST prefill is left out (the pool's zero fill)
    first, = [s for s in even.spans if s.name == "refill.prefill"
              and s.ids["rid"] == 0]
    m = _serve_recorded(monkeypatch, jump_after=int(first.handed))
    assert (m.stalls, m.stall_s) == (0, 0.0)


def test_stalled_spans_group_prefills_by_bucket_and_chunks_together():
    """By hand: a prefill is measured against its own bucket's median
    (a long bucket is no stall), every chunk.step against the call's."""
    def span(i, name, seconds, **ids):
        return types.SimpleNamespace(index=i, name=name, seconds=seconds,
                                     ids=ids)
    took = [("refill.prefill", 9.0, dict(bucket=8, hit_pages=0)),  # first
            ("refill.prefill", 1.0, dict(bucket=8, hit_pages=0)),
            ("refill.prefill", 5.0, dict(bucket=64, hit_pages=0)),
            ("refill.prefill", 1.2, dict(bucket=8, hit_pages=0)),
            ("refill.prefill", 5.5, dict(bucket=64, hit_pages=0)),
            ("refill.prefill", 2.5, dict(bucket=8, hit_pages=0)),
            ("refill.prefill", 2.5, dict(bucket=8, hit_pages=4)),
            ("chunk.step", 10.0, dict(step=1)),
            ("chunk.step", 30.0, dict(step=2)),
            ("chunk.step", 12.0, dict(step=3)),
            ("chunk.deliver", 99.0, dict(step=3))]
    spans = [span(i, n, s, **ids) for i, (n, s, ids) in enumerate(took)]
    assert [(sp.index, mid) for sp, mid in serving.stalled_spans(spans)] == [
        (5, 1.2), (8, 12.0)]
    assert serving.STALL_FACTOR == 2.0


@pytest.mark.parametrize("chunk", [1, 4, 8, 16])
def test_serve_call_counts_what_it_staged_and_the_pages_it_rewrote(chunk):
    """``kv_tokens_staged`` and ``kv_page_rewrites`` of a small serve
    call (pages of 8): a layer stages ``chunk`` tokens a slot a chunk
    and its flush rewrites a page a slot, two where the chunk's tokens
    cross a page boundary, so a token costs between 1 / chunk and 2 /
    chunk page rewrites, and exactly 1 at a chunk of one token."""
    cfg, params, prompts = _serve_setup()
    n_new, n_slots = [6, 3, 9, 2, 5, 7, 4], 3
    m = serving.serve_paged_greedy(
        params, cfg, prompts, n_new, n_slots=n_slots, max_len=32, family=tfm,
        chunk=chunk, page_tokens=8).metrics
    assert m.kv_tokens_staged == m.steps * chunk * n_slots
    runs = -(-chunk // 8)           # a chunk of 16: two runs of a page
    assert (runs * m.steps * n_slots <= m.kv_page_rewrites
            <= 2 * runs * m.steps * n_slots)
    per_token = m.kv_page_rewrites / m.kv_tokens_staged
    assert runs / chunk <= per_token <= 2 * runs / chunk
    if chunk == 1:
        assert per_token == 1
    if chunk == 8:              # a whole page a chunk: every start but a
        assert m.kv_page_rewrites > m.steps * n_slots   # page's lane 0 crosses


def test_chunk_rewrites_counts_a_page_a_slot_and_the_crossings():
    """PagedKV.chunk_rewrites, the host's count of the flush's pages."""
    cfg = tfm.tiny_config(vocab=31, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_seq=32)
    pkv = kvpage.PagedKV(cfg, tfm, n_slots=3, max_len=32, page_tokens=8,
                         n_pages=12)
    pkv.seat(0, [], pkv.alloc_evicting(4), new_pos=0)
    pkv.seat(1, [], pkv.alloc_evicting(4), new_pos=5)
    pkv.pos[2] = 15                                  # idle: it walks on
    assert pkv.chunk_rewrites(1) == 3
    assert pkv.chunk_rewrites(3) == 3                # 5, 6, 7
    assert pkv.chunk_rewrites(4) == 3 + 1            # 5..8
    assert pkv.chunk_rewrites(8) == 3 + 1            # lane 0 fills its page
    np.testing.assert_array_equal(
        np.asarray(flash_decode.chunk_write_pages(
            jnp.asarray(pkv.table), jnp.asarray(pkv.pos), 4, 8)),
        [[0, 0], [4, 5], [14, 14]])                  # idle: parked both
    assert pkv.chunk_rewrites(16) == 2 * 3 + 2       # 5..12, 13..20
    pkv.pos[1] = 29                                  # the table row ends:
    assert pkv.chunk_rewrites(8) == 3                # both halves, one page


# --------------------------------------------------------------------------
# the serve path's programs live once a process (PERF.md, PR 28)


def _own_setup(vocab, seed=0):
    """A configuration no other test of this file serves (its own
    ``vocab``), so its first call finds none of its programs traced;
    three prompts in two prefill buckets (8, 16, 16), drawn from
    ``seed``. The layers' weights are scaled up: at their initial size
    a tiny model with tied embeddings echoes its last token whatever
    its weights are, and equal tokens would show nothing."""
    cfg = tfm.tiny_config(vocab=vocab, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_seq=96)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (5, 9, 12)]
    params = tfm.init_params(jax.random.key(seed), cfg)
    params["layers"] = jax.tree.map(lambda a: a * 16, params["layers"])
    return cfg, params, prompts


def _serve_own(params, cfg, prompts, **kw):
    kw = dict(dict(n_slots=2, max_len=32, family=tfm, chunk=2,
                   page_tokens=8), **kw)
    return serving.serve_paged_greedy(params, cfg, prompts, 5, **kw)


def _same_tokens(a, b):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"request {i}")


def _sharing_a_page(prompts):
    """The same prompts behind one shared page of 8 tokens, cut to the
    16 bucket: served one at a time, the second and third hit it."""
    return [np.concatenate([prompts[0][:4]] * 2 + [p])[:16]
            for p in prompts]


@pytest.mark.parametrize("second,prefix_cache,vocab", [
    ("same_call", False, 67),
    ("other_prompts_same_buckets", False, 68),
    ("other_weights", False, 69),
    ("other_prompts_same_buckets", True, 70),
    ("other_weights", True, 71),
])
def test_second_serve_call_traces_nothing(second, prefix_cache, vocab):
    """Two calls in one process with the same static arguments and the
    same program shapes: the first traces its programs, the second none
    — and serves exactly what a call that traces everything afresh
    serves, token for token, with OTHER prompts and with OTHER weights
    of the same shapes too: weights are arguments, nothing is baked in
    or left over."""
    cfg, params, prompts = _own_setup(vocab)
    _, params2, prompts2 = _own_setup(vocab, seed=1)
    if prefix_cache:
        prompts, prompts2 = _sharing_a_page(prompts), _sharing_a_page(prompts2)
    kw = dict(prefix_cache=prefix_cache, n_slots=1)
    then_params, then_prompts = {
        "same_call": (params, prompts),
        "other_prompts_same_buckets": (params, prompts2),
        "other_weights": (params2, prompts)}[second]

    first = _serve_own(params, cfg, prompts, **kw)
    again = _serve_own(then_params, cfg, then_prompts, **kw)
    assert first.metrics.programs_traced > 0
    assert again.metrics.programs_traced == 0
    assert first.metrics.prefix_hits == (2 if prefix_cache else 0)
    jax.clear_caches()                  # a call that traces everything
    fresh = _serve_own(then_params, cfg, then_prompts, **kw)
    # (the pool's programs do not see the vocabulary: ``first`` may have
    # found another test's)
    assert fresh.metrics.programs_traced >= first.metrics.programs_traced
    _same_tokens(again, fresh)
    if second == "same_call":
        _same_tokens(again, first)
    else:
        assert any((a != f).any() for a, f in zip(again, first))


@pytest.mark.parametrize("differs,traced", [
    # the base call: 2 prefill buckets, 2 scatters, the chunk program
    (dict(kv_int8=True), 5),            # every program: another cache
    (dict(chunk=4), 1),                 # the chunk program alone
    (dict(page_tokens=16), 3),          # the pool's programs, no prefill
    (dict(on_tpu=True), 3),             # the model's programs, no scatter
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_serve_call_with_another_static_key_traces_anew(differs, traced,
                                                        monkeypatch):
    """What the traced programs depend on is in their static key: a
    call that differs in ``kv_int8``, ``chunk``, ``page_tokens`` or in
    what ``backend.on_tpu()`` says traces its own programs and leaves
    the first call's where they were."""
    cfg, params, prompts = _own_setup(79)
    differs = dict(differs)
    jax.clear_caches()                  # so that the counts are exact
    base = _serve_own(params, cfg, prompts)
    assert base.metrics.programs_traced == 5
    with monkeypatch.context() as m:
        if differs.pop("on_tpu", False):
            # tiny and untileable: both answers build the dense programs
            m.setattr(backend, "on_tpu", lambda: True)
        other = _serve_own(params, cfg, prompts, **differs)
        assert other.metrics.programs_traced == traced
        assert _serve_own(params, cfg, prompts,
                          **differs).metrics.programs_traced == 0
    if "kv_int8" not in differs:        # bit-equal across these three
        _same_tokens(other, base)
    back = _serve_own(params, cfg, prompts)
    assert back.metrics.programs_traced == 0
    _same_tokens(back, base)


def test_paged_state_is_freed_without_the_collector():
    """``PagedKV`` sits in no reference cycle, a prefix hit's gather
    included: with the collector off, the pool goes when the caller
    drops the batch (it once took a ``gc.collect()`` between calls to
    keep two 9 GB pools from meeting on the chip)."""
    import gc
    import weakref
    cfg, params, prompts = _own_setup(89)
    prompts = _sharing_a_page(prompts)
    gc.collect()
    gc.disable()
    try:
        out = _serve_own(params, cfg, prompts, prefix_cache=True,
                         n_slots=1, return_paged_state=True)
        assert out.metrics.prefix_hits == 2
        pool = weakref.ref(out.paged_state)
        leaf = weakref.ref(out.paged_state.pool["k"])
        assert pool() is not None
        del out
        assert pool() is None and leaf() is None
    finally:
        gc.enable()


# --------------------------------------------------------------------------
# the whole-sequence pass is kvpage's: a family is its operators and a spec

_FAMILIES = {"lfm2": "tiny_lfm2", "jamba": "tiny_jamba",
             "gigachat": "tiny_gigachat", "nemotron_h": "tiny_nemotron"}


@pytest.mark.parametrize("name", sorted(_FAMILIES))
def test_a_family_hands_the_shared_pass_operators_and_no_pass_of_its_own(
        name):
    """``kvpage.sequence_pass`` / ``prefill`` / ``forward`` are the ONE
    whole-sequence pass: a family's spec overrides neither prefill (GPT-2
    alone does: ROADMAP.md, Queue 3), its module defines no pass and
    reaches for no private name of another family's, and the pass reads
    a state tree by its STRUCTURE: with every leaf of the spec's state
    under another name (LFM2's is a bare leaf: no name at all) the
    prefill gives the same tails and end state, leaf for leaf."""
    import ast
    import dataclasses
    import importlib
    import inspect
    module = importlib.import_module(f"mpi_acx_tpu.models.{name}")
    cfg = getattr(module, _FAMILIES[name])()
    spec = module.paged_spec(cfg)
    assert spec.prefill is None and spec.suffix_prefill is None
    assert tfm.paged_spec(tfm.tiny_config()).prefill is not None

    tree = ast.parse(inspect.getsource(module))
    defined = {n.name for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef)}
    assert not defined & {"_sequence_pass", "_prefilled", "prefill",
                          "suffix_prefill"}
    others = {f"mpi_acx_tpu.models.{f}" for f in _FAMILIES if f != name}
    borrowed = [a.name for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom) and n.module in others
                for a in n.names]
    reached = [n.attr for n in ast.walk(tree)
               if isinstance(n, ast.Attribute)
               and isinstance(n.value, ast.Name)
               and n.value.id in set(_FAMILIES) - {name}]
    assert not [n for n in borrowed + reached if n.startswith("_")]

    params = module.cast_params(module.init_params(jax.random.key(0), cfg))
    tokens = jnp.asarray(np.arange(32)[None] % cfg.vocab, jnp.int32)

    def prefill(spec, history=None):
        return jax.jit(lambda p, t, h: kvpage.prefill(
            p, cfg, spec, t, 20, False, 8, h))(params, tokens, history)[1]
    one = prefill(spec)
    if spec.state is None:
        assert "tail" not in one and "end" not in one
        return
    n_tails = 32 // (8 * spec.snapshot_every)
    for got, lead in ((one["tail"], (spec.n_state_layers, n_tails)),
                      (one["end"], (spec.n_state_layers,))):
        assert jax.tree.structure(got) == jax.tree.structure(spec.state)
        for leaf, want in zip(jax.tree.leaves(got),
                              jax.tree.leaves(spec.state)):
            assert leaf.shape == lead + want.shape
            assert leaf.dtype == want.dtype

    def renamed(tree):          # the same leaves under names of no family
        return (None if tree is None else
                {f"leaf{i}": l for i, l in enumerate(jax.tree.leaves(tree))})

    def seq_state(cfg, lp, x, start, last_index, snapshot):
        start = jax.tree.unflatten(jax.tree.structure(spec.state),
                                   jax.tree.leaves(start))
        x, tails, end = spec.seq_state(cfg, lp, x, start, last_index,
                                       snapshot)
        return x, renamed(tails), renamed(end)
    other = dataclasses.replace(spec, state=renamed(spec.state),
                                seq_state=seq_state)
    # a suffix behind 16 cached tokens, from the first call's snapshot
    tail = jax.tree.map(lambda t: t[:, 0], one["tail"])
    hk = jnp.zeros((spec.n_page_layers, spec.n_kv_heads, spec.head_dim, 16),
                   cfg.dtype)
    want = prefill(spec, (hk, hk, tail))
    got = prefill(other, (hk, hk, renamed(tail)))
    for key in ("tail", "end"):
        assert sorted(got[key]) == sorted(renamed(spec.state))
        for a, b in zip(jax.tree.leaves(got[key]),
                        jax.tree.leaves(want[key])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
