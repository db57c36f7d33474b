"""Length-aware GQA flash-decode: a Pallas TPU decode-attention kernel.

:func:`mpi_acx_tpu.models.decoding.grouped_decode_attend` — the single
decode-attention definition every family, the serving loop, the
speculative window passes, and the TP generation loops share — is a
dense einsum that reads the ENTIRE ``[B, Hkv, D, max_len]`` cache every
token, even when a slot sits at position 40 of 4096. This kernel replaces
that read with an online softmax over K/V blocks that is

* **length-aware** — the per-slot ``pos`` vector is scalar-prefetched
  into SMEM and only the ``ceil((pos + W) / block_k)`` LIVE blocks of a
  slot cross HBM->VMEM, double-buffered against the fold: HBM traffic
  is O(live length), not O(max_len). One fold (:func:`_fold_block`),
  two walks. A contiguous cache row is walked by a ``(slot, K/V block)``
  grid whose index map clamps at the last live block (Pallas re-fetches
  a block only when its index changes; a dead step fetches and computes
  nothing but is still a grid step). A page pool is walked by the
  kernel itself: a grid of ``(slot,)``, inside a step a loop over the
  slot's live pages, each copied out of the pool (left whole in HBM)
  with ``make_async_copy`` into one of two VMEM buffers while the page
  before it is folded, the next slot's first page started behind this
  slot's last. Nothing is paid per dead page: the ``(32, 8)`` grid it
  replaces paid 0.30 us for each dead step, 60 us of a 126 us call
  (PERF.md, PR 30).
* **GQA-native** — q ``[B, W, Hkv, n_rep, D]`` rides as
  ``[B, Hkv, W*n_rep, D]`` (row ``i`` is window slot ``i // n_rep``),
  attending the UN-repeated KV groups directly; one grid step carries
  ALL of a slot's KV heads (one head-batched matmul), so a step moves
  ``Hkv * D * block_k`` elements per operand, not one head's sliver.
* **int8-fused** — when the cache is an ``(int8 codes, f32 scales)``
  tuple (ops/kvquant.py), int8 is the only HBM-resident form and the
  only form that crosses the DMA. The per-position scales arrive as
  ``[Hkv, 1, block_k]`` lane rows and are applied to the SMALL tensors —
  K's to the scores, V's to the probabilities — the same
  scale-on-scores factoring as the dense path
  (``sum_d q_d*(K_kd*s_k) == (sum_d q_d*K_kd)*s_k``).
* **window-capable** — W > 1 for the speculative-decode window passes,
  and ``pos`` scalar or ``[B]`` for continuous-batching serving.
* **paged or contiguous** — one fold: block j is either tokens
  ``[j*block_k, (j+1)*block_k)`` of the slot's own cache row or page
  ``(layer, table[b, j])`` of the WHOLE pool ``[L, P, Hkv, D,
  page_tokens]`` (models/kvpage.py), addressed from two more
  scalar-prefetched operands: the block table and the layer index. The
  step program never slices a layer out of the pool — a
  ``dynamic_index_in_dim`` there is a copy of 98 MB a layer a step at
  GPT-2 XL's 240 pages, and was 93% of a decode step (PERF.md, PR 25).
* **written in place** — :func:`paged_kv_write` is the step's other
  Pallas call: the one fresh K/V token of every slot goes into lane
  ``pos % page_tokens`` of page ``(layer, write_page[b])`` of the same
  whole pool, aliased to the call's result. With tokens on lanes one
  token is one lane of every ``(8..32, 128)`` tile of the page, so a
  page is the smallest unit a DMA can move for it: the write is a
  page's read-modify-write, not a scatter (XLA's ``.at[..., off].set``
  on the lane dimension relayouts the whole layer there and back).

Cache layout (models/decoding.to_cache_layout): ``[B, Hkv, D,
max_len]``, pool ``[L, P, Hkv, D, page_tokens]`` (one layer of it ``[P,
Hkv, D, page_tokens]``), scales ``[..., 1, T]`` — tokens on the lane
dimension. A block is then ``[Hkv, D, block_k]``:
whole (8..32, 128) tiles for any head_dim that is a multiple of 32, with
no lane padding at head_dim 64, in the layout XLA's TPU layout
assignment gives such arrays anyway (no relayout copy in front of the
kernel). With tokens outside heads and features minor the chip's
compiler refuses to slice a head out of the ``(Hkv, D)`` tile, and
refuses any DMA slice of an array whose minor dimension is 64.

Dispatch mirrors ``select_attention``: :func:`select_decode_attend` is
the ONE flash/dense decode switch (``decode_flash`` config field on all
three families). Off-TPU the pallas_call runs in interpret mode; that
checks the math, not that the chip's compiler accepts the kernel —
tests/test_tpu_compile.py compiles it for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_acx_tpu import backend
from mpi_acx_tpu.ops.attention import _NEG_INF, _out_struct


def _fit_block_k(max_len, want):
    """Largest 128-multiple divisor of max_len <= want (whole lane
    tiles); a cache with none gets its largest divisor, which only
    interpret mode accepts."""
    b = min(want, max_len) // 128 * 128
    while b >= 128:
        if max_len % b == 0:
            return b
        b -= 128
    b = min(want, max_len)
    while max_len % b:
        b -= 1
    return b


def _n_live(pos, W, block_k, n_k):
    """Blocks that carry any live key of a slot at ``pos``: block j
    holds cache cols [j*bk, (j+1)*bk) and the last visible col is
    pos + W - 1."""
    return jnp.minimum((pos + W + block_k - 1) // block_k, n_k)


def _fold_block(q_ref, kb, vb, scales, j, pos, m_scr, l_scr, acc_scr, *,
                block_k, n_rep, scale):
    """THE fold, for both walks: K/V block ``j`` of every KV head of
    one slot (``kb``/``vb`` ``[Hkv, D, block_k]``; ``scales`` is ``(ks,
    vs)``, each ``[Hkv, 1, block_k]``, when they are int8 codes, else
    empty) goes into the online-softmax state held in VMEM scratch. ``q_ref[0]`` is the
    slot's ``[Hkv, W*n_rep, D]`` tile. The mask is on ABSOLUTE
    positions: row i is window slot ``i // n_rep`` at ``pos + i //
    n_rep``, column c of block j is cache position ``j*block_k + c``;
    on fully visible blocks it is all true. The scales multiply the
    SMALL tensors: K's the scores, V's the probabilities."""
    quant = bool(scales)
    Wn = q_ref.shape[-2]
    # Pre-scale q once (the _flash_kernel idiom); on the quant path
    # q stays f32 to dot against the f32-converted codes exactly.
    q = q_ref[0].astype(jnp.float32) * scale                # [Hkv, Wn, D]
    if quant:
        kb, vb = kb.astype(jnp.float32), vb.astype(jnp.float32)
        prec = jax.lax.Precision.HIGHEST
    else:
        q = q.astype(q_ref.dtype)
        prec = (jax.lax.Precision.HIGHEST
                if q_ref.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
    s = jax.lax.dot_general(
        q, kb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec)
    if quant:
        s = s * scales[0]                                   # [Hkv, Wn, bk]
    rows = pos + jax.lax.broadcasted_iota(
        jnp.int32, (1, Wn, 1), 1) // n_rep
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, block_k), 2)
    s = jnp.where(rows >= cols, s, _NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        p = p * scales[1]
    acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec)
    m_scr[...] = m_new


def _fold_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, *refs, block_k, n_rep,
                   n_k, scale):
    """The contiguous walk: one (batch slot, K/V block) grid step folds
    block j of the slot's own cache row. ``pos_ref`` ([B], SMEM) bounds
    the live blocks; steps at or past ``n_live`` neither fetch (the
    index map repeats the last live block) nor compute, but each is
    still a grid step. ``refs``: the two scale blocks of an int8
    cache, if any, then the output and the fold's state."""
    *scale_refs, o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        _fold_init(m_scr, l_scr, acc_scr)

    @pl.when(j < _n_live(pos, q_ref.shape[2] // n_rep, block_k, n_k))
    def _fold():
        _fold_block(q_ref, k_ref[0], v_ref[0], [r[0] for r in scale_refs],
                    j, pos, m_scr, l_scr, acc_scr,
                    block_k=block_k, n_rep=n_rep, scale=scale)

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _paged_walk_kernel(pos_ref, table_ref, layer_ref, q_ref, *refs,
                       page_tokens, n_rep, n_k, n_pools, scale):
    """The paged walk: one grid step a slot, and inside it one pass
    over the slot's LIVE pages and nothing per dead page. The pools
    stay in HBM (``refs[:n_pools]``: K, V, and the scale pools of an
    int8 cache); the kernel copies page ``(layer, table[b, j])`` of
    each into one of two VMEM buffers a pool and folds it while the
    next page's copies are in flight. The next page of a slot's LAST
    page is the next slot's FIRST: it is already on its way when that
    slot's grid step starts, as a BlockSpec pipeline would have had it
    (32 exposed copy latencies a call otherwise). ``walked`` (SMEM)
    counts the pages folded so far in the call: its parity is the
    buffer the current page sits in, across slots. Needs sequential
    grid steps (``arbitrary``)."""
    pools, (o_ref, *refs) = refs[:n_pools], refs[n_pools:]
    bufs, (sem, m_scr, l_scr, acc_scr, walked) = (refs[:n_pools],
                                                  refs[n_pools:])
    b, B = pl.program_id(0), pl.num_programs(0)
    layer, pos = layer_ref[0], pos_ref[b]
    n = _n_live(pos, q_ref.shape[2] // n_rep, page_tokens, n_k)

    def copies(slot, j, buf):
        page = table_ref[slot, j]
        return [pltpu.make_async_copy(pool.at[layer, page], dst.at[buf],
                                      sem.at[i, buf])
                for i, (pool, dst) in enumerate(zip(pools, bufs))]

    @pl.when(b == 0)
    def _first_page_of_the_call():
        walked[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    first = walked[0]
    _fold_init(m_scr, l_scr, acc_scr)

    def page(j, _):
        buf = (first + j) % 2
        more = j + 1 < n

        @pl.when(more | (b + 1 < B))
        def _next_page():
            for c in copies(jnp.where(more, b, b + 1),
                            jnp.where(more, j + 1, 0), 1 - buf):
                c.start()

        for c in copies(b, j, buf):
            c.wait()
        kb, vb, *scales = [dst[buf] for dst in bufs]
        _fold_block(q_ref, kb, vb, scales, j, pos, m_scr, l_scr, acc_scr,
                    block_k=page_tokens, n_rep=n_rep, scale=scale)

    jax.lax.fori_loop(0, n, page, None)
    walked[0] = first + n
    o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _decode_call(q, k, v, pos, n_rep, block_k, n_k, table=None, layer=None):
    """The pallas_call behind both kernels. ``k``/``v`` are K/V
    arrays or (codes, scales) tuples in cache layout
    ([B, Hkv, *, max_len]) or, with ``table``, pool layout: the whole
    pool ([L, P, Hkv, *, page_tokens]) with ``layer`` (a traced scalar
    is fine) naming the layer to read, or one layer of it
    ([P, Hkv, *, page_tokens]) without. What the code observes
    (``table is not None``) chooses the walk: a cache row's blocks by a
    ``(B, n_k)`` grid, a slot's live pages by the kernel's own copies
    out of the pool; the fold is one function."""
    ks = vs = None
    if isinstance(k, tuple):
        k, ks = k
    if isinstance(v, tuple):
        v, vs = v
    quant = ks is not None
    interpret = not backend.on_tpu()
    if not interpret and block_k % 128:
        raise ValueError(
            f"flash decode: a K/V block of {block_k} tokens is not a "
            "whole number of 128-lane tiles, and the chip's compiler "
            "does not take it. Use a max_len / page_tokens that is a "
            "multiple of 128, or decode_flash=False for the dense "
            "reference.")

    B, W, Hq, D = q.shape
    Hkv = k.shape[-3]
    assert Hq == Hkv * n_rep, (Hq, Hkv, n_rep)
    Wn = W * n_rep

    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)

    # [B, W, Hkv, n_rep, D] -> [B, Hkv, W*n_rep, D]: row i = w*n_rep + r
    # so the kernel recovers the window slot as i // n_rep.
    qg = q.reshape(B, W, Hkv, n_rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, Wn, D)
    q_spec = pl.BlockSpec((1, Hkv, Wn, D), lambda b, *_: (b, 0, 0, 0))
    fold_state = [pltpu.VMEM((Hkv, Wn, 1), jnp.float32),
                  pltpu.VMEM((Hkv, Wn, 1), jnp.float32),
                  pltpu.VMEM((Hkv, Wn, D), jnp.float32)]
    operands = [qg, k, v] + ([ks, vs] if quant else [])
    static = dict(n_rep=n_rep, n_k=n_k, scale=1.0 / D ** 0.5)

    if table is None:
        def kv_spec(rows):
            """One slot's block of every KV head: [1, Hkv, rows, bk]."""
            return pl.BlockSpec(
                (1, Hkv, rows, block_k),
                lambda b, j, pos_ref: (b, 0, 0, jnp.minimum(
                    j, _n_live(pos_ref[b], W, block_k, n_k) - 1)))

        prefetch = [pos]
        kernel = functools.partial(_decode_kernel, block_k=block_k,
                                   **static)
        grid, semantics = (B, n_k), ("parallel", "arbitrary")
        in_specs = ([q_spec] + [kv_spec(D)] * 2
                    + [kv_spec(1)] * (2 if quant else 0))
        scratch = fold_state
    else:
        # One layer's pool is a pool of one layer (a free reshape of
        # major dimensions), so the kernel addresses one form.
        if layer is None:
            layer = 0
            operands[1:] = [p[None] for p in operands[1:]]
        pools = operands[1:]
        prefetch = [pos, jnp.asarray(table, jnp.int32),
                    jnp.asarray(layer, jnp.int32).reshape(1)]
        kernel = functools.partial(_paged_walk_kernel, page_tokens=block_k,
                                   n_pools=len(pools), **static)
        grid, semantics = (B,), ("arbitrary",)
        in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools)
        scratch = ([pltpu.VMEM((2,) + p.shape[2:], p.dtype) for p in pools]
                   + [pltpu.SemaphoreType.DMA((len(pools), 2))]
                   + fold_state + [pltpu.SMEM((1,), jnp.int32)])

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs, out_specs=q_spec, scratch_shapes=scratch),
        out_shape=_out_struct((B, Hkv, Wn, D), q.dtype, q, k, v),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        # What the device trace prints the kernel as, by variant.
        name=("paged_" if table is not None else "") + "flash_decode_attend",
    )(*prefetch, *operands)
    return out.reshape(B, Hkv, W, n_rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, W, Hq * D)


def flash_decode_attend(q, kc, vc, pos, max_len, n_rep, block_k: int = 256):
    """Length-aware Pallas decode attention; drop-in for
    :func:`mpi_acx_tpu.models.decoding.dense_decode_attend` — same
    signature, same output [B, W, Hq*D], same (codes, scales) tuple
    convention for int8 caches. See the module docstring. Compiled for
    the chip, a ``max_len`` with no 128-multiple divisor raises."""
    block_k = _fit_block_k(max_len, block_k)
    return _decode_call(q, kc, vc, pos, n_rep, block_k, max_len // block_k)


def _layer_of(pool, layer):
    """One layer of a whole pool, sliced out (a copy of the layer: the
    dense reference's way), for arrays and (codes, scales) tuples."""
    if layer is None:
        return pool
    return jax.tree.map(lambda p: jax.lax.dynamic_index_in_dim(
        p, layer, 0, keepdims=False), pool)


def paged_gather_attend(q, kp, vp, table, pos, page_tokens, n_rep,
                        layer=None):
    """Dense reference for paged attention: gather each slot's pages
    into the contiguous ``[B, Hkv, D, max_len]`` layout the fixed-slot
    path attends and call :func:`dense_decode_attend` — identical
    shapes, identical XLA reduction, so a paged slot whose pages hold
    the fixed cache's rows produces BIT-EQUAL output (gathered garbage
    past the horizon contributes exactly 0.0 through the masked
    softmax, same as the fixed cache's own dead tail). With ``layer``
    the pools are whole (``[L, P, ...]``) and that layer is sliced out
    first."""
    from mpi_acx_tpu.models.decoding import dense_decode_attend

    B, max_pages = table.shape
    max_len = max_pages * page_tokens

    def gather(pool):
        t = jnp.take(pool, table, axis=0)     # [B, max_pages, H, *, pt]
        t = jnp.moveaxis(t, 1, 3)             # [B, H, *, max_pages, pt]
        return t.reshape(t.shape[:3] + (max_len,))

    kin = jax.tree.map(gather, _layer_of(kp, layer))
    vin = jax.tree.map(gather, _layer_of(vp, layer))
    return dense_decode_attend(q, kin, vin, pos, max_len, n_rep)


def paged_flash_decode_attend(q, kp, vp, table, pos, page_tokens, n_rep,
                              layer=None):
    """Pallas paged decode attention: K/V pools ``[P, Hkv, D,
    page_tokens]`` (plus (codes, scales) tuples for int8 pools) addressed through
    a ``[B, max_pages]`` block table — or, with ``layer``, the whole
    pools ``[L, P, Hkv, D, page_tokens]``, of which the kernel copies
    layer ``layer``'s live pages out in place (the live-page walk,
    :func:`_paged_walk_kernel`). Block size IS the page size, so
    at ``block_k == page_tokens`` this and :func:`flash_decode_attend`
    run identical FLOPs over identical block values (one fold): every
    row is bit-equal. Compiled for the chip, a page that is not a
    multiple of 128 tokens raises."""
    return _decode_call(q, kp, vp, pos, n_rep, page_tokens,
                        table.shape[1], table=table, layer=layer)


def _paged_kernels_fit(page_tokens):
    """The paged auto policy's one predicate, for the attend and the
    write alike: on a TPU, and a page Mosaic can tile."""
    return backend.on_tpu() and page_tokens % 128 == 0


def select_paged_decode_attend(decode_flash, page_tokens):
    """The paged arm of the ``select_attention`` idiom, keyed on the
    same ``decode_flash`` config field: ``None`` -> the Pallas paged
    kernel on a TPU when Mosaic can tile the page (``page_tokens % 128
    == 0``) and the gather-dense reference elsewhere (on CPU a dense
    einsum beats an interpreted kernel, and gather-dense is also the
    bit-equality anchor); ``True`` -> the kernel (interpret mode
    off-TPU); ``False`` -> the reference. Both take ``(q, kp, vp,
    table, pos, page_tokens, n_rep, layer=None)``; the chosen
    function's ``__name__`` is what
    ``ServingMetrics.paged_decode_attend`` records
    (``paged_flash_decode_attend`` is the live-page walk, all or
    nothing)."""
    if decode_flash is None:
        decode_flash = _paged_kernels_fit(page_tokens)
    return paged_flash_decode_attend if decode_flash else paged_gather_attend


def _kv_write_kernel(layer_ref, page_ref, off_ref, *refs):
    """One slot's grid step: for each pool, its page ``[H, *, pt]``
    with lane ``off`` replaced by the slot's fresh vector. ``refs`` =
    the fresh blocks ``[H, *, B]`` (every slot's vector, slots on
    lanes), the pages in, the pages out; the layer and the page were
    the index maps' business. The slot's column is picked by a masked
    lane sum of the values' BITS (one nonzero term: exact, and a -0.0
    or a NaN stays what it was), then broadcast along the page's lanes."""
    n = len(refs) // 3
    b = pl.program_id(0)
    off = off_ref[b]
    for fresh, page, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        x = fresh[...]
        floating = jnp.issubdtype(x.dtype, jnp.floating)
        bits = (jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
                if floating else x.astype(jnp.int32))
        slot = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        col = jnp.sum(jnp.where(slot == b, bits, 0), axis=-1, keepdims=True)
        if floating:
            col = jax.lax.bitcast_convert_type(col, jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, page.shape,
                                        page.ndim - 1)
        out[...] = jnp.where(lane == off, col.astype(page.dtype), page[...])


def paged_kv_write(pools, fresh, layer, write_page, off):
    """Write one token per slot into the page pools IN PLACE: for every
    pool ``[L, P, H, *, pt]`` of ``pools`` and its ``fresh`` ``[B, 1, H,
    *]``, slot b's vector lands at ``pool[layer, write_page[b], :, :,
    off[b]]``. One Pallas call over grid ``(slots,)`` for all the pools
    (K and V, and their scale pages when the cache is int8), each
    passed whole and aliased to its result; ``layer``, ``write_page``
    and ``off`` are scalar-prefetched and the index maps address
    ``(layer, page)``, so no value of a layer's pool is ever made. A
    slot's step reads its page, replaces one lane and writes the page
    back (module docstring: the page is the unit). Slots write distinct
    pages (each owns its pages; an idle slot its parking page), so the
    grid steps never collide. Returns the pools as a tuple.

    The call is jitted on its own, inside whatever program calls it,
    so that it is traced ONCE a process. A Mosaic kernel is serialized
    into its program with the Python traceback of where it was traced,
    ten frames deep, and the persistent compilation cache hashes those
    bytes: traced afresh under each caller, this call (eight frames
    below ``serve_paged_greedy``'s caller) made the step program a new
    cache entry, and a compile, for every call path to the server
    (PERF.md, PR 25). The attend's frames end inside this package."""
    return _paged_kv_write(tuple(pools), tuple(fresh), layer, write_page,
                           off, interpret=not backend.on_tpu())


@functools.partial(jax.jit, static_argnames="interpret")
def _paged_kv_write(pools, fresh, layer, write_page, off, interpret):
    B, n = write_page.shape[0], len(pools)
    # All slots' fresh vectors ride as ONE resident block a pool, slots
    # on lanes ([H, *, B]: a small transpose outside). One lane-wide
    # block a slot ([H, *, 1]) works too, but a lane-1 array is padded
    # to 128 lanes in HBM and XLA's relayout into it cost as much as
    # the page writes themselves (PERF.md, PR 25).
    fresh = [jnp.moveaxis(f[:, 0], 0, -1).astype(p.dtype)
             for f, p in zip(fresh, pools)]

    def page_spec(p):
        return pl.BlockSpec(
            (None, None) + p.shape[2:],
            lambda b, layer_ref, page_ref, off_ref: (
                layer_ref[0], page_ref[b], 0, 0, 0))

    fresh_specs = [pl.BlockSpec(f.shape, lambda b, *_: (0, 0, 0))
                   for f in fresh]
    page_specs = [page_spec(p) for p in pools]
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1),
                write_page.astype(jnp.int32), off.astype(jnp.int32))
    out = pl.pallas_call(
        _kv_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(B,),
            in_specs=fresh_specs + page_specs, out_specs=page_specs),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # Operand numbers count the prefetched scalars.
        input_output_aliases={len(prefetch) + n + j: j for j in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_kv_write",          # what the device trace prints
    )(*prefetch, *fresh, *pools)
    return tuple(out)


def paged_kv_write_dense(pools, fresh, layer, write_page, off):
    """Dense reference for :func:`paged_kv_write`, same arguments and
    result: slice the layer out of each pool, scatter the token columns
    (``.at[write_page, :, :, off].set``), put the layer back. Right
    anywhere and the anchor the kernel is held bit-equal to; on the
    chip it copies and relayouts the whole layer for one token."""
    def write(pool, f):
        lyr = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
        lyr = lyr.at[write_page, :, :, off].set(f[:, 0].astype(pool.dtype))
        return jax.lax.dynamic_update_index_in_dim(pool, lyr, layer, 0)
    return tuple(write(p, f) for p, f in zip(pools, fresh))


def select_paged_kv_write(decode_flash, page_tokens):
    """The write arm of the same idiom, keyed on the same
    ``decode_flash`` field and choosing as the attend does: ``None`` ->
    :func:`paged_kv_write` where :func:`select_paged_decode_attend`
    takes the Pallas kernel (on a TPU, ``page_tokens % 128 == 0``) and the
    dense write elsewhere; ``True`` -> the kernel (interpret mode
    off-TPU); ``False`` -> the dense write. Both take
    ``(pools, fresh, layer, write_page, off)``; the chosen function's
    ``__name__`` is what ``ServingMetrics.paged_kv_write`` records."""
    if decode_flash is None:
        decode_flash = _paged_kernels_fit(page_tokens)
    return paged_kv_write if decode_flash else paged_kv_write_dense


def auto_decode_attend(q, kc, vc, pos, max_len, n_rep):
    """THE decode flash/dense auto policy (mirrors ``auto_attention``):
    the Pallas kernel on TPU when the cache is long enough for
    block-skip to pay (max_len >= 1024) and Mosaic can tile it
    (max_len % 128 == 0); the dense reference elsewhere — including
    every CPU path, where a dense einsum beats an interpreted kernel."""
    if backend.on_tpu() and max_len >= 1024 and max_len % 128 == 0:
        return flash_decode_attend(q, kc, vc, pos, max_len, n_rep)
    from mpi_acx_tpu.models.decoding import dense_decode_attend

    return dense_decode_attend(q, kc, vc, pos, max_len, n_rep)


def select_decode_attend(decode_flash):
    """THE single flash/dense dispatch for the ``decode_flash`` config
    field (the ``select_attention`` idiom — every decode path routes
    here so the policy can't drift): ``None`` -> per-shape auto policy,
    ``True`` -> Pallas decode kernel (interpret mode off-TPU), ``False``
    -> dense reference. All returned callables take
    ``(q, kc, vc, pos, max_len, n_rep)``."""
    from mpi_acx_tpu.models.decoding import dense_decode_attend

    if decode_flash is None:
        return auto_decode_attend
    return flash_decode_attend if decode_flash else dense_decode_attend
