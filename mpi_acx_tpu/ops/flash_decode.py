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
  (PERF.md, PR 30). Nor per dead SLOT: told how many tokens each slot
  still owes (``left``, what only the serve loop knows), the walk
  starts no copy, folds nothing and fetches no block of ``q`` or of
  the stage for a slot that can deliver no token in this step (it
  owns no request, or its request ended earlier in the chunk), hops
  it in the cross-slot prefetch, and writes its row as zeros; a third
  of the pages a window's attends fetched were such slots' (PERF.md,
  PR 38). Every LIVE slot still walks at least one page.
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
* **written in place** — :func:`paged_kv_write` is the other Pallas
  call: the fresh K/V tokens of every slot go into lanes ``pos %
  page_tokens`` on of page ``(layer, write_page[b])`` of the same
  whole pool, aliased to the call's result. With tokens on lanes one
  token is one lane of every ``(8..32, 128)`` tile of the page, so a
  page is the smallest unit a DMA can move for it: the write is a
  page's read-modify-write, not a scatter (XLA's ``.at[..., off].set``
  on the lane dimension relayouts the whole layer there and back).
* **staged** — a page's read-modify-write for ONE token moves 256
  times what it stores, so a decode chunk keeps its own tokens in a
  stage (:func:`new_kv_stage`, ``(layer, slot)`` a block), the paged
  walk reads the pool up to the chunk's start and folds the stage
  behind it (one more block of the one fold), and the chunk writes
  all its tokens a slot with ONE :func:`paged_kv_write` a layer
  (models/kvpage.py: ``paged_decode_chunk``; PERF.md, PR 36).

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


def _fold_block(q_ref, kb, vb, scales, col0, pos, m_scr, l_scr, acc_scr, *,
                n_rep, scale, stop=None, tokens_minor=True):
    """THE fold, for both walks and for a chunk's stage: one block of
    K/V of every KV head of one slot goes into the online-softmax state
    held in VMEM scratch. ``kb``/``vb`` are ``[Hkv, D, block_k]`` (a
    cache block or a page: tokens on lanes) or, with ``tokens_minor``
    false, BOTH the one ``[Hkv, block_k, 2 D]`` of a stage's block
    (tokens on sublanes, a row V then K: the same two products with the other
    operand dimension contracted, ``q_ref`` then holding ``q`` under
    K's lanes and zeros under V's, and the output being the leading
    ``D`` lanes of the second product);
    ``scales`` is ``(ks, vs)``, each ``[Hkv, 1, block_k]``, when they
    are int8 codes, else empty. ``q_ref[0]`` is the slot's ``[Hkv,
    W*n_rep, D]`` tile. The mask is on ABSOLUTE positions: row i is
    window slot ``i // n_rep`` at ``pos + i // n_rep``, column c of the
    block is cache position ``col0 + c``, visible up to the row's own
    position and below ``stop`` when one is given (a pool behind a
    stage is stale from the chunk's start on); on fully visible blocks
    it is all true. The scales multiply the SMALL tensors: K's the
    scores, V's the probabilities."""
    quant = bool(scales)
    Wn = q_ref.shape[-2]
    tok = 2 if tokens_minor else 1          # the tokens' axis of kb, vb
    block_k = kb.shape[tok]
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
        q, kb, (((2,), (3 - tok,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec)
    if quant:
        s = s * scales[0]                                   # [Hkv, Wn, bk]
    rows = pos + jax.lax.broadcasted_iota(
        jnp.int32, (1, Wn, 1), 1) // n_rep
    cols = col0 + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, block_k), 2)
    seen = rows >= cols
    if stop is not None:
        seen = seen & (cols < stop)
    s = jnp.where(seen, s, _NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        p = p * scales[1]
    pv = jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((2,), (tok,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=prec)
    acc_scr[...] = corr * acc_scr[...] + pv[..., :acc_scr.shape[-1]]
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
                    j * block_k, pos, m_scr, l_scr, acc_scr,
                    n_rep=n_rep, scale=scale)

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _paged_walk_kernel(pos_ref, table_ref, layer_ref, step_ref, nxt_ref,
                       held_ref, q_ref, *refs, page_tokens, n_rep, n_k,
                       n_pools, n_stage, scale, v_dim=None):
    """The paged walk: one grid step a slot, and inside it one pass
    over the slot's LIVE pages and nothing per dead page. The pools
    stay in HBM (``refs[:n_pools]``: K, V, and the scale pools of an
    int8 cache); the kernel copies page ``(layer, table[b, j])`` of
    each into one of two VMEM buffers a pool and folds it while the
    next page's copies are in flight. The next page of a slot's LAST
    page is the next LIVE slot's FIRST: it is already on its way when
    that slot's grid step starts, as a BlockSpec pipeline would have
    had it (32 exposed copy latencies a call otherwise). ``walked``
    (SMEM) counts the pages folded so far in the call: its parity is
    the buffer the current page sits in, across slots. Needs sequential
    grid steps (``arbitrary``).

    A DEAD slot (one that can deliver no token in this step: it owns no
    request, or its request ended earlier in the chunk; ``_live_slots``)
    costs the walk a grid step (0.23 us) and nothing else: no page copy
    is started or waited for, nothing is folded, its tile of ``q`` and
    its block of the stage are not fetched (the index maps hold the
    last live slot's, ``held_ref``: 205 KB and 0.25 us a dead slot a
    call, 14% of the attend in a draining chunk: PERF.md, PR 38), and
    its output row is ZEROS: defined and finite, where ``acc / l`` of an
    empty fold is NaN and would reach the stage, the slot's pages and,
    through the pool, a later owner. ``nxt_ref`` (``[B + 1]``) is the
    first live slot at or after each slot, ``B`` where there is none:
    slot ``b`` is live iff ``nxt_ref[b] == b``, the call's first copy is
    ``nxt_ref[0]``'s, and the slot prefetched behind ``b``'s last page
    is ``nxt_ref[b + 1]``.

    ``n_stage``: a decode chunk keeps its own tokens in a stage
    (:func:`new_kv_stage`; ``refs`` then holds, behind the pools, ``q``
    widened for it and block ``(layer, slot)`` of each of its arrays,
    brought in by the grid's pipeline while the slot before is walked)
    and the pool is stale from the slot's position at the chunk's
    start on, ``pos0 = pos - step``. The walk then covers the pages
    that hold a token below ``pos0`` (for a live slot at least one, so
    that it has a first page to prefetch: with ``pos0`` 0 it is masked
    whole, and what a masked block leaves in the fold's state the next
    live column wipes, the weight ``exp(-1e30 - m)`` being 0.0), and
    the stage's tokens ``0..step`` are one more block of the same
    fold.

    ``v_dim``: a LATENT pool (one row a token, ``[1, D, page_tokens]`` a
    page, read by all ``n_rep`` query heads; no V pool): the value is
    the first ``v_dim`` features of the key's row, so ``P @ V`` runs
    against the leading sublanes of the SAME block that the scores were
    taken from, and the result is ``v_dim`` wide. Its stage block is
    ``[chunk, D]``, the rows as the pages hold them, met by ``q``
    itself."""
    del held_ref                                # the index maps' alone
    pools, refs = refs[:n_pools], refs[n_pools:]
    staged, (o_ref, *refs) = refs[:n_stage], refs[n_stage:]
    bufs, (sem, m_scr, l_scr, acc_scr, walked) = (refs[:n_pools],
                                                  refs[n_pools:])
    b, B = pl.program_id(0), pl.num_programs(0)
    layer, pos = layer_ref[0], pos_ref[b]
    live, after = nxt_ref[b] == b, nxt_ref[b + 1]
    if staged:
        pos0 = pos - step_ref[0]
        n = jnp.clip((pos0 + page_tokens - 1) // page_tokens, 1, n_k)
    else:
        pos0 = None
        n = _n_live(pos, q_ref.shape[2] // n_rep, page_tokens, n_k)

    def copies(slot, j, buf):
        page = table_ref[slot, j]
        return [pltpu.make_async_copy(pool.at[layer, page], dst.at[buf],
                                      sem.at[i, buf])
                for i, (pool, dst) in enumerate(zip(pools, bufs))]

    @pl.when(b == 0)
    def _first_page_of_the_call():
        walked[0] = 0

        @pl.when(nxt_ref[0] < B)
        def _():
            for c in copies(nxt_ref[0], 0, 0):
                c.start()

    @pl.when(live)
    def _walk():
        first = walked[0]
        _fold_init(m_scr, l_scr, acc_scr)

        def page(j, _):
            buf = (first + j) % 2
            more = j + 1 < n

            @pl.when(more | (after < B))
            def _next_page():
                for c in copies(jnp.where(more, b, after),
                                jnp.where(more, j + 1, 0), 1 - buf):
                    c.start()

            for c in copies(b, j, buf):
                c.wait()
            kb, *rest = [dst[buf] for dst in bufs]
            if v_dim is not None:
                rest = [kb[:, :v_dim]]
            vb, *scales = rest
            _fold_block(q_ref, kb, vb, scales, j * page_tokens, pos, m_scr,
                        l_scr, acc_scr, n_rep=n_rep, scale=scale, stop=pos0)

        jax.lax.fori_loop(0, n, page, None)
        walked[0] = first + n
        if staged:
            if v_dim is None:
                q_wide, vk, *scales = staged
                vk = jnp.swapaxes(vk[...], 0, 1)    # [chunk, H, 2 D]
            else:               # the one head's rows, met by q itself
                q_wide, vk, scales = q_ref, staged[0][...][None], []
                # Rows the chunk has not filled yet count as zeros
                # whatever they hold: their columns weigh 0.0, and 0.0
                # times a non-finite value is NaN, which the flush would
                # carry into pages a later request inherits. (They ARE
                # zeros as long as the compiled chunk fills its stage:
                # see stage_put. This costs nothing that a trace shows.)
                filled = jax.lax.broadcasted_iota(
                    jnp.int32, vk.shape, 1) <= step_ref[0]
                vk = jnp.where(filled, vk, jnp.zeros_like(vk))
            _fold_block(q_wide, vk, vk, [s[...] for s in scales], pos0,
                        pos, m_scr, l_scr, acc_scr, n_rep=n_rep, scale=scale,
                        tokens_minor=False)
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def _live_slots(left, step, B):
    """What the paged walk is told of the slots' lives, from ``left``
    (``[B]``: the tokens each slot's request still owes at the chunk's
    start; None: every slot live) and the chunk step: slot ``b`` can
    deliver a token iff ``step < left[b]``. Returns ``nxt`` ``[B +
    1]``, the first live slot at or after each slot (``B``: none, and
    behind the last), and ``held`` ``[B]``, the slot whose blocks the
    grid's pipeline holds at each grid step: the slot itself while it
    lives, else the last live one before it (slot 0 in front of them
    all), so that a dead slot's step changes no block index and
    fetches nothing. Two running extrema that depend on the step
    alone: XLA lifts them out of the scan over layers (an elementwise
    pass behind one it leaves inside, a fusion a call: each vector is
    a cumulative op's own result)."""
    slots = jnp.arange(B + 1, dtype=jnp.int32)
    if left is None:
        return slots, slots[:B]
    live = step < jnp.asarray(left, jnp.int32)
    nxt = jax.lax.cummin(jnp.where(jnp.append(live, True), slots, B),
                         reverse=True)
    return nxt, jax.lax.cummax(jnp.where(live, slots[:B], 0))


def _decode_call(q, k, v, pos, n_rep, block_k, n_k, table=None, layer=None,
                 stage=None, left=None, v_dim=None, scale=None):
    """The pallas_call behind both kernels. ``k``/``v`` are K/V
    arrays or (codes, scales) tuples in cache layout
    ([B, Hkv, *, max_len]) or, with ``table``, pool layout: the whole
    pool ([L, P, Hkv, *, page_tokens]) with ``layer`` (a traced scalar
    is fine) naming the layer to read, or one layer of it
    ([P, Hkv, *, page_tokens]) without. What the code observes
    (``table is not None``) chooses the walk: a cache row's blocks by a
    ``(B, n_k)`` grid, a slot's live pages by the kernel's own copies
    out of the pool; the fold is one function. ``stage`` (paged only):
    ``(arrays, step)``, a chunk's stage (:func:`new_kv_stage`) and the
    chunk step it is filled up to; ``left`` (paged only): the tokens
    each slot still owes, of which the walk reads which slots are dead
    at this step (:func:`_live_slots`). ``v_dim`` (paged only, with
    ``v`` None): ``k`` is a latent pool whose rows' first ``v_dim``
    features are the values (:func:`_paged_walk_kernel`); ``scale``:
    the scores' factor where it is not ``1 / sqrt(D)``."""
    assert (v is None) == (v_dim is not None) and (v is not None
                                                   or table is not None)
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
    Dv = D if v_dim is None else v_dim
    q_spec = pl.BlockSpec((1, Hkv, Wn, D), lambda b, *_: (b, 0, 0, 0))
    o_spec = pl.BlockSpec((1, Hkv, Wn, Dv), lambda b, *_: (b, 0, 0, 0))
    fold_state = [pltpu.VMEM((Hkv, Wn, 1), jnp.float32),
                  pltpu.VMEM((Hkv, Wn, 1), jnp.float32),
                  pltpu.VMEM((Hkv, Wn, Dv), jnp.float32)]
    operands = ([qg, k] + ([v] if v is not None else [])
                + ([ks, vs] if quant else []))
    static = dict(n_rep=n_rep, n_k=n_k,
                  scale=1.0 / D ** 0.5 if scale is None else scale)

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
        staged, step = [], 0
        if stage is not None:
            arrays, step = stage
            # the stage, one layer of it being a stage of one layer; in
            # front of it q under K's lanes of a stage row, zeros under
            # V's (a latent stage's rows are met by q itself)
            whole = 5 if v_dim is None else 4
            staged = [a if a.ndim == whole else a[None] for a in arrays]
            if v_dim is None:
                staged.insert(0, jnp.pad(qg, [(0, 0)] * 3 + [(D, 0)]))
            operands += staged
        prefetch = [pos, jnp.asarray(table, jnp.int32),
                    jnp.asarray(layer, jnp.int32).reshape(1),
                    jnp.asarray(step, jnp.int32).reshape(1),
                    *_live_slots(left, step, B)]
        kernel = functools.partial(_paged_walk_kernel, page_tokens=block_k,
                                   n_pools=len(pools), n_stage=len(staged),
                                   v_dim=v_dim, **static)
        grid, semantics = (B,), ("arbitrary",)

        def q_held(width):
            """A slot's own tile of ``q``, a dead slot's left where the
            last live slot's is (``held``, the last prefetched)."""
            return pl.BlockSpec((1, Hkv, Wn, width),
                                lambda b, *refs: (refs[-1][b], 0, 0, 0))

        in_specs = ([q_held(D)]
                    + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools))
        if staged:
            in_specs += [q_held(2 * D)] * (v_dim is None) + [
                pl.BlockSpec((None, None) + a.shape[2:],
                             lambda b, _, __, layer_ref, *refs, n=a.ndim - 2:
                             (layer_ref[0], refs[-1][b]) + (0,) * n)
                for a in staged[v_dim is None:]]
        scratch = ([pltpu.VMEM((2,) + p.shape[2:], p.dtype) for p in pools]
                   + [pltpu.SemaphoreType.DMA((len(pools), 2))]
                   + fold_state + [pltpu.SMEM((1,), jnp.int32)])

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=grid,
            in_specs=in_specs, out_specs=o_spec, scratch_shapes=scratch),
        out_shape=_out_struct((B, Hkv, Wn, Dv), q.dtype, q, k,
                              *(() if v is None else (v,))),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        # What the device trace prints the kernel as, by variant.
        name=("paged_" if table is not None else "") + "flash_decode_attend",
    )(*prefetch, *operands)
    return out.reshape(B, Hkv, W, n_rep, Dv).transpose(
        0, 2, 1, 3, 4).reshape(B, W, Hq * Dv)


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


def new_kv_stage(pools, n_slots, chunk, v_dim=None):
    """A decode chunk's stage, zeroed: the chunk's own tokens of every
    layer with pages, ``(layer, slot)`` a block. K and V (or their
    codes) lie in ONE array ``[L, B, chunk, H, 2 D]``: a token of a slot
    is ``[H, 2 D]``, every head's V then K side by side on the lanes. A
    step's update ``[1, B, 1, H, 2 D]`` then covers WHOLE tiles (the
    token is a major dimension): with the tokens on the sublanes, ``[L,
    B, H, chunk, 2 D]``, XLA's update of one row of 800 tiles read and
    wrote every one of them, 13 us a layer a step (PERF.md, PR 36). The
    attend swaps the block's two major dimensions in VMEM (3 us a call)
    and folds ``[H, chunk, 2 D]``: the lanes contracted for the scores
    (``q`` under K's, zeros under V's), the tokens for the output (whose
    leading ``D`` lanes are V's), both products natural on that block.
    The minor dimension is two heads' widths, not the chunk (an array
    whose last dimension is 32 is laid out with 128 lanes in HBM, 4x;
    this one fills its 128 at a head of 64; the heads pad to a multiple
    of 16 rows). Behind it, for an int8 cache, K's and V's scales as in
    a page, ``[L, B, H, 1, chunk]`` each.

    A LATENT pool (``v_dim``: ``pools`` is the one pool ``[L, P, 1, D,
    pt]``, a row a token, its value the row's head: as every stage
    helper is told which layout it has) stages the rows as they are,
    ``[L, B, chunk, D]``: one head has no second major dimension to
    swap, and a row is met by ``q`` unwidened."""
    (L, _, H, D, _), kv = pools[0].shape, pools[0].dtype
    if v_dim is not None:
        return (jnp.zeros((L, n_slots, chunk, D), kv),)
    return (jnp.zeros((L, n_slots, chunk, H, 2 * D), kv),) + tuple(
        jnp.zeros((L, n_slots, H, 1, chunk), p.dtype) for p in pools[2:])


def stage_put(stage, fresh, layer, step, v_dim=None):
    """``stage`` (:func:`new_kv_stage`) with every slot's ``fresh``
    token (``[B, 1, H, *]`` a pool, as the write takes it: K, V, and
    their scales) as token ``step`` of layer ``layer``: XLA's update in
    place, one token of every slot's block (one lane of the scales').
    A latent pool's ``fresh`` is its one row a slot ``[B, 1, 1, D]``."""
    zero = jnp.int32(0)
    if v_dim is not None:
        # The layer's block rewritten whole with row ``step`` selected
        # in, NOT an update of one row: a one-row update (one SUBLANE
        # row of packed bf16 tiles) lets XLA's TPU compiler see a loop
        # that overwrites every row, and it then drops the stage's zero
        # fill (``AllocateBuffer``: uninitialised memory) without
        # counting the attend's reads of rows not yet written inside
        # that loop. Reading the block keeps the fill
        # (tests/test_tpu_compile.py::_unfilled_stage holds every
        # family's chunk to it; PERF.md, PR 40). 2.4 MB a layer-step at
        # 64 slots, about what the one-row update's tiles cost.
        slab = jax.lax.dynamic_index_in_dim(stage[0], layer, 0)
        row = fresh[0][None, :, :, 0].astype(slab.dtype)    # [1, B, 1, D]
        at = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 2) == step
        return (jax.lax.dynamic_update_slice(
            stage[0], jnp.where(at, row, slab), (layer, zero, zero, zero)),)
    k, v, *scales = fresh
    if stage[0].shape[0] == 1 and not scales:
        # ONE layer with pages (``nemotron_h``'s stage of a pipeline):
        # the layer's index is known to be 0, so a one-token update a
        # step is again a loop that overwrites every element and the
        # zero fill is dropped as above; the same cure, 3 MB a step at
        # 96 slots.
        row = jnp.concatenate([v, k], axis=-1)[None].astype(stage[0].dtype)
        at = jax.lax.broadcasted_iota(jnp.int32, stage[0].shape, 2) == step
        return (jnp.where(at, row, stage[0]),)

    def put(into, row, at):
        return jax.lax.dynamic_update_slice(
            into, row[None].astype(into.dtype), (layer, zero) + at)
    return (put(stage[0], jnp.concatenate([v, k], axis=-1),
                (step, zero, zero)),
            *(put(s, jnp.swapaxes(f, 1, 2), (zero, zero, step))
              for s, f in zip(stage[1:], scales)))


def stage_tokens(stage, layer=None, v_dim=None):
    """Layer ``layer`` of a stage (or the one layer it is) as the write
    takes tokens: K, V, and their scales, ``[B, chunk, H, *]`` each (a
    latent pool's stage: its rows, ``[B, chunk, 1, D]``)."""
    vk, *scales = _layer_of(stage, layer)
    if v_dim is not None:
        return (vk[:, :, None],)
    D = vk.shape[-1] // 2
    return (vk[..., D:], vk[..., :D],
            *(a.transpose(0, 3, 1, 2) for a in scales))


def paged_gather_attend(q, kp, vp, table, pos, page_tokens, n_rep,
                        layer=None, stage=None, left=None, v_dim=None,
                        scale=None):
    """Dense reference for paged attention: gather each slot's pages
    into the contiguous ``[B, Hkv, D, max_len]`` layout the fixed-slot
    path attends and call :func:`dense_decode_attend` — identical
    shapes, identical XLA reduction, so a paged slot whose pages hold
    the fixed cache's rows produces BIT-EQUAL output (gathered garbage
    past the horizon contributes exactly 0.0 through the masked
    softmax, same as the fixed cache's own dead tail). With ``layer``
    the pools are whole (``[L, P, ...]``) and that layer is sliced out
    first. With ``stage`` (``(arrays, step)``, as the kernel takes it)
    the chunk's staged tokens ``0..step`` are first written into the
    sliced layer by the dense write itself
    (:func:`paged_kv_write_runs`): what each slot's pages would hold
    had every token been written as it came, an idle slot's parking
    page and a row's clipped end included, so a staged chunk is
    bit-equal to the same steps with the dense write. With ``left``
    (``[B]``: the tokens each slot still owes at the chunk's start) the
    row of a slot that is dead at this step (``step >= left[b]``; step 0
    without a stage) is zeros, as the kernel's is: the two agree on
    every row. A latent pool (``vp`` None, ``v_dim``, ``scale``: as the
    kernel takes them) is gathered once and its rows' first ``v_dim``
    features are the values."""
    from mpi_acx_tpu.models.decoding import dense_decode_attend

    B, max_pages = table.shape
    max_len = max_pages * page_tokens
    step = 0

    def gather(pool):
        t = jnp.take(pool, table, axis=0)     # [B, max_pages, H, *, pt]
        t = jnp.moveaxis(t, 1, 3)             # [B, H, *, max_pages, pt]
        return t.reshape(t.shape[:3] + (max_len,))

    kl, vl = _layer_of(kp, layer), _layer_of(vp, layer)
    if stage is not None:
        staged, step = stage
        quant = isinstance(kl, tuple)
        pools = ((kl[0], vl[0], kl[1], vl[1]) if quant
                 else (kl, vl) if v_dim is None else (kl,))
        pos0 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32) - step, (B,))
        pools = paged_kv_write_runs(
            paged_kv_write_dense, [p[None] for p in pools],
            stage_tokens(staged, layer, v_dim), 0, table, pos0, page_tokens,
            n_live=step + 1)
        k, *rest = (p[0] for p in pools)
        v, *scales = rest if v_dim is None else [None]      # (no V pool)
        kl, vl = ((k, scales[0]), (v, scales[1])) if quant else (k, v)
    kin = jax.tree.map(gather, kl)
    vin = jax.tree.map(gather, vl) if v_dim is None else kin[:, :, :v_dim]
    out = dense_decode_attend(q, kin, vin, pos, max_len, n_rep, scale=scale)
    if left is None:
        return out
    return jnp.where((step < jnp.asarray(left))[:, None, None], out, 0)


def paged_flash_decode_attend(q, kp, vp, table, pos, page_tokens, n_rep,
                              layer=None, stage=None, left=None, v_dim=None,
                              scale=None):
    """Pallas paged decode attention: K/V pools ``[P, Hkv, D,
    page_tokens]`` (plus (codes, scales) tuples for int8 pools) addressed through
    a ``[B, max_pages]`` block table — or, with ``layer``, the whole
    pools ``[L, P, Hkv, D, page_tokens]``, of which the kernel copies
    layer ``layer``'s live pages out in place (the live-page walk,
    :func:`_paged_walk_kernel`). Block size IS the page size, so
    at ``block_k == page_tokens`` this and :func:`flash_decode_attend`
    run identical FLOPs over identical block values (one fold): every
    row is bit-equal. Compiled for the chip, a page that is not a
    multiple of 128 tokens raises. With ``stage`` (``(arrays, step)``:
    a decode chunk's :func:`new_kv_stage` and the chunk step it is
    filled up to, ``pos`` being the slots' positions NOW) the pool
    counts up to ``pos - step`` and the stage's tokens ``0..step``
    follow it: still one call with the one result. With ``left``
    (``[B]``: the tokens each slot still owes at the chunk's start) a
    slot that can deliver nothing at this step (``step >= left[b]``)
    costs no page copy, no fold and no stage block, and its row is
    zeros; live rows are what they are without ``left``, bit for bit.
    With ``vp`` None and ``v_dim`` the pool is a LATENT one (``[L, P,
    1, D, page_tokens]``, a row a token read by all ``n_rep`` heads, no
    V pool): the values are the first ``v_dim`` features of the rows,
    the result ``[B, W, Hq * v_dim]``; ``scale`` replaces the scores'
    ``1 / sqrt(D)``."""
    return _decode_call(q, kp, vp, pos, n_rep, page_tokens,
                        table.shape[1], table=table, layer=layer,
                        stage=stage, left=left, v_dim=v_dim, scale=scale)


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
    table, pos, page_tokens, n_rep, layer=None, stage=None,
    left=None, v_dim=None, scale=None)``; the chosen
    function's ``__name__`` is what
    ``ServingMetrics.paged_decode_attend`` records
    (``paged_flash_decode_attend`` is the live-page walk, all or
    nothing)."""
    if decode_flash is None:
        decode_flash = _paged_kernels_fit(page_tokens)
    return paged_flash_decode_attend if decode_flash else paged_gather_attend


def _kv_write_kernel(layer_ref, page_ref, off_ref, *refs, n_tokens, stride):
    """Grid step ``(b, h)``: for each pool, page ``page_ref[b, h]``
    ``[H, *, pt]`` with the lanes replaced that slot b's fresh tokens
    land in: token t in lane ``(off + t) % pt`` of the slot's page
    ``(off + t) // pt`` (0 or 1: ``n_tokens <= pt``). ``refs`` = the
    fresh blocks ``[H, *, pt]`` (tokens on lanes, slot b's from lane
    ``b * stride % pt`` on), the pages in, the pages out; the layer and
    the page were the index maps' business. The tokens are ROLLED into
    place along the lanes, 32 bits wide, so every value keeps its bits
    (a -0.0 or a NaN stays what it was). Where step ``(b, 1)`` is given
    the page of ``(b, 0)`` again (no token crosses into a next page, or
    the table row ends there and both halves land in its last page) the
    block is not fetched again and the result block, still resident, is
    what the second half goes on top of."""
    n = len(refs) // 3
    b, h = pl.program_id(0), pl.program_id(1)
    off = off_ref[b]
    again = (h > 0) & (page_ref[b, h] == page_ref[b, 0])
    for fresh, page, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        pt = page.shape[-1]
        x = fresh[...]
        wide = jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.int32
        placed = pltpu.roll(x.astype(wide), (off - b * stride) % pt,
                            axis=x.ndim - 1).astype(page.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, page.shape, page.ndim - 1)
        t = jnp.where(lane < off, lane + pt, lane) - off    # the lane's token
        mine = (t < n_tokens) & ((off + t >= pt).astype(jnp.int32) == h)
        out[...] = jnp.where(mine, placed,
                             jnp.where(again, out[...], page[...]))


def paged_kv_write(pools, fresh, layer, write_page, off):
    """Write ``n`` tokens per slot into the page pools IN PLACE: for
    every pool ``[L, P, H, *, pt]`` of ``pools`` and its ``fresh`` ``[B,
    n, H, *]``, slot b's token t lands at ``pool[layer, write_page[b,
    (off[b] + t) // pt], :, :, (off[b] + t) % pt]``. ``n`` = 1 is a
    decode step's write (``write_page`` ``[B]``); a decode chunk's flush
    writes its ``n <= pt`` staged tokens a slot at once (``write_page``
    ``[B, 2]``: the page the first token lands in and the next, or the
    first again where no token crosses: :func:`chunk_write_pages`; more
    tokens than a page go in runs, :func:`paged_kv_write_runs`), each
    page read and written once where ``n`` one-token writes moved it
    ``n`` times. One Pallas
    call over grid ``(slots, pages a slot)`` for all the pools (K and V,
    and their scale pages when the cache is int8), each passed whole
    and aliased to its result; ``layer``, ``write_page`` and ``off`` are
    scalar-prefetched and the index maps address ``(layer, page)``, so
    no value of a layer's pool is ever made. A step reads its page,
    replaces the lanes and writes the page back (module docstring: the
    page is the unit). Slots write distinct pages (each owns its pages;
    an idle slot its parking page), so the slots' steps never collide.
    Returns the pools as a tuple.

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
    (B, n_tokens), n = fresh[0].shape[:2], len(pools)
    pt = pools[0].shape[-1]
    assert n_tokens <= pt, (n_tokens, pt)
    write_page = write_page.reshape(B, -1)
    # All slots' fresh tokens ride with tokens on LANES, a slot's
    # ``stride`` lanes after the slot's before ([H, *, B * stride]: a
    # small transpose outside), a page-wide block of them resident a
    # grid step; ``stride`` is n where the slots' runs then tile the
    # page-wide blocks, else a whole block a slot. One lane-wide block
    # a slot ([H, *, 1]) works too, but a lane-1 array is padded to 128
    # lanes in HBM and XLA's relayout into it cost as much as the page
    # writes themselves (PERF.md, PR 25).
    stride = n_tokens if pt % n_tokens == 0 else pt

    def lanes(f, p):
        f = jnp.moveaxis(f, (0, 1), (2, 3)).astype(p.dtype)    # [H, *, B, n]
        f = jnp.pad(f, [(0, 0)] * 3 + [(0, stride - n_tokens)])
        f = f.reshape(f.shape[:2] + (B * stride,))
        return jnp.pad(f, [(0, 0)] * 2 + [(0, -(B * stride) % pt)])

    fresh = [lanes(f, p) for f, p in zip(fresh, pools)]

    def page_spec(p):
        return pl.BlockSpec(
            (None, None) + p.shape[2:],
            lambda b, h, layer_ref, page_ref, off_ref: (
                layer_ref[0], page_ref[b, h], 0, 0, 0))

    fresh_specs = [pl.BlockSpec(f.shape[:2] + (pt,),
                                lambda b, h, *_: (0, 0, b * stride // pt))
                   for f in fresh]
    page_specs = [page_spec(p) for p in pools]
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1),
                write_page.astype(jnp.int32), off.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_kv_write_kernel, n_tokens=n_tokens, stride=stride),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=write_page.shape,
            in_specs=fresh_specs + page_specs, out_specs=page_specs),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # Operand numbers count the prefetched scalars.
        input_output_aliases={len(prefetch) + n + j: j for j in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_kv_write",          # what the device trace prints
    )(*prefetch, *fresh, *pools)
    return tuple(out)


def paged_kv_write_dense(pools, fresh, layer, write_page, off, n_live=None):
    """Dense reference for :func:`paged_kv_write`, same arguments and
    result: slice the layer out of each pool, scatter the token columns
    (``.at[page, :, :, lane].set``), put the layer back. Right
    anywhere and the anchor the kernel is held bit-equal to; on the
    chip it copies and relayouts the whole layer for one token. With
    ``n_live`` only the tokens below it are written (the rows of a
    stage that a chunk has filled so far: :func:`paged_gather_attend`)."""
    B, n_tokens = fresh[0].shape[:2]
    P, pt = pools[0].shape[1], pools[0].shape[-1]
    t = jnp.arange(n_tokens)
    at = off[:, None] + t                                   # [B, n]
    page = jnp.take_along_axis(write_page.reshape(B, -1), at // pt, axis=1)
    if n_live is not None:
        page = jnp.where(t < n_live, page, P)               # past the pool

    def write(pool, f):
        lyr = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
        lyr = lyr.at[page, :, :, at % pt].set(f.astype(pool.dtype),
                                              mode="drop")
        return jax.lax.dynamic_update_index_in_dim(pool, lyr, layer, 0)
    return tuple(write(p, f) for p, f in zip(pools, fresh))


def chunk_write_pages(table, pos, n: int, page_tokens: int):
    """``[B, 2]`` (``[B, 1]`` at ``n`` 1): the pages that ``n <=
    page_tokens`` tokens a slot from ``pos`` on land in, as
    :func:`paged_kv_write` takes them: the first token's (the index
    clipped at the table row's end, as a step's write clips it) and the
    next one's, or the first again where no token crosses into it."""
    assert n <= page_tokens, (n, page_tokens)
    max_pages = table.shape[1]
    first = pos // page_tokens
    cols = [first]
    if n > 1:
        cols.append(jnp.where(pos % page_tokens + n > page_tokens,
                              first + 1, first))
    return jnp.take_along_axis(
        table, jnp.minimum(jnp.stack(cols, axis=1), max_pages - 1), axis=1)


def paged_kv_write_runs(write, pools, fresh, layer, table, pos, page_tokens,
                        n_live=None):
    """``write`` (:func:`paged_kv_write` or its dense twin) for ANY
    number of tokens a slot: ``fresh`` ``[B, n, H, *]`` lands at
    positions ``pos, pos + 1, ...`` of each slot's table row in runs of
    at most a page, one call a run with the one or two pages that run
    touches (:func:`chunk_write_pages`), in order, so that where runs
    share a page (an idle slot's parking page, a row's clipped end) the
    later token stays, as after one-token writes. A chunk no longer
    than a page, every cell's, is ONE call. ``n_live``: the dense
    twin's, counted over all ``n`` tokens."""
    n = fresh[0].shape[1]
    for s in range(0, n, page_tokens):
        m = min(page_tokens, n - s)
        live = {} if n_live is None else {"n_live": n_live - s}
        pools = write(pools, [f[:, s:s + m] for f in fresh], layer,
                      chunk_write_pages(table, pos + s, m, page_tokens),
                      (pos + s) % page_tokens, **live)
    return pools


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
