"""Paged KV cache: block-table allocator + radix prefix sharing.

The serving stack (models/serving.py) and the disagg decode workers
(models/disagg.py) historically gave every slot a private
``[max_len]`` cache row, so a request at position 40 of a 4096-token
cache owned 4096 positions of HBM even though the flash-decode kernel
no longer *reads* the dead tail — at scale the server is
memory-capacity-bound, not compute-bound. This module replaces the
per-slot rows with a shared pool of fixed-size PAGES (default 128
tokens, matching flash-decode's block granularity) and a per-slot
block table:

* **Pool** — ``{'k','v': [L, P, H, Dh, page_tokens]}`` device buffers
  in the cache layout (decoding.to_cache_layout: a page of one head is
  a ``[Dh, page_tokens]`` slab of whole TPU tiles), kept WHOLE through
  the decode step: its kernels address a page as ``(layer, page)``
  from prefetched scalars and no layer is ever sliced out
  (:func:`paged_decode_step`), plus ``'ks','vs'``
  ``[L, P, H, 1, page_tokens]`` f32 scale pages when the cache is int8 —
  ops/kvquant.py codes + scales stay the only page-resident form, the
  same EQuARX rule the wire plane enforces). The trailing ``n_slots``
  pages of P are per-slot PARKING pages: an idle slot's table points
  every entry at its own parking page, so the lockstep decode step's
  writes for idle slots land somewhere harmless instead of corrupting
  pages a live request owns. A decode CHUNK does not write a page a
  token: it keeps its own tokens in a stage and rewrites each page it
  touched once, after its steps (:func:`paged_decode_chunk`); between
  chunks every page is complete.
* **Block tables** — host-side ``[n_slots, max_pages]`` int32 rows
  (mirrored to the device per step) mapping token position
  ``t`` of slot ``b`` to pool page ``table[b, t // page_tokens]``.
* **Allocator** — :class:`PageAllocator`: a free list plus per-page
  refcounts; pages are shared by refcount and reclaimed at zero.
* **Radix prefix cache** — :class:`RadixPrefixCache`: a trie over
  full-page token chunks, so requests sharing a system prompt store
  the shared pages ONCE; a prefix hit seats the cached pages and
  prefill runs only on the suffix (:func:`prefill` with a history).
  Shared pages are never written (the matched depth is capped so the
  suffix always starts at a page boundary with >= 1 fresh token);
  copy-on-write (:meth:`PagedKV.ensure_writable`) guards the
  invariant defensively.

Bit-equality contract: the paged dense attend gathers the slot's
pages into the SAME ``[B, H, Dh, max_len]`` shape the fixed-slot path
attends (mpi_acx_tpu/ops/flash_decode.py:paged_gather_attend), so on
a cold (no-prefix-hit) schedule paged greedy serving is bit-equal to
fixed-slot ``serve_greedy`` — dead gathered positions contribute
exactly 0.0 through the masked softmax (finite garbage, never NaN).
Prefix-HIT prefills compute the suffix against the stored pages with
different tensor shapes than the cold full-prompt pass, so hit-path
outputs are deterministic per backend but not bitwise-pinned to the
cold path (docs/DESIGN.md §19).

What the plane asks of a model family it asks through ONE seam,
:class:`PagedSpec` (``family.paged_spec(cfg)``): per layer an operator
kind (attention | conv | mamba | mamba2 | none), an FFN kind (dense | moe |
none) and a cache kind (pages | state | none), the layers grouped into :class:`Segment` s of whole
periods that one ``lax.scan`` each can ride, and the family's own
functions for each piece. :func:`paged_decode_step`, its
whole-sequence sibling :func:`sequence_pass` (under :func:`prefill`,
which ``serving.paged_prefill`` / ``paged_suffix_prefill`` run, and
:func:`forward`) and :class:`PagedKV` read nothing else of a family:
a family is its operators and its spec. GPT-2
(``transformer.paged_spec``) is "every layer attention + dense + pages";
``lfm2`` mixes both operator kinds and both FFN kinds, ``jamba`` Mamba
and attention layers. A family without ``paged_spec`` raises by name.
The spec also says HOW a family generates: a token a slot a step, or
(``sdar``) a BLOCK of positions by diffusion, denoised over several
forwards and stored by one more (:func:`paged_block_forward`, the block
arm of :func:`paged_decode_chunk`, the block-causal mask of
:func:`sequence_pass`).

**Two kinds of state.** A layer whose cache kind is ``pages`` owns a
layer of the pools above. A layer whose cache kind is ``state`` (a
gated short conv: the last ``taps`` values of its gated input; a
state-space scan: a matrix a channel and the conv's window) owns a
FIXED-size state a slot, described by the spec as a tree of leaves
(``PagedSpec.state``: a shape and a type each) and held ``[L_state,
n_slots, *leaf]`` a leaf, carried WHOLE by the decode step beside the
pools (``state['held']``); an idle slot's state is its own, as its
parking page is. A radix hit or a resume continues a sequence from the
END OF A WHOLE PAGE, so the state at such points is kept as SNAPSHOTS:
:class:`SnapshotStore`, rows ``[L_state, n_snapshots + 1, *leaf]`` with
a free list of their own (the last row is a sink for what a scatter
program writes and nobody keeps). Which whole prompt pages get a row is
the spec's rule (``snapshot_every``: every page where the state is
small beside a page, every 4th where one snapshot outweighs 71 pages);
``scatter_prompt`` writes pages and snapshots in one program, a radix
match is cut back to the deepest matched page that holds a snapshot
(:meth:`PagedKV.restore_tail` starts the suffix prefill's state from
it), a freed page frees its row, and when no row is free the least
recently used one goes and its page stops being a resume point. Only
whole PROMPT pages enter the trie, so the decode step writes no
snapshot, and a copy-on-write copy (a page no trie holds) needs none.
"""

from __future__ import annotations

import dataclasses
import math
import os
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpi_acx_tpu import reqlog


def default_page_tokens(max_len: int) -> int:
    """Default page size: ``$ACX_KV_PAGE_TOKENS`` (128 unset — the
    flash-decode block granularity), stepped down to the largest
    divisor of ``max_len`` so the table tiles the cache exactly."""
    want = int(os.environ.get("ACX_KV_PAGE_TOKENS", "128") or "128")
    want = max(1, min(want, max_len))
    while max_len % want:
        want -= 1
    return want


def pages_needed(tokens: int, page_tokens: int) -> int:
    return -(-int(tokens) // page_tokens)            # ceil div


# --------------------------------------------------------------------------
# Allocator


class PageAllocator:
    """Host-side page bookkeeping: a deterministic (lowest-id-first)
    free list plus per-page refcounts. All-or-nothing allocation; a
    page is reclaimed exactly when its refcount reaches zero."""

    def __init__(self, n_pages: int):
        assert n_pages >= 1, n_pages
        self.n_pages = int(n_pages)
        # pop() takes from the end; storing descending ids hands out
        # page 0 first — deterministic layouts for reproducible tests.
        self._free = list(range(self.n_pages - 1, -1, -1))
        self._ref = [0] * self.n_pages
        # How many pages have been handed out so far, and each page's
        # number among them when it was last: a page nobody has been
        # handed since some moment still holds what it held then.
        self.issues = 0
        self._issued = [0] * self.n_pages
        # Called with a page's id when it is reclaimed (PagedKV: the
        # page's snapshot row goes with it).
        self.on_free: Optional[Callable[[int], None]] = None

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.n_pages - len(self._free)

    def shared_count(self) -> int:
        """Pages referenced by more than one owner (slot or trie)."""
        return sum(1 for r in self._ref if r > 1)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages at refcount 1, or None (nothing allocated)
        when fewer than n are free."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
            self.issues += 1
            self._issued[p] = self.issues
        return pages

    def untouched_since(self, pages, issues: int) -> bool:
        """None of ``pages`` was handed out after ``issues`` pages had
        been."""
        return all(self._issued[p] <= issues for p in pages)

    def incref(self, page: int) -> None:
        assert self._ref[page] > 0, (page, "incref of a free page")
        self._ref[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; True iff the page was reclaimed."""
        assert self._ref[page] > 0, (page, "decref of a free page")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)
            # Keep the free list sorted descending so reclaimed pages
            # re-issue lowest-first too (determinism under churn).
            self._free.sort(reverse=True)
            if self.on_free is not None:
                self.on_free(page)
            return True
        return False


# --------------------------------------------------------------------------
# Radix prefix cache


class _TrieNode:
    __slots__ = ("children", "page", "stamp")

    def __init__(self, page: int = -1):
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.page = page
        self.stamp = 0


class RadixPrefixCache:
    """Trie over FULL-PAGE token chunks. ``match`` walks the prompt's
    complete pages and increfs every page on the matched path (the
    caller owns those references until it releases the slot);
    ``insert`` adopts a served request's prompt pages into the trie
    (incref — the trie is an owner like any slot). Eviction removes
    least-recently-matched LEAVES only, so an interior page can never
    outlive a cached extension of it.

    Invariant (why shared pages are never written): ``match`` caps the
    hit depth at ``(S - 1) // page_tokens`` — the suffix keeps >= 1
    token and starts exactly at a page boundary, so every position a
    prefill or decode write touches lands in a freshly allocated page.

    With ``snaps`` (a :class:`SnapshotStore`: the family keeps a state
    beside its pages) a sequence can only be continued from a page that
    holds a snapshot: ``match`` is cut back to the deepest such page,
    ``insert`` adopts no page past the prompt's last snapshot (nothing
    could resume from it) and hands a node whose page has lost its
    snapshot the new page that has one, and of the leaves those that
    are no resume point are evicted first.
    """

    def __init__(self, alloc: PageAllocator, page_tokens: int, snaps=None):
        self.alloc = alloc
        self.page_tokens = page_tokens
        self.snaps = snaps
        self.root = _TrieNode()
        self._clock = 0
        self.hits = 0            # matches with depth >= 1 page
        self.evictions = 0       # pages evicted (LRU leaves)
        self.pages_reused = 0    # cumulative pages handed out by match

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, prompt: np.ndarray) -> List[int]:
        """Longest cached full-page prefix of ``prompt``; increfs and
        returns its pages (possibly empty). Depth capped so at least
        one suffix token remains (see class docstring)."""
        max_depth = (len(prompt) - 1) // self.page_tokens
        node, pages = self.root, []
        stamp = self._tick()
        for d in range(max_depth):
            chunk = tuple(
                int(t) for t in
                prompt[d * self.page_tokens:(d + 1) * self.page_tokens])
            nxt = node.children.get(chunk)
            if nxt is None:
                break
            nxt.stamp = stamp
            pages.append(nxt.page)
            node = nxt
        if self.snaps is not None:
            while pages and not self.snaps.has(pages[-1]):
                pages.pop()
            if pages:
                self.snaps.touch(pages[-1])
        for p in pages:
            self.alloc.incref(p)
        if pages:
            self.hits += 1
            self.pages_reused += len(pages)
        return pages

    def insert(self, prompt: np.ndarray, pages: List[int]) -> int:
        """Adopt the prompt's full pages (``pages[d]`` backs chunk d)
        into the trie; returns how many pages were newly adopted."""
        node, adopted = self.root, 0
        stamp = self._tick()
        n_full = min(len(prompt) // self.page_tokens, len(pages))
        has = self.snaps.has if self.snaps is not None else None
        while has and n_full and not has(pages[n_full - 1]):
            n_full -= 1
        for d in range(n_full):
            chunk = tuple(
                int(t) for t in
                prompt[d * self.page_tokens:(d + 1) * self.page_tokens])
            nxt = node.children.get(chunk)
            if nxt is None:
                nxt = _TrieNode(pages[d])
                node.children[chunk] = nxt
                self.alloc.incref(pages[d])
                adopted += 1
            elif (has and nxt.page != pages[d] and has(pages[d])
                  and not has(nxt.page)):
                self.alloc.incref(pages[d])
                self.alloc.decref(nxt.page)
                nxt.page = pages[d]
            nxt.stamp = stamp
            node = nxt
        return adopted

    def evict_one(self) -> bool:
        """Drop the least-recently-matched leaf (decref its page), of
        those that are no resume point first. Returns False when the
        trie is empty."""
        best = None  # (rank, parent, key, node)
        has = self.snaps.has if self.snaps is not None else None
        stack = [(self.root, None, None)]
        while stack:
            node, parent, key = stack.pop()
            if parent is not None and not node.children:
                rank = (node.stamp if has is None
                        else (has(node.page), node.stamp))
                if best is None or rank < best[0]:
                    best = (rank, parent, key, node)
            for k, ch in node.children.items():
                stack.append((ch, node, k))
        if best is None:
            return False
        _, parent, key, node = best
        del parent.children[key]
        self.alloc.decref(node.page)
        self.evictions += 1
        return True


# --------------------------------------------------------------------------
# Snapshots of the state layers


class SnapshotStore:
    """The state at the end of some whole prompt pages, to continue a
    sequence from there: device ``rows`` ``[L_state, n_rows + 1,
    *leaf]`` a leaf of ``spec.state`` and the host's book of which page
    holds which row. Its size is its own (``n_rows``), not the page
    count's: one Mamba snapshot outweighs 71 pages. Row ``n_rows`` is
    the SINK: what a scatter program writes for a page that keeps no
    snapshot lands there. ``take`` hands a page a row, the least
    recently used (taken or matched) one's when none is free; that
    row's page is then no resume point any more."""

    def __init__(self, spec: "PagedSpec", n_rows: int):
        self.n_rows = int(n_rows)
        self.rows = jax.tree.map(
            lambda l: jnp.zeros((spec.n_state_layers, self.n_rows + 1)
                                + l.shape, l.dtype), spec.state)
        self._free = list(range(self.n_rows - 1, -1, -1))
        self.row_of: Dict[int, int] = {}        # page -> row
        self._stamp: Dict[int, int] = {}        # page -> last use
        self._clock = 0
        self.taken = self.evictions = self.rows_hwm = 0

    @property
    def sink(self) -> int:
        return self.n_rows

    def has(self, page: int) -> bool:
        return page in self.row_of

    def touch(self, page: int) -> None:
        self._clock += 1
        self._stamp[page] = self._clock

    def take(self, page: int) -> int:
        """A row for ``page`` (the sink when the store has none)."""
        if page in self.row_of:
            row = self.row_of[page]
        elif self._free:
            row = self._free.pop()
        elif self.row_of:
            old = min(self.row_of, key=self._stamp.__getitem__)
            row = self.row_of.pop(old)
            del self._stamp[old]
            self.evictions += 1
        else:
            return self.sink
        self.row_of[page] = row
        self.touch(page)
        self.taken += 1
        self.rows_hwm = max(self.rows_hwm, len(self.row_of))
        return row

    def drop(self, page: int) -> None:
        """``page`` was reclaimed: its row is free again."""
        if page in self.row_of:
            self._free.append(self.row_of.pop(page))
            self._free.sort(reverse=True)
            del self._stamp[page]


# --------------------------------------------------------------------------
# Device pool


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is to the paged plane: an operator on the
    sequence, then a feed-forward part. A family whose layers are ONE of
    the two (``nemotron_h``: a mixer OR a feed-forward part, each with
    its own norm and residual) says ``"none"`` for the other: a layer
    with no FFN ends behind its operator, a layer with no operator
    (``cache`` ``"none"`` too: it keeps nothing of the past) is its FFN
    alone."""
    # "attention" | "latent_attention" (pages) | "conv" | "mamba" |
    # "mamba2" (state) | "none"
    operator: str = "attention"
    ffn: str = "dense"               # "dense" | "moe" | "none"
    cache: str = "pages"             # "pages" | "state" | "none"


@dataclasses.dataclass(frozen=True)
class Segment:
    """``repeats`` whole periods of layers that ride one ``lax.scan``:
    ``params[key]`` holds the period's leaves stacked on a leading
    ``[repeats]`` axis, as one layer's dict when the period is one
    layer and as a tuple of dicts, one a layer of the period, else."""
    key: str
    period: Tuple[LayerKind, ...]
    repeats: int

    def count(self, cache: str) -> int:
        return self.repeats * sum(k.cache == cache for k in self.period)


def compress_layers(kinds, prefix: str = "seg") -> Tuple[Segment, ...]:
    """Layers of unequal kind cannot ride one scan: group ``kinds``
    (one :class:`LayerKind` a layer) greedily into runs of whole
    periods, at each point the period whose repeats cover most layers
    (the shortest of equals), so that the programs grow with the
    number of DIFFERENT stretches and not with the depth. Published
    LFM2-24B-A2B, 40 layers: (conv dense) x 2, (attn, conv, conv, conv
    moe) x 9, (attn moe), (conv moe)."""
    kinds, out, at = tuple(kinds), [], 0
    while at < len(kinds):
        best = (1, 1)
        for p in range(1, (len(kinds) - at) // 2 + 1):
            r = 1
            while kinds[at + r * p:at + (r + 1) * p] == kinds[at:at + p]:
                r += 1
            if r > 1 and r * p > best[0] * best[1]:
                best = (p, r)
        p, r = best
        out.append(Segment(f"{prefix}{len(out)}", kinds[at:at + p], r))
        at += p * r
    return tuple(out)


def cast_params(params, dtype, f32: Tuple[str, ...] = ()):
    """A family's tree (stacked by segment, leaves by name) in ``dtype``
    for inference; the leaves named in ``f32`` and every norm's weight
    (``"norm"`` in its name) stay float32: they are computed in it."""
    def cast(path, p):
        name = path[-1].key
        return p if name in f32 or "norm" in name else p.astype(dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


@dataclasses.dataclass(frozen=True)
class PagedSpec:
    """What the paged plane asks of a family (module docstring). The
    functions, with ``lp`` one layer's leaves and ``x`` ``[B, 1, d]``
    in a decode step:

    * ``embed(params, cfg, token [B], pos [B]) -> x``
    * ``qkv(cfg, lp, x, pos) -> q [B, 1, Hq, Dh], k, v [B, 1, Hkv,
      Dh]``, as they go into the cache (positions applied)
    * ``attn_out(cfg, lp, x, o) -> x``: residual + output projection
    * ``state_op(cfg, lp, x, held, at, live=None) -> (x, held)``: the
      operator of a ``state`` layer, residual included. ``held`` is the
      slots' state of ALL the state layers, ``[L_state, B, *leaf]`` a
      leaf of ``state``, and ``at`` this layer's index in it: the
      operator reads and writes ``(at, slot)`` and hands the stack back
      whole (a small state may slice its layer out and put it back; one
      of a GB goes to a Pallas call whole, aliased to its result).
      ``live`` ([B] bool, or None) as an expert layer's: the state of a
      slot that is not is read by nobody afterwards and need not move;
      an operator that makes use of it says so (``state_live``)
    * ``ffn(cfg, lp, x, kind) -> x``, and ``(x, idx [B, k])``, the
      experts each row chose, when ``kind`` is ``"moe"``; such a layer
      also takes ``live=`` ([B] bool, or None: the rows whose result
      anybody receives; the others' experts need not be computed)
    * ``head(params, cfg, x) -> logits [B, vocab]`` f32

    and the same operators over a WHOLE sequence, ``x`` ``[B, S, d]`` at
    ``positions`` [S], which :func:`sequence_pass` (``prefill``,
    ``forward``) calls:

    * ``seq_head(params, cfg, x) -> logits [B, S, vocab]`` f32 (the
      pass embeds by itself: the tokens' rows of ``params["embed"]`` in
      ``cfg.dtype``, what every family with this pass does)
    * ``seq_qkv(cfg, lp, x, positions) -> q [B, S, Hq, Dh], k, v [B, S,
      Hkv, Dh]``; the pass attends (causally on itself through the
      shared flash / dense policy, ``cfg.use_flash``; behind a gathered
      history densely on ``concat(history, own)``), then ``attn_out``.
      Of a LATENT pool instead the layer whole, of ONE sequence:
      ``seq_attention(cfg, lp, x [1, S, d], positions, history) -> (x,
      rows [S, D])``, ``history`` the cached rows ``[D, P]`` (a pool's
      layout) or None
    * ``seq_state(cfg, lp, x, start, last_index, snapshot) -> (x, tails,
      end)``: a ``state`` layer over the sequence, residual included.
      ``start``: the layer's state before ``x``, ``[1, *leaf]`` a leaf
      (a suffix prefill has ONE sequence), or None at a sequence's
      start. With ``snapshot`` (tokens; None: ``forward``, and nothing
      is cut) ``tails`` ``[S // snapshot, *leaf]`` a leaf, the state
      after every ``snapshot``-th token, and ``end`` ``[*leaf]`` after
      ``last_index``, both of sequence 0: positions past ``last_index``
      are padding and leave no mark.

    ``prefill`` / ``suffix_prefill``: a family's OWN whole-sequence
    programs in place of :func:`prefill` 's (arguments and results as
    there, without the spec). GPT-2 alone sets them (``transformer.
    prefill``, which the fixed-slot planes share, and
    :func:`prefill_with_history`): ROADMAP.md, Queue 3."""
    segments: Tuple[Segment, ...]
    n_kv_heads: int
    head_dim: int
    n_rep: int = 1                       # query heads a K/V head
    # A LATENT pool (None: K and V pools of ``head_dim``): a page layer
    # holds ONE row of ``head_dim`` values a token (``n_kv_heads`` 1),
    # read by all ``n_rep`` query heads, whose first ``v_dim`` values
    # ARE the value: there is no V pool, ``qkv`` returns ``(q, row)``,
    # a prefill's ``one`` holds ``'k'`` alone, a suffix prefill is
    # handed ``hv`` None, and the attend's result is ``v_dim`` wide a
    # head. ``attn_scale``: the decode attend's factor on the scores
    # where it is not ``1 / sqrt(head_dim)``.
    v_dim: Optional[int] = None
    attn_scale: Optional[float] = None
    # One slot's state in ONE state layer: a tree of
    # ``jax.ShapeDtypeStruct`` (a gated short conv: one leaf; a
    # state-space scan: the conv window and the scan's matrix, each in
    # its own type). None without state layers.
    state: Any = None
    # Which whole prompt pages get a snapshot row: every n-th.
    snapshot_every: int = 1
    # ``state_op`` reads its ``live``: a dead slot-step moves no state
    # (the step program then counts them, ``state['state_dead']``).
    state_live: bool = False
    n_experts: int = 0                   # of a "moe" FFN's router
    # The experts held HERE where that is a share of the router's
    # (``(first, count)``; None: all): the routing counters then also
    # say what of the routed pairs the share computes (_moe_tally).
    experts_held: Optional[Tuple[int, int]] = None
    # Leaves of a "moe" FFN that ``ffn`` is handed WHOLE, still stacked
    # over the segment's repeats, with the repeat under ``lp["repeat"]``:
    # a Pallas call cannot take one repeat's slice without a copy of it
    # (the pools' lesson; at 403 MB an expert matrix stack, two thirds
    # of a decode step: PERF.md, PR 31).
    moe_whole: Tuple[str, ...] = ()
    # Width of the row a routed (token, expert) pair carries where that
    # is not the model's: the experts work in a LATENT, and what an
    # exchange between chips would carry is the latent row (0: the
    # model's width).
    moe_row_dim: int = 0
    kv_int8: bool = True                 # int8 pages wired for it
    # How the family GENERATES. ``block`` 0 (or 1): a token a slot a
    # step, the argmax of the step's logits. ``block`` W > 1: generation
    # by diffusion over blocks (:func:`paged_block_forward`, the block
    # arm of :func:`paged_decode_chunk`): a slot's step is a block of W
    # positions, denoised together over ``denoise_steps`` forwards that
    # commit ``W / denoise_steps`` positions each, the still masked ones
    # fed ``mask_token``, and stored by one more forward; the mask of
    # the whole-sequence pass is then block-causal, a prefill needs no
    # head and hands out no token. The family's ``embed`` / ``qkv`` take
    # ``token`` [B, W] and ``x`` [B, W, d] at the block's first position
    # ``pos``, its ``head`` gives [B, W, vocab].
    block: int = 0
    denoise_steps: int = 0
    mask_token: int = 0
    # (ffn kind, implementation) pairs: ServingMetrics.paged_ffn
    ffn_built: Tuple[Tuple[str, str], ...] = (("dense", "dense"),)
    embed: Callable = None
    qkv: Callable = None
    attn_out: Callable = None
    state_op: Callable = None
    ffn: Callable = None
    head: Callable = None
    seq_qkv: Callable = None
    seq_attention: Callable = None
    seq_state: Callable = None
    seq_head: Callable = None
    prefill: Callable = None
    suffix_prefill: Callable = None

    @property
    def n_page_layers(self) -> int:
        return sum(s.count("pages") for s in self.segments)

    @property
    def n_state_layers(self) -> int:
        return sum(s.count("state") for s in self.segments)

    @property
    def state_bytes_slot(self) -> int:
        """Bytes of one slot's state over all the state layers."""
        return self.n_state_layers * sum(
            math.prod(l.shape) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree.leaves(self.state))

    def built(self, what: str) -> str:
        """``ServingMetrics.paged_operator`` / ``paged_ffn``: the kinds
        a step program of this family is built from."""
        if what == "ffn":
            return "+".join(f"{k}:{impl}" for k, impl in self.ffn_built)
        return "+".join(sorted({k.operator for s in self.segments
                                for k in s.period} - {"none"}))


def paged_spec(family, cfg) -> PagedSpec:
    """The family's :class:`PagedSpec` for ``cfg`` (``None``: GPT-2)."""
    if family is None:
        from mpi_acx_tpu.models import transformer as family  # noqa: N813
    if not hasattr(family, "paged_spec"):
        name = getattr(family, "__name__", repr(family)).rsplit(".", 1)[-1]
        raise NotImplementedError(
            f"paged KV serving asks a family for paged_spec(cfg) "
            f"(kvpage.PagedSpec); family {name!r} gives none")
    return family.paged_spec(cfg)


def init_page_pool(cfg, n_pages: int, page_tokens: int, n_slots: int,
                   kv_int8: bool = False, spec: Optional[PagedSpec] = None):
    """Zeroed page pool: ``{'k','v': [L, P, H, Dh, page_tokens]}``
    (+ ``'ks','vs'`` [L, P, H, 1, page_tokens] f32 scale pages when
    int8) over the ``L`` layers whose cache kind is ``pages``, with
    ``P = n_pages + n_slots`` — the trailing ``n_slots`` pages are the
    per-slot parking pages (module docstring), outside the allocator.
    A pool IS a cache whose batch axis counts pages."""
    from mpi_acx_tpu.models.decoding import new_kv_cache
    spec = spec or paged_spec(None, cfg)
    if spec.v_dim is not None:          # latent: the value lies in the row
        return {"k": jnp.zeros((spec.n_page_layers, n_pages + n_slots,
                                spec.n_kv_heads, spec.head_dim, page_tokens),
                               cfg.dtype)}
    pool = new_kv_cache(spec.n_page_layers, n_pages + n_slots,
                        spec.n_kv_heads, spec.head_dim, page_tokens,
                        cfg.dtype, kv_int8)
    del pool["pos"]
    return pool


_POOL_KEYS = ("k", "v", "ks", "vs")        # what the decode step carries


# --------------------------------------------------------------------------
# Paged decode step (any family, through its PagedSpec)


def _moe_tally(idx, owns, n_experts: int, held=None, kept=None, live=None):
    """[5] int32 of one MoE layer's routing in one step, over the slots
    that OWN a request and are ``live`` ([B] bool, the mask the expert
    layer was handed; None: every slot): (token, expert) pairs routed,
    distinct experts hit, the fullest expert's pairs, 1 (the layer-steps
    counted) and, last, the pairs the mask left out (``top_k`` a slot
    that is not live, whether it owns a request or idles). Where the
    loop says what each slot owes (``state['left']``) a live slot owns
    its request, and the first two are what the expert kernel computes
    and reads; without it an idle slot routes too and its experts are
    fetched: they are then a floor.

    Where the experts held here are a share of the router's (``held`` =
    ``(first, count)``) the experts hit and the fullest are counted over
    the HELD ones, whose weights are what this chip reads, and two more
    stand before the last, [7]: the pairs whose expert is held, and the
    counted slots' tokens whose kept routing groups (``kept`` [B,
    groups] bool, of a group-limited router) include the held experts'
    own."""
    dead = jnp.int32(0)
    if live is not None:
        owns = owns & live
        dead = idx.shape[1] * (~live).sum().astype(jnp.int32)
    hits = jnp.zeros((n_experts,), jnp.int32).at[idx].add(
        owns.astype(jnp.int32)[:, None])
    if held is None:
        return jnp.stack([hits.sum(), (hits > 0).sum().astype(jnp.int32),
                          hits.max(), jnp.int32(1), dead])
    first, count = held
    mine = hits[first:first + count]
    group = first * kept.shape[1] // n_experts
    return jnp.stack([hits.sum(), (mine > 0).sum().astype(jnp.int32),
                      mine.max(), jnp.int32(1), mine.sum(),
                      (kept[:, group] & owns).sum().astype(jnp.int32), dead])


def _split_leaves(spec: PagedSpec, seg: Segment, stacked):
    """``params[seg.key]`` as one ``(scanned, whole)`` pair of dicts a
    layer of the period: ``whole`` the leaves an expert layer is handed
    still stacked over the segment's repeats (``spec.moe_whole``),
    ``scanned`` the rest, of which a scan's body sees one repeat."""
    subs = stacked if len(seg.period) > 1 else (stacked,)
    names = [spec.moe_whole if kind.ffn == "moe" else ()
             for kind in seg.period]
    return [({n: a for n, a in sub.items() if n not in whole},
             {n: sub[n] for n in whole}) for sub, whole in zip(subs, names)]


def _one_layer(scanned, whole, repeat):
    """One layer's ``lp`` at ``repeat`` of its segment: its own slice of
    the ``scanned`` leaves and, where it has ``whole`` ones, those and
    ``lp["repeat"]`` to find its own in them."""
    return dict(scanned, **whole, repeat=repeat) if whole else scanned


def _through_layers(spec: PagedSpec, params, carry, layer):
    """``carry`` = ``(x, pools, rest)`` through every layer of a step:
    each :class:`Segment` one ``lax.scan`` over its whole periods, the
    period's layers unrolled inside. ``layer(kind, lp, x, pools, rest,
    at)`` is one layer, ``at`` its index among those of its cache
    kind."""
    def nth(base, i, stride, j):
        """``base + i * stride + j`` without the identities."""
        i = i if stride == 1 else i * stride
        return i if base + j == 0 else i + (base + j)

    pages_at = states_at = 0
    for seg in spec.segments:
        stacked = params[seg.key]
        split = _split_leaves(spec, seg, stacked)
        n_pg = sum(k.cache == "pages" for k in seg.period)
        n_st = sum(k.cache == "state" for k in seg.period)

        def body(carry, i, seg=seg, split=split, n_pg=n_pg, n_st=n_st,
                 pages_at=pages_at, states_at=states_at):
            x, pools, rest = carry
            pg = st = 0
            for kind, (scanned, whole) in zip(seg.period, split):
                lp = _one_layer(jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, i, 0,
                                                       keepdims=False),
                    scanned), whole, i)
                x, pools, rest = layer(
                    kind, lp, x, pools, rest,
                    nth(pages_at, i, n_pg, pg) if kind.cache == "pages"
                    else nth(states_at, i, n_st, st))
                pg += kind.cache == "pages"
                st += kind.cache == "state"
            return (x, pools, rest), None

        # (the depth is the stacked leaves', as it always was: a tree
        # cut or grown in depth runs at its own)
        repeats = jax.tree.leaves(stacked)[0].shape[0]
        carry, _ = lax.scan(body, carry, jnp.arange(repeats))
        pages_at += repeats * n_pg
        states_at += repeats * n_st
    return carry


def paged_decode_step(params, cfg, state, token, page_tokens: int,
                      family=None):
    """One autoregressive step against the paged state, for any family
    through its :class:`PagedSpec` (``family`` None: GPT-2, whose step
    mirrors ``transformer.decode_step`` exactly — same _qkv/attend/ffn
    math, so active slots are bit-equal to the fixed-slot step).
    ``state`` = pool keys + ``'table'`` [B, max_pages] + ``'pos'`` [B]
    and, for a family with state layers, ``'held'`` ([L_state, B,
    *leaf] a leaf); with ``'moe'`` [5 or 7] and ``'owns'`` [B] it also
    counts its routing (:func:`_moe_tally`). With ``'left'`` ([B] int32:
    the tokens each slot's request still owes at the chunk's start, 0
    for a slot that owns none; what only the loop knows,
    ``RequestBook.left``) the attend and the expert layers (FFN kind
    ``"moe"``) are told which slots can deliver no token at this step
    (``step >= left[b]``; a step by itself is step 0): the attend
    fetches and folds nothing for them and their rows are zeros, the
    expert layer routes none of their pairs to an expert, so an expert
    that only they chose is not read, and adds zeros to their residual,
    and a state operator that reads ``live`` (``PagedSpec.state_live``:
    Mamba-2's ``ssd_update``) neither reads nor writes their state and
    adds what a read-out of zeros gives; with ``'state_dead'`` (a
    scalar) the step counts them there, once whatever the state layers.
    Absent, every slot is live. Nothing else reads it: a dead slot's
    token still runs the dense weights, the router and the projections
    and short conv of a state layer (finite, dropped by the loop), its
    K/V is staged and flushed, and in a family whose state operator
    ignores ``live`` (Jamba's ``ssm_update``, LFM2's conv) its state
    moves.

    An attention layer's fresh K/V for slot b lands at ``pool[i,
    table[b, pos_b // pt], :, :, pos_b % pt]``, ``i`` counting the
    layers with pages. Idle slots write their parking page (their table
    rows point nowhere else) and the page index is clipped so a
    long-idle slot's walking pos can never index past its table row. A
    state layer reads and writes the slot's own rows of ``held``, which
    its operator is handed whole (``PagedSpec.state_op``).

    Each :class:`Segment` is one ``lax.scan`` over its whole periods,
    the period's layers unrolled inside. The pools ride the scans
    WHOLE: the write and the attend are handed ``[L, P, H, *, pt]`` and
    the layer index, and on the chip both are Pallas calls whose index
    maps address ``(layer, page)`` from prefetched scalars
    (ops/flash_decode.py: ``paged_kv_write`` updates the pool in place,
    a page's read-modify-write a slot; ``paged_flash_decode_attend``
    reads the live pages, GQA-native at ``spec.n_rep`` query heads a
    K/V head). Nothing here may slice a layer out of a pool: that is a
    copy of the layer every layer of every step. Off the chip, or at a
    page Mosaic cannot tile, ``select_paged_kv_write`` /
    ``select_paged_decode_attend`` hand back the dense pair (``.at[]
    .set`` on a sliced layer, gather + dense attend): the same values,
    the bit-equality anchor.

    With ``state['stage']`` (a decode chunk's ``(arrays, step)``:
    :func:`paged_decode_chunk`) the step writes NO page: the fresh K/V
    go to row ``step`` of the stage (``flash_decode.stage_put``), the
    attend reads the pool
    up to the slot's position at the chunk's start and the stage's
    tokens behind it, and the chunk flushes the stage into the pages
    once. A step by itself has no stage and writes its page."""
    from mpi_acx_tpu.ops.flash_decode import (chunk_write_pages,
                                              select_paged_decode_attend,
                                              select_paged_kv_write,
                                              stage_put)
    from mpi_acx_tpu.ops.kvquant import kv_quant

    spec = paged_spec(family, cfg)
    table, pos = state["table"], state["pos"]
    keys = tuple(k for k in _POOL_KEYS if k in state)   # k, v[, ks, vs]
    quant = "ks" in keys
    x = spec.embed(params, cfg, token, pos)

    # Slot b's token column: distinct pages per slot (each slot owns its
    # pages; idle slots own their parking page), so writes never collide.
    write_page = chunk_write_pages(table, pos, 1, page_tokens)      # [B, 1]
    off = pos % page_tokens

    write = select_paged_kv_write(cfg.decode_flash, page_tokens)
    attend = select_paged_decode_attend(cfg.decode_flash, page_tokens)
    rest = {k: state[k] for k in ("held", "moe") if k in state}
    step = None
    if "stage" in state:
        rest["stage"], step = state["stage"]
    # [B] bool: the slots that can still deliver a token at this step
    live = ((0 if step is None else step) < state["left"]
            if "left" in state else None)
    state_dead = state.get("state_dead")
    if state_dead is not None and live is not None:
        # the slot-steps whose state the state operators leave alone
        state_dead = state_dead + (~live).sum().astype(jnp.int32)

    def layer(kind, lp, x, pools, rest, at):
        """``at``: the layer's index among those of its cache kind."""
        if kind.cache == "pages":
            q, *fresh = spec.qkv(cfg, lp, x, pos)   # k, v; a latent row
            if quant:
                (k, ks), (v, vs) = kv_quant(fresh[0]), kv_quant(fresh[1])
                fresh = (k, v, ks, vs)
            if step is None:
                pools = write(pools, fresh, at, write_page, off)
                stage = None
            else:
                rest = dict(rest, stage=stage_put(rest["stage"], fresh, at,
                                                  step, spec.v_dim))
                stage = rest["stage"], step
            kp, vp = pools[0], pools[1] if spec.v_dim is None else None
            if quant:
                kp, vp = (kp, pools[2]), (vp, pools[3])
            o = attend(q, kp, vp, table, pos, page_tokens, spec.n_rep,
                       layer=at, stage=stage, left=state.get("left"),
                       v_dim=spec.v_dim, scale=spec.attn_scale)
            x = spec.attn_out(cfg, lp, x, o)
        elif kind.cache == "state":
            x, held = spec.state_op(cfg, lp, x, rest["held"], at,
                                    live=live)
            rest = dict(rest, held=held)
        if kind.ffn == "moe":
            # (idx, and the routing groups a group-limited router kept)
            x, *routed = spec.ffn(cfg, lp, x, kind.ffn, live=live)
            if "moe" in rest:
                rest = dict(rest, moe=rest["moe"] + _moe_tally(
                    routed[0], state["owns"], spec.n_experts,
                    spec.experts_held, *routed[1:], live=live))
        elif kind.ffn != "none":
            x = spec.ffn(cfg, lp, x, kind.ffn)
        return x, pools, rest

    x, pools, rest = _through_layers(
        spec, params, (x, tuple(state[k] for k in keys), rest), layer)
    if step is not None:
        rest["stage"] = rest["stage"], step + 1
    out = dict(zip(keys, pools), **rest)
    out["table"] = table
    out["pos"] = pos + 1
    for k in ("owns", "left"):
        if k in state:
            out[k] = state[k]
    if state_dead is not None:
        out["state_dead"] = state_dead
    return spec.head(params, cfg, x), out


def paged_block_forward(params, cfg, state, tokens, page_tokens: int,
                        family):
    """One forward of every slot's BLOCK (``PagedSpec.block`` = W > 1
    positions: generation by diffusion over blocks) against the paged
    state: the block sibling of :func:`paged_decode_step`, inside a
    chunk only. ``tokens`` [B, W]: what the block holds now (the mask
    token where a position is not committed yet); ``state['pos']`` [B]
    the slots' positions at the CHUNK's start (it does not move inside
    a chunk) and ``state['stage']`` = ``(arrays, row)``, ``row`` the
    block's first row of the chunk's stage (block ``j``: ``j W``): the
    block sits at positions ``pos + row ..``. Every row of the block
    sees the pages up to ``pos``, the stage's rows of the chunk's
    earlier blocks and ALL W rows of its own block, which this forward
    writes (rows ``row .. row + W - 1`` of every page layer's stage,
    over what the forward before left there): no causal mask inside a
    block, so a K/V head's ``W x n_rep`` query rows meet ONE key set and
    ride the attend as ``W n_rep`` heads of one position (``pos + row +
    W - 1``, the stage filled up to that row): the call the token step
    makes, the rows folded (no kernel of its own). NO forward writes a
    page: the last forward of a block (its tokens all committed) leaves
    in the stage what the chunk's flush then stores.

    ``state['left']`` ([B]: the POSITIONS each slot still owes at the
    chunk's start, the tokens of its request and what of its prompt
    lies in its first block) says which blocks are dead (``row >=
    left``): as in the token step the attend fetches nothing for them
    and the expert layers route none of their pairs. Returns (``x`` [B,
    W, d] behind the last layer, state): the head is the caller's, the
    forward that stores a finished block needs none."""
    from mpi_acx_tpu.ops.flash_decode import select_paged_decode_attend
    from mpi_acx_tpu.ops.kvquant import kv_quant

    spec = paged_spec(family, cfg)
    W, n_rep = spec.block, spec.n_rep
    assert W > 1 and spec.v_dim is None and not spec.n_state_layers, spec
    table, pos = state["table"], state["pos"]
    keys = tuple(k for k in _POOL_KEYS if k in state)
    quant = "ks" in keys
    stage, row = state["stage"]
    B = tokens.shape[0]
    first = pos + row                       # [B]: the block's first position
    x = spec.embed(params, cfg, tokens, first)
    attend = select_paged_decode_attend(cfg.decode_flash, page_tokens)
    rest = {"stage": stage}
    if "moe" in state:
        rest["moe"] = state["moe"]
    left = state.get("left")
    # [B * W] bool: the rows of the blocks that can still deliver
    live = None if left is None else jnp.repeat(row < left, W)
    # (the attend reads ``step < left`` with the stage's LAST filled row
    # for ``step``: ``row + W - 1 < left + W - 1`` iff ``row < left``)
    last = row + (W - 1)
    left_attend = None if left is None else left + (W - 1)

    def put(into, rows, at, lanes: bool):
        """``rows`` [B, W, H, *] as rows ``row ..`` of layer ``at`` of a
        stage array: tokens a major axis of K/V's [L, B, chunk, H, 2 D],
        the lanes of a scale's [L, B, H, 1, chunk]."""
        zero = jnp.int32(0)
        if lanes:
            return lax.dynamic_update_slice(
                into, rows.transpose(0, 2, 3, 1)[None].astype(into.dtype),
                (at, zero, zero, zero, row))
        return lax.dynamic_update_slice(
            into, rows[None].astype(into.dtype), (at, zero, row, zero, zero))

    def layer(kind, lp, x, pools, rest, at):
        if kind.cache == "pages":
            q, k, v = spec.qkv(cfg, lp, x, first)       # [B, W, H*, Dh]
            scales = ()
            if quant:
                (k, ks), (v, vs) = kv_quant(k), kv_quant(v)
                scales = (ks, vs)
            st = rest["stage"]
            st = (put(st[0], jnp.concatenate([v, k], axis=-1), at, False),
                  *(put(s, f, at, True) for s, f in zip(st[1:], scales)))
            rest = dict(rest, stage=st)
            kp, vp = pools[0], pools[1]
            if quant:
                kp, vp = (kp, pools[2]), (vp, pools[3])
            # [B, W, Hkv, n_rep, D] -> one position of Hkv x (W n_rep)
            # heads, and back
            D = q.shape[-1]
            qf = q.reshape(B, W, spec.n_kv_heads, n_rep, D).transpose(
                0, 2, 1, 3, 4).reshape(B, 1, -1, D)
            o = attend(qf, kp, vp, table, pos + last, page_tokens, W * n_rep,
                       layer=at, stage=(st, last), left=left_attend,
                       scale=spec.attn_scale)
            o = o.reshape(B, spec.n_kv_heads, W, n_rep * D).transpose(
                0, 2, 1, 3).reshape(B, W, -1)
            x = spec.attn_out(cfg, lp, x, o)
        if kind.ffn == "moe":
            x, *routed = spec.ffn(cfg, lp, x, kind.ffn, live=live)
            if "moe" in rest:
                rest = dict(rest, moe=rest["moe"] + _moe_tally(
                    routed[0], jnp.repeat(state["owns"], W), spec.n_experts,
                    spec.experts_held, *routed[1:], live=live))
        elif kind.ffn != "none":
            x = spec.ffn(cfg, lp, x, kind.ffn)
        return x, pools, rest

    x, pools, rest = _through_layers(
        spec, params, (x, tuple(state[k] for k in keys), rest), layer)
    out = dict(state, **dict(zip(keys, pools)), **rest)
    out["stage"] = rest["stage"], row
    return x, out


def _block_chunk(params, cfg, spec: PagedSpec, state, tok, chunk: int,
                 page_tokens: int, family):
    """The steps of a decode chunk for a family that generates by
    diffusion over blocks (``spec.block`` = W > 1; ``chunk`` a multiple
    of W): ``chunk / W`` blocks a slot, in lockstep over the slots, each
    block ``spec.denoise_steps`` denoising forwards and one storing
    forward (:func:`paged_block_forward`), all ``denoise_steps + 1`` one
    ``lax.scan`` whose last turn skips the head. ``tok`` [B, W]: what a
    slot's FIRST block of the chunk already holds (the last ``P mod W``
    tokens of a prompt just seated, from the block's start), -1 where a
    position is masked: "masked" is a flag of the position, never a
    token's value (a head may emit the mask token's id, and a prompt may
    hold it). Every later block starts all masked.

    A denoising forward feeds the mask token at the masked positions,
    takes at each ``t = argmax logits`` and its confidence ``c =
    softmax(logits)[t]`` (float32) and commits the ``W / denoise_steps``
    masked positions of highest ``c`` (of equal ``c`` the lower
    position: the static low-confidence schedule); a block with fewer
    masks than that commits what it has and nothing in the steps it has
    no mask for. A committed position never changes. The storing
    forward runs the finished block; its K/V are what the stage keeps.

    Returns (state, ``toks`` [chunk, B]: the blocks' tokens in position
    order, ``at`` [chunk, B]: the denoising step that committed each, -1
    for what ``tok`` brought)."""
    W, n_steps = spec.block, spec.denoise_steps
    assert chunk % W == 0 and W % n_steps == 0, (chunk, W, n_steps)
    per_step = W // n_steps
    order = jnp.arange(W)

    def block(state, j):
        state = dict(state, stage=(state["stage"][0], j * W))
        held = jnp.where(j == 0, tok, -1)                   # [B, W]
        masked = held < 0
        tokens = jnp.where(masked, 0, held)
        at = jnp.where(masked, n_steps, -1).astype(jnp.int32)

        def commit(logits, tokens, masked, at, s):
            best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            top = jnp.max(logits, axis=-1, keepdims=True)
            conf = 1.0 / jnp.sum(jnp.exp(logits - top), axis=-1)
            conf = jnp.where(masked, conf, -1.0)            # [B, W]
            # rank among the block's positions: higher confidence first,
            # of equals the lower position
            ahead = ((conf[:, None, :] > conf[:, :, None])
                     | ((conf[:, None, :] == conf[:, :, None])
                        & (order[None, None, :] < order[None, :, None])))
            take = masked & (ahead.sum(-1) < per_step)
            return (jnp.where(take, best, tokens), masked & ~take,
                    jnp.where(take, s, at))

        def forward(carry, s):
            state, tokens, masked, at = carry
            x, state = paged_block_forward(
                params, cfg, state, jnp.where(masked, spec.mask_token, tokens),
                page_tokens, family)
            # (the storing forward, ``s == n_steps``, runs no head)
            tokens, masked, at = lax.cond(
                s < n_steps,
                lambda: commit(spec.head(params, cfg, x), tokens, masked, at,
                               s),
                lambda: (tokens, masked, at))
            return (state, tokens, masked, at), None

        (state, tokens, _, at), _ = lax.scan(
            forward, (state, tokens, masked, at), jnp.arange(n_steps + 1))
        return state, (tokens.T, at.T)                      # [W, B] each

    state, (toks, at) = lax.scan(block, state, jnp.arange(chunk // W))
    B = tok.shape[0]
    return state, toks.reshape(chunk, B), at.reshape(chunk, B)


# The paged serve path's programs (the chunk below, PagedKV's _scatter /
# _gather / _copy, serving.paged_prefill / paged_suffix_prefill) are
# module-level jitted functions, so that jax.jit's own cache finds the
# traced, loaded program in every later call of the process: made per
# call or per PagedKV, each was traced and loaded anew at its first use
# in every call (PERF.md, PR 28). What a trace reads from the process
# and not from its arguments, ``backend.on_tpu()`` (the ops' compile or
# interpret, the auto policies), the caller reads once a call and passes
# as the static ``on_tpu``: two calls that would trace different
# programs never share one.

_programs_traced = 0


def programs_traced() -> int:
    """How many of the paged serve path's programs this process has
    TRACED so far; the difference over a serve call is its
    ``ServingMetrics.programs_traced``."""
    return _programs_traced


def note_trace() -> None:
    """First line of each of those programs' Python bodies, which run
    only under a trace."""
    global _programs_traced
    _programs_traced += 1


@partial(jax.jit, static_argnames=("cfg", "family", "chunk", "page_tokens",
                                   "on_tpu"),
         donate_argnames="state")
def paged_decode_chunk(params, state, tok, keys, *, cfg, chunk,
                       page_tokens, on_tpu, family=None):
    """``chunk`` greedy steps of :func:`paged_decode_step` as ONE
    program. Every slot writes at the same chunk step whatever its
    ``pos``, so the chunk's fresh K/V go to a STAGE, a scratch of this
    program addressed ``(layer, slot, ..., step)``
    (``flash_decode.new_kv_stage``: what it weighs), which the attend
    reads behind the pool; after the steps the stage is flushed: a
    scan over the layers with pages, in each ONE ``paged_kv_write`` of
    ``chunk`` tokens a slot (one for each run of a page's length where
    a chunk is longer than a page: ``paged_kv_write_runs``), so that a
    page is read and written once a chunk (twice where the tokens cross
    into the next page) and not once a token. Between chunks every page is complete: what the host
    side reads of a pool (``grow``, copy-on-write, ``scatter_prompt``,
    the trie, ``gather_history``) sees what a chunk of one-token writes
    left, bit for bit.

    A family that generates by diffusion over blocks (``PagedSpec.block``
    = W > 1) takes the BLOCK arm: ``chunk`` stays the tokens a slot a
    program call, ``chunk / W`` blocks of ``denoise_steps + 1`` forwards
    each (:func:`_block_chunk`); ``tok`` is then ``[B, W]``, what each
    slot's first block already holds (-1: masked), and the result's
    tokens ``[2, chunk, B]``: under them the denoising step that
    committed each. The stage and its flush are the token arm's: no
    forward writes a page, the last forward of a block leaves its K/V in
    the stage, and a page is still written once a chunk."""
    from mpi_acx_tpu.ops.flash_decode import (new_kv_stage,
                                              paged_kv_write_runs,
                                              select_paged_kv_write,
                                              stage_tokens)
    note_trace()
    spec = paged_spec(family, cfg)
    pool_keys = tuple(k for k in _POOL_KEYS if k in state)
    table, pos0 = state["table"], state["pos"]
    state = dict(state, stage=(
        new_kv_stage([state[k] for k in pool_keys], table.shape[0], chunk,
                     spec.v_dim),
        jnp.int32(0)))

    def one(carry, _):
        state, tok, keys = carry
        logits, state = paged_decode_step(params, cfg, state, tok,
                                          page_tokens, family)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (state, nxt, keys), nxt
    if spec.block > 1:
        # ``chunk / W`` blocks a slot (:func:`_block_chunk`); the tokens
        # come back with the step that committed each under them, [2,
        # chunk, B], and the slots have moved on by the whole chunk
        state, toks, at = _block_chunk(params, cfg, spec, state, tok, chunk,
                                       page_tokens, family)
        toks = jnp.stack([toks, at])
        state = dict(state, pos=pos0 + chunk)
    else:
        (state, _, keys), toks = lax.scan(one, (state, tok, keys), None,
                                          length=chunk)

    stage, _ = state.pop("stage")
    write = select_paged_kv_write(cfg.decode_flash, page_tokens)

    def flush(pools, layer):
        return paged_kv_write_runs(
            write, pools, stage_tokens(stage, layer, spec.v_dim), layer,
            table, pos0, page_tokens), None
    pools, _ = lax.scan(flush, tuple(state[k] for k in pool_keys),
                        jnp.arange(stage[0].shape[0]))
    return dict(state, **dict(zip(pool_keys, pools))), toks, keys


def make_paged_step_fn(params, cfg, family, chunk: int,
                       page_tokens: int):
    """The chunked decode step over the paged state (the paged sibling
    of make_server_fns' step_fn — greedy only): ``(state, tok, keys) ->
    (state, toks [chunk, B], keys)``, the state donated so XLA updates
    the pool in place. It only binds ``params`` (an argument, never a
    constant) and the static key to the process's one
    :func:`paged_decode_chunk`; nothing is traced here."""
    from mpi_acx_tpu import backend
    paged_spec(family, cfg)             # a family without one raises here
    return partial(paged_decode_chunk, params, cfg=cfg, family=family,
                   chunk=chunk, page_tokens=page_tokens,
                   on_tpu=backend.on_tpu())


# --------------------------------------------------------------------------
# Whole sequences (any family, through its PagedSpec): the pass, prefill
# cold and behind a prefix hit, forward


def _by_layer(per_segment):
    """Scan outputs ``[(segment, [a tree of [repeats, ...] leaves a
    layer of the period that has one])]`` -> the tree with leaves
    ``[layers, ...]`` in model order (repeat-major inside a segment), or
    None when no layer has one."""
    per_segment = [outs for outs in per_segment if outs]
    if not per_segment:
        return None

    def leaf(*arrays):      # this leaf of every (segment, layer) above
        arrays, parts = iter(arrays), []
        for outs in per_segment:
            mine = [next(arrays) for _ in outs]
            parts.append(jnp.stack(mine, axis=1).reshape(
                (len(mine) * mine[0].shape[0],) + mine[0].shape[1:]))
        return jnp.concatenate(parts, axis=0)
    return jax.tree.map(leaf, *(o for outs in per_segment for o in outs))


def sequence_pass(params, cfg, spec: PagedSpec, x, positions, history=None,
                  page_tokens: Optional[int] = None, last_index=None):
    """``x`` [B, S, d] at ``positions`` [S] through every layer: the
    whole-sequence sibling of :func:`paged_decode_step`, one
    ``lax.scan`` a :class:`Segment` over the family's sequence operators
    (:class:`PagedSpec`). ``history`` = ``(hk, hv, tail)``: the sequence
    (B = 1) continues one whose first P positions are cached: a page
    layer sees the gathered ``hk`` / ``hv`` ``[L_pages, Hkv, Dh, P]``
    (of a latent pool the rows ``hk`` alone, ``hv`` None) in front of
    its own, a state layer starts from ``tail`` (``[L_state, *leaf]`` a
    leaf; None without state layers). Returns ``(x, fresh, tails,
    ends)``: the page layers' ``(k, v)`` ``[L_pages, B, S, Hkv, Dh]`` as
    ``qkv`` gives them (of a latent pool ``(rows,)`` ``[L_pages, S,
    D]``), and with ``page_tokens`` the state layers' ``tails`` (leaves
    ``[L_state, S // (page_tokens * snapshot_every), *leaf]``: the state
    at the end of every page that keeps a snapshot) and ``ends``
    (``[L_state, *leaf]``: at ``last_index``), else None, None; all in
    model order.

    A family that generates by diffusion over blocks (``spec.block`` = W
    > 1) is attended BLOCK-causally, cold and behind a history alike:
    position ``i`` sees ``j`` where ``j // W <= i // W`` (the history a
    whole number of blocks, as a whole number of pages is)."""
    from mpi_acx_tpu.models.decoding import (dense_decode_attend,
                                             to_cache_layout)
    from mpi_acx_tpu.models.llama import _repeat_kv
    from mpi_acx_tpu.ops.attention import (select_attention,
                                           select_block_attention)
    S = x.shape[1]
    hk, hv, tail0 = history if history is not None else (None, None, None)
    P = 0 if hk is None else hk.shape[-1]
    snapshot = page_tokens * spec.snapshot_every if page_tokens else None

    def page_layer(lp, x, xs, pg):
        """A layer with pages -> (x, what it leaves in them). ``xs``
        holds the gathered history of the period's page layers, of
        which this is ``pg``, where there is one."""
        if spec.v_dim is not None:          # latent: the family's, whole
            x, rows = spec.seq_attention(cfg, lp, x, positions, xs.get("hk"))
            return x, (rows,)
        q, k, v = spec.seq_qkv(cfg, lp, x, positions)
        if "hk" in xs:
            kcat, vcat = (jnp.concatenate(
                [xs[h][pg][None].astype(x.dtype), to_cache_layout(own)],
                axis=-1) for h, own in (("hk", k), ("hv", v)))
            o = (dense_decode_attend(q, kcat, vcat, P, P + S, spec.n_rep)
                 if spec.block <= 1 else
                 _block_attend_behind(q, kcat, vcat, P, spec.block,
                                      spec.n_rep))
        else:
            # (the policy takes as many K/V heads as query heads: they
            # are REPEATED for it, as llama's are)
            attention = (select_attention(cfg.use_flash) if spec.block <= 1
                         else select_block_attention(cfg.use_flash,
                                                     spec.block))
            o = attention(
                q, _repeat_kv(k, spec.n_rep), _repeat_kv(v, spec.n_rep))
            o = o.reshape(q.shape[0], S, -1)
        return spec.attn_out(cfg, lp, x, o), (k, v)

    fresh, tails, ends = [], [], []
    pages_at = states_at = 0
    for seg in spec.segments:
        split = _split_leaves(spec, seg, params[seg.key])
        n_pg = sum(k.cache == "pages" for k in seg.period)
        n_st = sum(k.cache == "state" for k in seg.period)

        def cut(a, at, n):
            """Rows [at, at + repeats * n) of a per-layer array as scan
            inputs [repeats, n, ...]."""
            a = a[at:at + seg.repeats * n]
            return a.reshape((seg.repeats, n) + a.shape[1:])

        xs = {"lp": tuple(scanned for scanned, _ in split)}
        if spec.moe_whole:              # the repeat, to find a layer's own
            xs["i"] = jnp.arange(seg.repeats)
        if hk is not None and n_pg:
            if spec.v_dim is None:
                xs["hk"], xs["hv"] = (cut(hk, pages_at, n_pg),
                                      cut(hv, pages_at, n_pg))
            else:
                # a latent layer stands alone in its period: the rows of
                # its one head ride the scan as they lie, [repeats, D, P]
                assert n_pg == 1, seg
                xs["hk"] = hk[pages_at:pages_at + seg.repeats, 0]
        if tail0 is not None and n_st:
            xs["tail"] = jax.tree.map(lambda t: cut(t, states_at, n_st),
                                      tail0)

        def body(x, xs, seg=seg, split=split):
            kv, kept, pg, st = [], [], 0, 0
            for kind, scanned, (_, whole) in zip(seg.period, xs["lp"],
                                                 split):
                lp = _one_layer(scanned, whole, xs.get("i"))
                if kind.cache == "pages":
                    x, new = page_layer(lp, x, xs, pg)
                    kv.append(new)
                    pg += 1
                elif kind.cache == "state":
                    start = (jax.tree.map(lambda t: t[st][None], xs["tail"])
                             if "tail" in xs else None)
                    x, tail, end = spec.seq_state(cfg, lp, x, start,
                                                  last_index, snapshot)
                    if snapshot is not None:
                        kept.append((tail, end))
                    st += 1
                if kind.ffn == "moe":
                    x = spec.ffn(cfg, lp, x, kind.ffn)[0]
                elif kind.ffn != "none":
                    x = spec.ffn(cfg, lp, x, kind.ffn)
            return x, (tuple(kv), tuple(kept))

        x, (kv, kept) = lax.scan(body, x, xs)
        fresh.append(kv)
        tails.append([t for t, _ in kept])
        ends.append([e for _, e in kept])
        pages_at += seg.repeats * n_pg
        states_at += seg.repeats * n_st
    return x, _by_layer(fresh), _by_layer(tails), _by_layer(ends)


def _block_attend_behind(q, kc, vc, P: int, block: int, n_rep: int):
    """``decoding.dense_decode_attend`` under the block-causal mask: q
    [B, S, Hq, D], rows at positions ``P ..`` (``P`` a whole number of
    blocks), against ``kc`` / ``vc`` [B, Hkv, D, P + S] in cache layout;
    row ``w`` sees the columns below the end of its own block, ``P + (w
    // block + 1) * block``. -> [B, S, Hq * D]."""
    B, S = q.shape[:2]
    Hkv, Dh = kc.shape[1], kc.shape[2]
    qg = (q.reshape(B, S, Hkv, n_rep, Dh).astype(jnp.float32)
          * (1.0 / Dh ** 0.5)).astype(q.dtype)
    logits = jnp.einsum("bqgrd,bgdk->bgrqk", qg, kc).astype(jnp.float32)
    ends = P + (jnp.arange(S) // block + 1) * block
    seen = jnp.arange(kc.shape[-1])[None, :] < ends[:, None]
    logits = jnp.where(seen, logits, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bgdk->bqgrd", p, vc).reshape(B, S, -1)


def forward(params, cfg, spec: PagedSpec, tokens):
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): a family's
    plain whole-sequence pass, no cache."""
    x = params["embed"][tokens].astype(cfg.dtype)
    x = sequence_pass(params, cfg, spec, x, jnp.arange(tokens.shape[1]))[0]
    return spec.seq_head(params, cfg, x)


def prefill(params, cfg, spec: PagedSpec, tokens, last_index,
            kv_int8: bool = False, page_tokens: Optional[int] = None,
            history=None):
    """What ``serving.paged_prefill`` / ``paged_suffix_prefill`` run.
    One prompt ``tokens`` [1, S] (bucket-padded, its real last token at
    ``last_index``) -> (logits [1, 1, vocab] there, ``one``). ``one``
    holds ``'k','v'[,'ks','vs']`` ``[L_pages, 1, Hkv, *, S]`` in cache
    layout (:func:`decoding.pack_kv`; of a latent pool ``'k'`` alone,
    the rows) ready for :meth:`PagedKV.scatter_prompt` and, with state
    layers and ``page_tokens``, ``'tail'`` ``[L_state, S // (page_tokens
    * snapshot_every), *leaf]`` a leaf (the state at the end of every
    ``snapshot_every``-th whole page) and ``'end'`` ``[L_state, *leaf]``
    (at ``last_index``; padding behind it leaves no mark).

    With ``history`` = ``(hk, hv, tail)`` ``tokens`` is only the SUFFIX
    of a prompt whose first P tokens are paged in (a radix hit, with
    state layers cut back to a page that holds a snapshot), at positions
    ``P ..``: :meth:`PagedKV.gather_history` 's ``hk`` / ``hv`` and
    :meth:`PagedKV.restore_tail` 's ``tail``, as :func:`sequence_pass`
    takes them. The compute skipped is the point: a hit at depth P runs
    S rows through the trunk instead of P + S. The cost is bitwise
    freedom: the shapes differ from the cold pass's, so hit-path logits
    match cold only to numerics (docs/DESIGN.md §19)."""
    from mpi_acx_tpu.models.decoding import pack_kv, to_cache_layout
    if spec.prefill is not None:            # GPT-2's own: PagedSpec
        if history is None:
            return spec.prefill(params, cfg, tokens, last_index, kv_int8,
                                page_tokens)
        return spec.suffix_prefill(params, cfg, tokens, *history, last_index,
                                   kv_int8, page_tokens)
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(tokens.shape[1])
    if history is not None:
        positions = history[0].shape[-1] + positions
    x, fresh, tails, ends = sequence_pass(
        params, cfg, spec, x, positions, history, page_tokens, last_index)
    x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    if spec.v_dim is not None:
        # rows [L, S, D] -> the pool's [L, 1, 1 head, D, S]
        one = {"k": to_cache_layout(fresh[0][:, None, :, None, :])}
    else:
        one = pack_kv(*fresh, kv_int8)
    if tails is not None:
        one["tail"], one["end"] = tails, ends
    if spec.block > 1:
        # generation by diffusion over blocks: a prompt's whole blocks
        # are stored and NO token comes of them (no head runs); the first
        # arrives with the first block of a chunk
        return None, one
    return spec.seq_head(params, cfg, x), one


def prefill_with_history(params, cfg, suffix, hk, hv, last_index,
                         kv_int8: bool = False):
    """GPT-2's ``PagedSpec.suffix_prefill``. Prefill ONLY the suffix of a prompt whose first ``P`` tokens'
    K/V are already paged in (a radix prefix hit): ``suffix``
    [1, S_suf] tokens occupying absolute positions ``P..P+S_suf-1``,
    ``hk``/``hv`` [L, H, Dh, P] the gathered (dequantized) history in
    cache layout. Per layer the suffix queries attend ``concat(history,
    suffix)`` through the shared :func:`dense_decode_attend` definition
    (pos=P scalar — row w sees cols <= P + w, full history + causal
    suffix). Returns (logits [1, 1, vocab] at ``last_index``, the
    suffix K/V as a :func:`decoding.pack_kv` dict [L, 1, H, *, S_suf]
    ready for :meth:`PagedKV.scatter_prompt` — int8 codes + scales when
    ``kv_int8``).

    The compute skipped is the point: a hit at depth P runs S_suf
    rows through the trunk instead of P + S_suf. The cost is bitwise
    freedom — the concat shapes differ from the cold full-prompt
    pass, so hit-path logits match cold only to numerics (docs/
    DESIGN.md §19)."""
    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.models.decoding import (dense_decode_attend, pack_kv,
                                             to_cache_layout)
    from mpi_acx_tpu.ops.wquant import wread

    B, Sb = suffix.shape
    P = hk.shape[-1]
    x = (params["embed"][suffix]
         + params["pos"][P:P + Sb]).astype(cfg.dtype)

    def body(x, xs):
        lp, hkl, hvl = xs
        q, k, v = tfm._qkv(cfg, lp, x)
        kcat = jnp.concatenate(
            [hkl[None].astype(x.dtype), to_cache_layout(k)], axis=-1)
        vcat = jnp.concatenate(
            [hvl[None].astype(x.dtype), to_cache_layout(v)], axis=-1)
        o = dense_decode_attend(q, kcat, vcat, P, P + Sb, 1)
        x = x + o @ wread(lp, "wo", x.dtype)
        return tfm._mlp(cfg, lp, x), (k, v)

    x, (ks, vs) = lax.scan(body, x, (params["layers"], hk, hv))
    x = tfm.layernorm(x, params["lnf_g"], params["lnf_b"])
    x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    logits = jnp.einsum("bsd,vd->bsv", x,
                        params["embed"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return logits, pack_kv(ks, vs, kv_int8)


# --------------------------------------------------------------------------
# Host-side paged state manager


class PagedKV:
    """The serving scheduler's view of the page plane: device pool +
    host block tables + allocator + (optional) radix prefix cache.
    The scheduler calls the seat/grow/release methods; the jitted step
    consumes :meth:`device_state` and hands the donated result back
    through :meth:`absorb`."""

    def __init__(self, cfg, family, n_slots: int, max_len: int,
                 page_tokens: int, n_pages: int, kv_int8: bool = False,
                 prefix_cache: bool = False,
                 n_snapshots: Optional[int] = None):
        assert max_len % page_tokens == 0, \
            (f"max_len={max_len} must be a multiple of "
             f"page_tokens={page_tokens} (the block table tiles the "
             "cache exactly)")
        self.spec = paged_spec(family, cfg)
        # Generation by diffusion over blocks attends a block whole: a
        # page's K/V are a function of the tokens up to ITS end, which
        # is what lets the trie share it, only where no block straddles
        # a page boundary.
        assert self.spec.block <= 1 or page_tokens % self.spec.block == 0, \
            (f"page_tokens={page_tokens} must be a multiple of the "
             f"family's block of {self.spec.block}")
        if kv_int8 and not self.spec.kv_int8:
            raise NotImplementedError(
                "kv_int8 pages are not wired for family "
                f"{getattr(family, '__name__', family)!r} (its PagedSpec "
                "says kv_int8=False): serve it with kv_int8=False")
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.page_tokens = int(page_tokens)
        self.n_pages = int(n_pages)
        self.max_pages = max_len // page_tokens
        self.kv_int8 = bool(kv_int8)
        # Snapshot rows (module docstring): by default one for every
        # page that the spec's rule can give one, so that none is ever
        # evicted; fewer is a size, and costs resume points only.
        self.n_snapshots = (pages_needed(n_pages, self.spec.snapshot_every)
                            if n_snapshots is None else int(n_snapshots))
        self.pool = init_page_pool(cfg, n_pages, page_tokens, n_slots,
                                   kv_int8=kv_int8, spec=self.spec)
        self._fresh_books(prefix_cache)
        # The slots' fixed state (module docstring), None without state
        # layers; the routing each chunk's steps counted (a tuple as
        # _moe_tally's) and the snapshots loaded to continue a sequence.
        self.held = self._fresh_held()
        self.moe_chunks: List[Tuple[int, ...]] = []     # one a chunk
        # ... and the slot-steps its state operators were told are dead
        self.state_dead_chunks: List[int] = []
        self.tail_restores = 0
        # Slot b's parking page sits past the allocator's range.
        self._park = [n_pages + b for b in range(n_slots)]
        self.pages: List[List[int]] = [[] for _ in range(n_slots)]
        self.pos = np.zeros((n_slots,), np.int32)
        self.table = np.asarray(
            [[self._park[b]] * self.max_pages
             for b in range(n_slots)], np.int32)
        self._dev_table = None
        self.pages_hwm = 0
        self.preemptions = 0
        # What a finished request left behind (:meth:`release` with its
        # rid): its pages, the position it stopped at, and how many
        # pages the allocator had handed out by then.
        self.retired: Dict[int, Tuple[List[int], int, int]] = {}

    # -- device state ------------------------------------------------------

    def _fresh_held(self):
        return jax.tree.map(
            lambda l: jnp.zeros((self.spec.n_state_layers, self.n_slots)
                                + l.shape, l.dtype), self.spec.state)

    def _fresh_books(self, prefix_cache: bool) -> None:
        """Allocator, snapshot store and trie, all empty; a page that is
        reclaimed frees its snapshot row."""
        self.alloc = PageAllocator(self.n_pages)
        self.snaps = (SnapshotStore(self.spec, self.n_snapshots)
                      if self.spec.n_state_layers else None)
        if self.snaps is not None:
            self.alloc.on_free = self.snaps.drop
        self.prefix = (RadixPrefixCache(self.alloc, self.page_tokens,
                                        self.snaps)
                       if prefix_cache else None)

    def device_state(self, left=None):
        """What the step program takes. ``left`` ([n_slots]: the tokens
        each slot still owes, ``RequestBook.left``) rides along as
        ``state['left']`` when the loop has it."""
        if self._dev_table is None:
            self._dev_table = jnp.asarray(self.table)
        state = {k: self.pool[k] for k in _POOL_KEYS if k in self.pool}
        state["table"] = self._dev_table
        state["pos"] = jnp.asarray(self.pos)
        if left is not None:
            state["left"] = jnp.asarray(left, jnp.int32)
        if self.held is not None:
            state["held"] = self.held
        if self.spec.state_live:
            state["state_dead"] = jnp.zeros((), jnp.int32)
        if self.spec.n_experts:
            # A slot owns a request exactly while it holds pages.
            state["owns"] = jnp.asarray([bool(p) for p in self.pages])
            state["moe"] = jnp.zeros(
                (5 if self.spec.experts_held is None else 7,), jnp.int32)
        return state

    def absorb(self, state) -> None:
        self.pool = dict(self.pool, **{k: state[k] for k in _POOL_KEYS
                                       if k in state})
        self._dev_table = state["table"]
        # np.array (copy): np.asarray of a device array is a read-only
        # view, and the host mirror gets written by seat/release.
        self.pos = np.array(state["pos"], np.int32)
        if "held" in state:
            self.held = state["held"]
        if "moe" in state:
            self.moe_chunks.append(tuple(int(n) for n in
                                         np.asarray(state["moe"])))
        if "state_dead" in state:
            self.state_dead_chunks.append(int(state["state_dead"]))

    def reset_pool(self) -> None:
        """Rebuild the device pool from zeros (after a failed donated
        step the buffers can't be trusted) and drop every reference —
        allocator, tables, and the prefix cache start over."""
        self.pool = init_page_pool(self.cfg, self.n_pages,
                                   self.page_tokens, self.n_slots,
                                   kv_int8=self.kv_int8, spec=self.spec)
        self.held = self._fresh_held()
        was, was_snaps = self.prefix, self.snaps
        self._fresh_books(was is not None)
        if was is not None:
            self.prefix.hits, self.prefix.evictions = was.hits, was.evictions
            self.prefix.pages_reused = was.pages_reused
        if was_snaps is not None:
            self.snaps.taken, self.snaps.evictions = (was_snaps.taken,
                                                      was_snaps.evictions)
            self.snaps.rows_hwm = was_snaps.rows_hwm
        self.pages = [[] for _ in range(self.n_slots)]
        self.retired = {}
        self.pos = np.zeros((self.n_slots,), np.int32)
        self.table = np.asarray(
            [[self._park[b]] * self.max_pages
             for b in range(self.n_slots)], np.int32)
        self._dev_table = None

    def live_pages(self, chunk: int, left=None) -> int:
        """Pages one layer's attends fetch out of the pool over the
        next ``chunk`` steps: a slot reads in each step in which it can
        still deliver a token (the first ``min(left[b], chunk)`` of
        them; all of them without ``left``, as before PR 38) the pages
        that hold a token below its ``pos`` now (the chunk's own tokens
        come from the stage), at least one and at most its table row.
        A slot that owns no request (``left`` 0) reads none; without
        ``left`` an idle slot's are its parking page, again and
        again."""
        pt = self.page_tokens
        steps = np.asarray(chunk if left is None else np.clip(left, 0, chunk))
        if self.spec.block > 1:
            # a block family: ``left`` counts positions, and a slot
            # reads in every forward of each block that is live
            steps = (-(-steps // self.spec.block)
                     * (self.spec.denoise_steps + 1))
        return int((steps * np.clip((self.pos + pt - 1) // pt, 1,
                                    self.max_pages)).sum())

    def chunk_rewrites(self, chunk: int) -> int:
        """Pages one layer's flush reads and writes back after the
        next ``chunk`` steps (``flash_decode.paged_kv_write_runs``): for
        each run of at most a page's tokens a page a slot, two where the
        run crosses into another (an idle slot's next page is its
        parking page again)."""
        pt, rows, total = self.page_tokens, np.arange(self.n_slots), 0
        for s in range(0, chunk, pt):
            pos = self.pos + s
            first = np.minimum(pos // pt, self.max_pages - 1)
            after = np.minimum(first + 1, self.max_pages - 1)
            crosses = ((pos % pt + min(pt, chunk - s) > pt)
                       & (self.table[rows, after] != self.table[rows, first]))
            total += self.n_slots + int(crosses.sum())
        return total

    # -- table bookkeeping -------------------------------------------------

    def _sync_row(self, b: int) -> None:
        row = self.pages[b] + [self._park[b]] * (self.max_pages
                                                 - len(self.pages[b]))
        self.table[b] = np.asarray(row, np.int32)
        self._dev_table = None

    def _note_hwm(self) -> None:
        self.pages_hwm = max(self.pages_hwm, self.alloc.used_count)

    def alloc_evicting(self, n: int) -> Optional[List[int]]:
        """Allocate n pages, evicting prefix-cache LRU leaves to make
        room; None when the pool can't cover n even fully drained."""
        while self.alloc.free_count < n:
            if self.prefix is None or not self.prefix.evict_one():
                return None
        got = self.alloc.alloc(n)
        if got is not None:
            self._note_hwm()
        return got

    def seat(self, b: int, prompt_pages: List[int],
             fresh_pages: List[int], new_pos: int, rid: int = -1,
             state=None) -> None:
        """Slot b takes ownership of ``prompt_pages + fresh_pages``
        (references already held by the caller) at position
        ``new_pos``. ``rid`` only labels the journey event (ACX_REQLOG,
        docs/DESIGN.md §20) — the allocator itself is request-blind.
        With state layers the slot's fixed state becomes ``state``
        (``[L_state, *leaf]`` a leaf), the prefill's ``'end'`` (zeros
        when None: whatever the slot's last request left is dropped)."""
        assert not self.pages[b], (b, "seat of an occupied slot")
        if self.held is not None:
            if state is None:
                state = jax.tree.map(lambda h: jnp.zeros_like(h[:, 0]),
                                     self.held)
            self.held = _seat_state(self.held, state, jnp.int32(b))
        self.pages[b] = list(prompt_pages) + list(fresh_pages)
        assert len(self.pages[b]) <= self.max_pages, \
            (b, len(self.pages[b]), self.max_pages)
        self.pos[b] = new_pos
        reqlog.emit("seat", rid, slot=b, pages=len(self.pages[b]),
                    shared=len(prompt_pages), pos=new_pos)
        self._sync_row(b)

    def release(self, b: int, rid: int = -1) -> None:
        """Drop slot b's page references (shared prefix pages survive
        through the trie's reference) and park the slot. Its fixed
        state is dropped with them: the next seat overwrites it. With
        ``rid`` (a request that FINISHED there) what it leaves behind is
        noted for :meth:`left_behind`."""
        if rid >= 0:
            self.retired[rid] = (list(self.pages[b]), int(self.pos[b]),
                                 self.alloc.issues)
        for p in self.pages[b]:
            self.alloc.decref(p)
        self.pages[b] = []
        self.pos[b] = 0
        self._sync_row(b)

    def left_behind(self, rid: int):
        """``(pages, pos)`` of finished request ``rid`` if the pages it
        held when it retired still hold its K/V up to ``pos`` (nobody
        has been handed one of them since; a page reclaimed keeps its
        content until then), else None: what a reader of the pool
        (tests, the benchmark's comparison) may gather after the call."""
        pages, pos, issues = self.retired.get(rid, ((), 0, 0))
        if not pages or not self.alloc.untouched_since(pages, issues):
            return None
        return pages, pos

    def grow(self, b: int, need_pages: int) -> bool:
        """Extend slot b's page list to ``need_pages``; False when the
        pool is dry even after prefix eviction (caller preempts)."""
        need_pages = min(need_pages, self.max_pages)
        short = need_pages - len(self.pages[b])
        if short <= 0:
            return True
        got = self.alloc_evicting(short)
        if got is None:
            return False
        self.pages[b].extend(got)
        self._sync_row(b)
        return True

    def ensure_writable(self, b: int, j: int) -> bool:
        """Copy-on-write: if slot b's page j is shared (refcount > 1),
        give the slot a private copy. Unreachable under the default
        policy (RadixPrefixCache docstring) — kept as the defensive
        guard the scheduler runs before decode writes. Returns True
        iff a copy was made."""
        page = self.pages[b][j]
        if self.alloc.refcount(page) <= 1:
            return False
        got = self.alloc_evicting(1)
        if got is None:
            raise RuntimeError(
                "copy-on-write with a dry pool (admission should have "
                "bounded the request)")
        # (no snapshot goes along: no trie holds the copy, and the
        # slot's own state is where the sequence continues from)
        self.pool = _copy(self.pool, jnp.int32(page), jnp.int32(got[0]))
        self.pages[b][j] = got[0]
        self.alloc.decref(page)
        self._sync_row(b)
        return True

    # -- prompt scatter / history gather -----------------------------------

    def scatter_prompt(self, one, pages: List[int], start_page: int = 0,
                       whole: Optional[int] = None) -> None:
        """Write a prefilled cache (``one`` = {'k','v'[,'ks','vs']:
        [L, 1, H, *, S_bucket]}) into ``pages`` — page d takes bucket
        tokens [d*pt, (d+1)*pt) (zero-padded tokens past the prompt are
        never attended). ``start_page`` offsets the SOURCE rows only
        (0 for a cold full-prompt scatter; unused pages cost
        nothing — only ``len(pages)`` pages are written). With state
        layers, ``one['tail']`` goes to the snapshot store in the same
        program: of the first ``whole`` pages (the prompt's whole
        pages; None: all that ``one`` has a tail for) every
        ``snapshot_every``-th takes a row, the rest write the sink."""
        if not pages:
            return
        idx, rows = [pages], []
        if self.snaps is not None:
            every = self.spec.snapshot_every
            n_tail = jax.tree.leaves(one["tail"])[0].shape[1]
            whole = len(pages) if whole is None else whole
            rows = [self.snaps.take(pages[(j + 1) * every - 1])
                    if (j + 1) * every <= whole else self.snaps.sink
                    for j in range(min(len(pages) // every, n_tail))]
        if rows:        # one upload: the pages, and under them the rows
            idx.append(rows + [self.snaps.sink] * (len(pages) - len(rows)))
        self.pool, snaps = _scatter(
            self.pool, self.snaps.rows if rows else None,
            {k: one[k] for k in _POOL_KEYS if k in one and k in self.pool},
            one["tail"] if rows else None,
            jnp.asarray(idx if rows else pages, jnp.int32))
        if rows:
            self.snaps.rows = snaps

    def gather_history(self, pages: List[int]):
        """Gather ``pages`` into contiguous [L, H, Dh, n*pt] history
        K/V (cache layout) in compute dtype (dequantizing int8 pages —
        the only page-resident form — through their f32 scales); of a
        latent pool ``(rows [L, 1, D, n*pt], None)``."""
        return _gather(self.pool, jnp.asarray(pages, jnp.int32),
                       dtype=self.cfg.dtype,
                       latent=self.spec.v_dim is not None)

    def restore_tail(self, page: int):
        """The snapshot at the end of ``page`` (``[L_state, *leaf]`` a
        leaf of the spec's state tree), to continue a sequence from
        there (a radix hit's suffix prefill; a resume is one when the
        trie kept its pages); None without state layers. ``page`` is
        one a match returned last, so it holds a row."""
        if self.snaps is None:
            return None
        self.tail_restores += 1
        return _tail(self.snaps.rows, jnp.int32(self.snaps.row_of[page]))


# PagedKV's programs, one compile per shape in jit's own cache:
# what the per-instance closures captured (pages written, bucket, pool
# keys, page size) is all in the arguments' shapes and tree.


def _at_page(arr, page):
    return (0, page) + (0,) * (arr.ndim - 2)


@partial(jax.jit, donate_argnums=(0,))
def _copy(pool, src, dst):
    note_trace()
    out = {}
    for key in pool:
        page_data = lax.dynamic_index_in_dim(pool[key], src, 1,
                                             keepdims=True)
        out[key] = lax.dynamic_update_slice(pool[key], page_data,
                                            _at_page(pool[key], dst))
    return out


@partial(jax.jit, donate_argnums=(0, 1))
def _scatter(pool, snaps, one, tail, idx):
    """Pages and snapshot rows in one program. ``idx`` is the pages
    [n], or with snapshots to write [2, n]: the pages, and under them
    the rows of ``snaps`` that ``tail`` ([L_state, m, *leaf] a leaf)
    goes to, row j to ``idx[1, j]`` (the sink where no page keeps it)."""
    note_trace()
    pages_arr = idx if tail is None else idx[0]
    pt, bucket = pool["k"].shape[-1], one["k"].shape[-1]
    for j in range(pages_arr.shape[0]):
        n = min(pt, bucket - j * pt)
        if n <= 0:
            break
        for key in one:
            src = one[key][:, 0, ..., j * pt:j * pt + n]
            pool[key] = lax.dynamic_update_slice(
                pool[key], src[:, None].astype(pool[key].dtype),
                _at_page(pool[key], pages_arr[j]))
    if tail is not None:
        m = jax.tree.leaves(tail)[0].shape[1]
        for j in range(min(m, pages_arr.shape[0])):
            snaps = jax.tree.map(
                lambda rows, t: lax.dynamic_update_slice(
                    rows, t[:, j:j + 1].astype(rows.dtype),
                    _at_page(rows, idx[1, j])), snaps, tail)
    return pool, snaps


@jax.jit
def _tail(snaps, row):
    note_trace()
    return jax.tree.map(
        lambda rows: lax.dynamic_index_in_dim(rows, row, 1, keepdims=False),
        snaps)


@partial(jax.jit, donate_argnums=(0,))
def _seat_state(held, state, b):
    note_trace()
    return jax.tree.map(
        lambda h, s: lax.dynamic_update_index_in_dim(h, s.astype(h.dtype),
                                                     b, 1), held, state)


@partial(jax.jit, static_argnames=("dtype", "latent"))
def _gather(pool, pages_arr, *, dtype, latent=False):
    note_trace()

    def grab(key):
        return jnp.take(pool[key], pages_arr, axis=1)

    def join(t):          # [L, n, H, Dh, pt] -> [L, H, Dh, n*pt]
        t = jnp.moveaxis(t, 1, 3)
        return t.reshape(t.shape[:3] + (-1,)).astype(dtype)
    if latent:            # a latent pool: its rows; no V to gather
        return join(grab("k")), None
    k, v = grab("k"), grab("v")
    if "ks" in pool:
        k = k.astype(jnp.float32) * grab("ks")
        v = v.astype(jnp.float32) * grab("vs")
    return join(k), join(v)


# --------------------------------------------------------------------------
# Native-metrics publication (no-build/no-load discipline)


def publish_page_stats_best_effort(pages_free: int, pages_shared: int,
                                   prefix_hits: int,
                                   prefix_evictions: int,
                                   preemptions: int) -> bool:
    """Mirror the page plane into the native registry gauges/counters
    (src/core/metrics.cc: pages_free, pages_shared, prefix_hits,
    prefix_evictions, preemptions) — but only when the native runtime
    is already loaded; never build or load the library for telemetry
    (the ``_flight_dump_best_effort`` discipline)."""
    try:
        import ctypes
        import mpi_acx_tpu.runtime as _rt
        if _rt._lib is None:
            return False
        _rt._lib.acx_serving_page_stats(
            ctypes.c_uint64(pages_free), ctypes.c_uint64(pages_shared),
            ctypes.c_uint64(prefix_hits),
            ctypes.c_uint64(prefix_evictions),
            ctypes.c_uint64(preemptions))
        return True
    except Exception:  # pragma: no cover — diagnostics must never raise
        return False
