"""Continuous-batching serving loop (single device).

The reference has no serving stack at all (SURVEY.md §0); this module
is the framework-goal tier above models/decoding.py. A static-batch
server leaves slots idle from the moment their request finishes until
the whole batch drains — at B slots and mixed output lengths that is a
bubble of up to (B-1)/B of the work. Here B cache slots decode in
lockstep as ONE jitted step while a host-side scheduler swaps finished
requests out and queued prompts in mid-stream, so the device never
waits for the slowest request.

The mechanism is per-slot positions: decode_layer_scan's vector-pos
mode writes each slot's fresh K/V at its own ``pos[b]`` and
grouped_decode_attend masks each slot at ``cols <= pos[b]`` — every
slot's math is exactly its solo run's (no left-padding, no shared
clock), so greedy outputs are bit-equal to per-request generate()
(tested). Prompts are right-padded to a power-of-two bucket for the
prefill compile cache; pad rows are never attended (they sit past
``pos[b]`` until overwritten by decode writes).

Static shapes throughout: one compiled prefill per bucket length, one
compiled decode step, one compiled slot-scatter — the host loop only
schedules.
"""

from __future__ import annotations

import ctypes
import json
import math
import os
import time
from bisect import bisect_right
from collections import Counter, deque
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpi_acx_tpu import backend, reqlog
from mpi_acx_tpu.models import kvpage


def _pct(samples: List[float], p: float) -> float:
    """Nearest-rank percentile: the ceil(p*n)-th smallest sample, no
    interpolation."""
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[max(0, math.ceil(p * len(s)) - 1)]


@dataclass
class RequestTelemetry:
    """Per-request serving telemetry (times from the batch's arrival,
    RequestBook.t0, so queue wait is included — the number a caller of
    a serving system actually experiences)."""

    rid: int
    ttft_s: float        # time to first token (prefill emits it)
    latency_s: float     # arrival -> retire
    new_tokens: int
    tokens_per_s: float  # new_tokens / latency_s
    retries: int         # failed attempts that re-queued this request
    # serve_paged_greedy only, on its ENTRY clock (see its docstring):
    queue_wait_s: float = 0.0  # entry -> start of the refill that seated it
    prefill_s: float = 0.0     # that refill's ``refill.prefill`` span
    refill_host_s: float = 0.0  # its ``refill.match`` + ``.scatter`` + ``.seat``
    # ... and read from the call's span record at its end (_request_paths):
    prefill_wait_s: float = 0.0  # program handed over -> first token on host
    decode_s: float = 0.0      # first token -> last token delivered: the
    # end of the ``refill.seat`` that seated it -> the end of the last
    # ``chunk.deliver`` of a chunk it owned a slot in; of that interval,
    decode_in_refill_s: float = 0.0  # under OTHER requests' ``refill.*`` spans
    decode_in_chunk_s: float = 0.0   # under its chunks' ``.upload`` + ``.step``
    chunks: int = 0            # chunks it owned a slot in
    # A family that generates by diffusion over blocks: ``(token, the
    # denoising step that committed it)`` of every position its blocks
    # computed, from the prompt's last whole block on, in position order
    # (-1: the prompt's own tokens in its first block; behind the
    # ``new_tokens`` delivered, a last block's excess).
    block_log: List[tuple] = field(default_factory=list)


@dataclass
class ServingMetrics:
    """Batch-level serving telemetry returned on ServedBatch.metrics."""

    requests: int = 0
    wall_s: float = 0.0
    new_tokens: int = 0
    tokens_per_s: float = 0.0     # aggregate: new_tokens / wall_s
    steps: int = 0                # decode step_fn dispatches
    prefills: int = 0             # successful refills
    requeues: int = 0             # failure-path restarts
    peer_requeues: int = 0        # requeues from peer loss (uncharged)
    slots_shed: int = 0           # slots retired to match lost capacity
    slots_revived: int = 0        # shed slots returned after a fleet join
    hang_dumps: int = 0           # flight dumps written on step failure
    rejections: int = 0           # typed admission rejections
    rejection_reasons: Dict[str, int] = field(default_factory=dict)
    preemptions: int = 0          # paged: page-pressure evictions (uncharged)
    prefix_hits: int = 0          # paged: radix-cache prompt matches
    prefix_evictions: int = 0     # paged: trie pages evicted under pressure
    prefix_pages_reused: int = 0  # paged: prompt pages seated from the trie
    pages_hwm: int = 0            # paged: pool pages-in-use high-water mark
    paged_kv_write: str = ""      # paged: select_paged_kv_write's choice, and
    # what it had to do (PR 36): the tokens a layer staged, the pages its
    # flushes rewrote (PagedKV.chunk_rewrites), from pos at a chunk's start
    kv_tokens_staged: int = 0
    kv_page_rewrites: int = 0
    # paged: select_paged_decode_attend's choice, and the pages a layer's
    # attends fetch out of the pool (PagedKV.live_pages) over ``n_slots x
    # max_pages x chunk``, the (slot, page) grid's steps until PR 30.
    # ``attend_pages_dead`` (PR 38): the pages the same chunks would
    # have fetched besides, for the slot-steps that can deliver no token
    # (a slot that owns no request, or whose request ended earlier in
    # the chunk), and since the loop hands the chunk ``left`` do not.
    paged_decode_attend: str = ""
    attend_pages_walked: int = 0
    attend_pages_dead: int = 0
    attend_pages_grid: int = 0
    # paged: the kinds of operator and of FFN the step program was built
    # from (kvpage.PagedSpec.built: "attention", "attention+conv",
    # "attention+mamba";
    # "dense:_mlp", "dense:_dense_ffn+moe:sorted_expert_ffn"), and what a
    # routed FFN had to do, counted ON THE DEVICE in every decode step
    # and MoE layer over the slots that own a request and can still
    # deliver a token at that step (kvpage._moe_tally): (token, expert)
    # pairs routed, distinct experts hit, the fullest expert's pairs:
    # what the expert kernel computes and reads, since the chunk is told
    # ``left`` and routes no other slot's pairs. ``moe_pairs_dead``: the
    # pairs that mask left out (idle slots' and those of requests that
    # ended earlier in the chunk). ``moe_layer_steps``: (MoE layer,
    # decode step) pairs counted; ``moe_experts``: the router's width
    # (0 without).
    paged_operator: str = ""
    paged_ffn: str = ""
    moe_assignments: int = 0
    moe_experts_live: int = 0
    moe_load_max: int = 0
    moe_layer_steps: int = 0
    moe_pairs_dead: int = 0
    moe_experts: int = 0
    # Where the experts held here are a share of the router's
    # (PagedSpec.experts_held): ``moe_experts_live`` / ``moe_load_max``
    # count over the HELD experts, ``moe_pairs_held`` the routed pairs
    # whose expert is held (``moe_assignments``: all of them), and
    # ``moe_group_hits`` the owning slots' tokens whose kept routing
    # groups include the held experts' own; ``moe_experts_held``: how
    # many are held (0: every one).
    moe_pairs_held: int = 0
    moe_group_hits: int = 0
    moe_experts_held: int = 0
    # Where the experts work in a latent (PagedSpec.moe_row_dim): its
    # width (``moe_latent_rows`` below: the rows of it dispatched).
    moe_row_dim: int = 0
    # (pairs, experts hit, fullest, layer-steps[, pairs held, group
    # hits], pairs dead) of each decode chunk
    moe_by_chunk: List[tuple] = field(default_factory=list)
    # paged: snapshots of the state layers loaded to continue a sequence
    # from a page's end (radix hits, resumes that hit; the name is from
    # when the only state was a conv's tail), and the snapshot store's
    # book (kvpage.SnapshotStore): bytes of one slot's state over the
    # state layers, rows handed to whole prompt pages, the most rows
    # held at once, rows taken from the least recently used page.
    conv_tail_restores: int = 0
    state_bytes_slot: int = 0
    # ... and the seats that began from one (a restore whose prefill
    # failed seats nobody); the slot-steps of each decode chunk that
    # could still deliver a token (``RequestBook.left`` clipped to the
    # chunk): the state a chunk is ASKED to move is theirs. A state
    # operator that reads ``live`` (``PagedSpec.state_live``: Mamba-2's
    # ``ssd_update``) moves theirs alone, and ``state_steps_dead`` is
    # what the step program counted of the others, once a slot-step
    # whatever the state layers (with the delivering ones: every
    # slot-step of the chunks); elsewhere (Jamba, LFM2) every slot's
    # state moves, idle or not, and it is 0.
    state_snapshot_seats: int = 0
    state_steps_by_chunk: List[int] = field(default_factory=list)
    state_steps_dead: int = 0
    # paged: bytes a token keeps in the page pools over all the layers
    # with pages (from the pools' shapes: K and V of every K/V head and
    # an int8 cache's scales, or a latent pool's one row)
    kv_bytes_token: int = 0
    state_snapshots_taken: int = 0
    state_snapshot_rows_hwm: int = 0
    state_snapshot_evictions: int = 0
    slo_deferrals: int = 0        # paged: refills deferred by the SLO gate
    ttft_p50_s: float = 0.0
    ttft_p99_s: float = 0.0
    itl_p50_s: float = 0.0        # inter-token latency (per decoded token)
    itl_p99_s: float = 0.0
    queue_depth_max: int = 0
    queue_depth_mean: float = 0.0
    slot_occupancy_mean: float = 0.0  # slots owned at a chunk's START
    per_request: List[RequestTelemetry] = field(default_factory=list)
    # The decode work, counted where the tokens are consumed
    # (RequestBook.deliver).
    decode_slot_steps: int = 0    # every chunk: chunk x n_slots
    decode_tokens: int = 0        # tokens the deliver loop consumed
    # paged, a family that generates by diffusion over blocks
    # (kvpage.PagedSpec.block = ``block_length`` > 1; all 0 elsewhere): a
    # slot-step is a POSITION (``decode_slot_steps``: the positions the
    # chunks' blocks computed, live and dead slots together;
    # ``decode_tokens`` of them were delivered). The forwards the chunks
    # ran, by kind: ``denoise_steps`` a block that commit tokens, one
    # more that stores the finished block's K/V. The positions computed
    # and NOT delivered: ``block_positions_kept`` in blocks that
    # delivered something or could (a prompt's last ``P mod W`` tokens in
    # its first block, a last block's excess past ``n_new``),
    # ``block_positions_dead`` in the blocks of slots with nothing left
    # to deliver (idle, or the request ended earlier in the chunk).
    # ``block_by_chunk``: (denoising forwards, storing forwards,
    # positions, delivered, kept, dead, pages a layer's attends walked)
    # of each chunk.
    block_length: int = 0
    denoise_steps: int = 0
    forwards_denoise: int = 0
    forwards_store: int = 0
    block_positions_kept: int = 0
    block_positions_dead: int = 0
    block_by_chunk: List[tuple] = field(default_factory=list)
    # serve_paged_greedy only: the call split into the phases of its
    # docstring's table (profiling.Phases: self seconds and entries per
    # span name; the self times sum to call_s).
    call_s: float = 0.0           # function entry -> return (wall_s + set-up)
    phase_s: Dict[str, float] = field(default_factory=dict)
    phase_n: Dict[str, int] = field(default_factory=dict)
    # ... and every span of the call, in order of opening
    # (profiling.Phases.spans: name, t0, t1, parent, ids, the hand-over
    # mark, the programs JAX loaded inside it), with what the loop reads
    # from it once, at the call's end: the waiting spans that took more
    # than STALL_FACTOR times their group's median (stalled_spans), as a
    # count and as the seconds above the median.
    spans: List[object] = field(default_factory=list)
    stalls: int = 0
    stall_s: float = 0.0
    # How many of the paged path's programs were TRACED during this call
    # (kvpage.programs_traced): 10-20 in a process's first call, 0 in
    # every later one with the same static arguments and shapes.
    programs_traced: int = 0

    @property
    def state_snapshot_restores(self) -> int:
        """``conv_tail_restores`` under the name of what it is."""
        return self.conv_tail_restores

    @property
    def state_slot_steps(self) -> int:
        """Delivering slot-steps over the call's decode chunks."""
        return sum(self.state_steps_by_chunk)

    @property
    def state_dead_share(self) -> float:
        """Share of the decode chunks' slot-steps whose state a state
        operator that reads ``live`` left where it was: how often
        telling it engages (0 where the operator does not read it)."""
        every = self.state_slot_steps + self.state_steps_dead
        return self.state_steps_dead / every if every else 0.0

    @property
    def state_bytes_moved(self) -> int:
        """Bytes of state the decode chunks were asked to move: a
        delivering slot-step reads and writes ``state_bytes_slot``."""
        return 2 * self.state_slot_steps * self.state_bytes_slot

    @property
    def moe_latent_rows(self) -> int:
        """Latent rows dispatched to experts held here (the computed
        pairs: what an exchange between chips would carry); 0 where the
        experts work at the model's width."""
        if not self.moe_row_dim:
            return 0
        return (self.moe_pairs_held if self.moe_experts_held
                else self.moe_assignments)

    @property
    def attend_live_share(self) -> float:
        """Share of the old (slot, page) grid's steps that had a page
        to fetch: what the live-page walk is left with."""
        return (self.attend_pages_walked / self.attend_pages_grid
                if self.attend_pages_grid else 0.0)

    @property
    def attend_dead_share(self) -> float:
        """Share of the pages a walk that knew no dead slot fetched
        (every slot's, every step) that the dead slot-steps' were: how
        often telling the chunk ``left`` engages."""
        every = self.attend_pages_walked + self.attend_pages_dead
        return self.attend_pages_dead / every if every else 0.0

    @property
    def moe_live_expert_share(self) -> float:
        """Share of (expert, MoE layer, decode step) triples whose
        expert an owning slot routed to: how much of the expert weights
        a step has to read."""
        return (self.moe_experts_live
                / ((self.moe_experts_held or self.moe_experts)
                   * self.moe_layer_steps)
                if self.moe_layer_steps else 0.0)

    @property
    def moe_load_max_over_mean(self) -> float:
        """The fullest expert's pairs over the mean expert's, averaged
        over steps and MoE layers by pairs: a fact about the routing."""
        return (self.moe_load_max * self.moe_experts / self.moe_assignments
                if self.moe_assignments else 0.0)

    @property
    def step_utilization(self) -> float:
        """Share of decode slot-steps that delivered a token (an empty
        slot, and a slot whose request ended mid-chunk, deliver none; of
        a block family: share of the positions computed)."""
        return (self.decode_tokens / self.decode_slot_steps
                if self.decode_slot_steps else 0.0)


@dataclass
class RequestRejected:
    """Typed admission rejection: an oversized (or otherwise
    unservable) request degrades to this marker at its index in the
    ServedBatch instead of an assert killing the whole server. Check
    with ``isinstance(out[i], RequestRejected)``; ``reason`` is a
    stable token (``exceeds_max_len``, ``exceeds_model_ceiling``,
    ``exceeds_page_budget``), ``detail`` the human-readable arithmetic.
    Counted in ``ServingMetrics.rejections`` / ``rejection_reasons``."""

    rid: int
    reason: str
    detail: str = ""


def _admit(prompts, n_new, chunk, max_len, max_seq, page_budget=None,
           page_tokens=None) -> Dict[int, RequestRejected]:
    """The serving admission rule over a batch: a request needs
    ``len(prompt) + n + chunk`` cache positions (the chunk overrun is
    real — a slot finishing mid-chunk keeps writing until the boundary);
    the paged path adds the pool-budget bound (``page_budget`` in pages
    of ``page_tokens``). Returns the rejections by rid, and emits the
    journey's ``admit`` / ``reject`` / ``queue`` events."""
    rejected: Dict[int, RequestRejected] = {}
    for rid, (p, n) in enumerate(zip(prompts, n_new)):
        total = len(p) + n + chunk
        need = -(-total // page_tokens) if page_budget is not None else 0
        if total <= min(max_len, max_seq) and need <= (page_budget or 0):
            reqlog.emit("admit", rid, prompt_len=len(p), n_new=n)
            continue
        sums = f"len(prompt)={len(p)} + n_new={n} + chunk={chunk} = {total}"
        if total > max_len:
            rej = ("exceeds_max_len", f"{sums} > max_len={max_len}")
        elif total > max_seq:
            rej = ("exceeds_model_ceiling", f"{sums} > cfg.max_seq={max_seq}")
        else:
            rej = ("exceeds_page_budget",
                   f"ceil({total} / {page_tokens}) = {need} pages > "
                   f"pool n_pages={page_budget}")
        rejected[rid] = RequestRejected(rid, *rej)
        reqlog.emit("reject", rid, reason=rej[0])
    admitted = (rid for rid in range(len(prompts)) if rid not in rejected)
    for depth, rid in enumerate(admitted):
        reqlog.emit("queue", rid, depth=depth)
    return rejected


class ServedBatch(list):
    """serve_greedy/serve_sample result: a plain list of per-request
    ``prompt + generated`` arrays (full backward compatibility — index,
    iterate, len as before) carrying the batch telemetry as
    ``.metrics``."""

    def __init__(self, outputs, metrics: ServingMetrics):
        super().__init__(outputs)
        self.metrics = metrics


def _peer_dead(exc: BaseException) -> bool:
    """True iff ``exc`` is peer-loss shaped: the runtime's typed
    AcxPeerDeadError, anything carrying ``error == ERR_PEER_DEAD``
    (a multi-host collective that failed on a dead rank), or an error
    message naming the condition. Peer loss is an infrastructure event,
    not the request's fault — the scheduler requeues its victims without
    charging their retry budget (docs/DESIGN.md "Survivable links")."""
    try:
        from mpi_acx_tpu.runtime import ERR_PEER_DEAD, AcxPeerDeadError
    except Exception:  # pragma: no cover — runtime layer unavailable
        AcxPeerDeadError, ERR_PEER_DEAD = (), 20
    if isinstance(exc, AcxPeerDeadError):
        return True
    if getattr(exc, "error", None) == ERR_PEER_DEAD:
        return True
    msg = str(exc).lower()
    return "peer dead" in msg or "peer_dead" in msg


def _native_lib():
    """The native runtime's library if it is ALREADY loaded, else None.
    The diagnostics below never build or load it (the serving loop must
    keep making progress) and never raise."""
    try:
        import mpi_acx_tpu.runtime as _rt
        return _rt._lib
    except Exception:  # pragma: no cover — runtime layer unavailable
        return None


def _fleet_active() -> Optional[int]:
    """Best-effort count of ACTIVE rank slots in this process's fleet view
    (docs/DESIGN.md §12), or None when the native runtime isn't loaded.
    The serving loop polls this to notice capacity RETURNING: a
    replacement rank joining raises the count, and shed slots come back."""
    lib = _native_lib()
    if lib is None:
        return None
    try:
        out = (ctypes.c_uint64 * 5)()
        lib.acx_fleet_stats(out)
        return int(out[4])
    except Exception:  # pragma: no cover — diagnostics must never raise
        return None


def _flight_dump_best_effort() -> bool:
    """Write this rank's flight-recorder dump if the operator opted in
    ($ACX_FLIGHT names a prefix — same gate as the fatal-signal dump, so
    deliberate failure-path tests don't litter the cwd) and the native
    runtime is loaded. A failed step usually means a comm op wedged
    underneath XLA; the dump plus tools/acx_doctor.py turns 'the batch
    hung' into 'rank R never sent tag T'. Returns True iff a dump file
    was written."""
    lib = _native_lib() if os.environ.get("ACX_FLIGHT") else None
    if lib is None:
        return False
    try:
        return lib.acx_flight_dump(None) == 0
    except Exception:  # pragma: no cover — diagnostics must never raise
        return False


class RollingSLO:
    """Sliding-window serving SLOs for the live telemetry plane
    (docs/DESIGN.md §13): TTFT and inter-token-latency samples kept in a
    time-bounded window (default 30 s) plus point-in-time queue-depth and
    slot-occupancy gauges. ``live_slos()`` returns the rolling p50/p99 —
    the numbers an operator watching acx_top needs mid-run, as opposed to
    ServingMetrics' whole-batch aggregates computed at the end."""

    def __init__(self, window_s: float = 30.0):
        self.window_s = float(window_s)
        self._ttft: deque = deque()  # (monotonic t, seconds)
        self._itl: deque = deque()
        self.queue_depth = 0
        self.slot_occupancy = 0.0
        # Lifecycle counters for the live "app" fragment: cumulative over
        # the serve call (not windowed — a rejection burst 40 s ago still
        # matters to an operator triaging "why is goodput down"). acx_top
        # renders the per-reason breakdown from these, live, instead of
        # waiting for the end-of-batch ServingMetrics totals.
        self.rejects: Dict[str, int] = {}
        self.preemptions = 0
        self.resumes = 0

    def note_reject(self, reason: str) -> None:
        self.rejects[reason] = self.rejects.get(reason, 0) + 1

    def note_preempt(self) -> None:
        self.preemptions += 1

    def note_resume(self) -> None:
        self.resumes += 1

    def _trim(self, dq: deque, now: float) -> None:
        cutoff = now - self.window_s
        while dq and dq[0][0] < cutoff:
            dq.popleft()

    def note_ttft(self, seconds: float) -> None:
        now = time.monotonic()
        self._ttft.append((now, float(seconds)))
        self._trim(self._ttft, now)

    def note_itl(self, seconds: float) -> None:
        now = time.monotonic()
        self._itl.append((now, float(seconds)))
        self._trim(self._itl, now)

    def note_gauges(self, queue_depth: int, slot_occupancy: float) -> None:
        self.queue_depth = int(queue_depth)
        self.slot_occupancy = float(slot_occupancy)

    def live_slos(self) -> dict:
        """Rolling-window percentiles + live gauges, JSON-ready."""
        now = time.monotonic()
        self._trim(self._ttft, now)
        self._trim(self._itl, now)
        ttft = [v for _, v in self._ttft]
        itl = [v for _, v in self._itl]
        return {
            "window_s": self.window_s,
            "ttft_p50_s": _pct(ttft, 0.50),
            "ttft_p99_s": _pct(ttft, 0.99),
            "ttft_n": len(ttft),
            "itl_p50_s": _pct(itl, 0.50),
            "itl_p99_s": _pct(itl, 0.99),
            "itl_n": len(itl),
            "queue_depth": self.queue_depth,
            "slot_occupancy": self.slot_occupancy,
            "rejections": sum(self.rejects.values()),
            "rejects": dict(self.rejects),
            "preemptions": self.preemptions,
            "resumes": self.resumes,
        }


def _tseries_annotate_best_effort(slo: RollingSLO) -> bool:
    """Publish ``slo.live_slos()`` to the native telemetry sampler (it
    rides along under ``"app"`` in every subsequent ACX_TSERIES sample)
    — but only if ACX_TSERIES is set, the native runtime is loaded and
    its sampler runs: the fragment (``live_slos`` sorts both windows)
    is not built when nobody is sampling. Returns True iff a fragment
    was handed to the sampler."""
    lib = _native_lib() if os.environ.get("ACX_TSERIES") else None
    if lib is None:
        return False
    try:
        if not lib.acx_tseries_enabled():
            return False
        lib.acx_tseries_annotate(
            json.dumps(slo.live_slos(), separators=(",", ":")).encode())
        return True
    except Exception:  # pragma: no cover — diagnostics must never raise
        return False


def _span_app_begin_best_effort(request_id: int) -> bool:
    """Bracket-open for causal tracing (docs/DESIGN.md §14): ties every
    native op enqueued until the matching end-call to ``request_id``, so
    an offline acx_critpath.py run splits this request's TTFT into queue
    vs compute vs wire. Only if the native runtime is loaded and tracing
    is armed (ACX_TRACE). The id is offset by 1 — request ids start at 0
    and span id 0 means "unspanned" on the native side. Returns True iff
    the bracket was opened (the caller must then close it)."""
    lib = _native_lib() if os.environ.get("ACX_TRACE") else None
    if lib is None:
        return False
    try:
        lib.acx_span_app_begin(ctypes.c_uint64(request_id + 1))
        return True
    except Exception:  # pragma: no cover — diagnostics must never raise
        return False


def _span_app_end_best_effort() -> None:
    try:
        _native_lib().acx_span_app_end()
    except Exception:  # pragma: no cover — diagnostics must never raise
        pass


def _bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _padded(tokens, *caps) -> np.ndarray:
    """``tokens`` as a ``[1, bucket]`` row, right-padded with zeros to
    its power-of-two bucket (one prefill compile per bucket) capped at
    ``caps``: the cache length, so the scatter's update always fits the
    slot buffer, and the model's position ceiling (prefill asserts
    padded S <= max_seq)."""
    out = np.zeros((1, min(_bucket(len(tokens)), *caps)), np.int32)
    out[0, :len(tokens)] = tokens
    return out


def _per_request_n_new(prompts, n_new) -> List[int]:
    """``n_new`` as one int per request, with the checks every serve
    entry point makes of its requests."""
    assert prompts, "no requests"
    assert all(len(p) > 0 for p in prompts), \
        "zero-length prompt (prefill needs at least one token to attend)"
    n_new = ([int(n_new)] * len(prompts) if np.ndim(n_new) == 0
             else [int(n) for n in n_new])
    assert len(n_new) == len(prompts), (len(n_new), len(prompts))
    assert all(n >= 1 for n in n_new), \
        "n_new >= 1 per request (the prefill itself emits the first token)"
    return n_new


class RequestBook:
    """What every serve loop keeps per request and per slot, and the
    rules over it, each written once. The loops (``_serve``,
    ``serve_paged_greedy``, disagg's ``run_decode_worker``) own what is
    particular to their cache: where a first token comes from, where a
    slot's K/V lives, what a step is. The book owns ``queue`` (rids,
    arrival order), ``owner[b]`` (the rid in slot b; -1 idle, -2 shed:
    capacity retired after a peer loss, never refilled, skipped by
    every ``owner[b] >= 0`` loop), ``last_tok`` (the step's input), per
    rid ``emitted`` / ``done`` / ``attempts`` / ``ttft`` / ``finish``,
    the counters, gauge samples and RollingSLO, ``left()`` (what each
    slot's request still owes: the paged loops hand it to the chunk),
    and ``metrics()``.

    ``rids`` narrows the book to the requests this rank serves (a
    decode rank's share); ``rejected`` rows are never queued. All
    requests "arrive" when the book is made (``t0``), so queue wait
    counts toward TTFT and latency."""

    def __init__(self, prompts, n_new, n_slots, eos, chunk,
                 max_request_retries, rejected=None, rids=None,
                 on_token=None, block: int = 0):
        n = len(prompts)
        self.prompts = [np.asarray(p, np.int32) for p in prompts]
        self.n_new, self.n_slots, self.eos, self.chunk = (
            n_new, n_slots, eos, chunk)
        # A request whose prefill or step raised restarts from scratch
        # this many times before the error propagates (0 = fail fast).
        self.max_request_retries = max_request_retries
        self.on_token = on_token
        self.rejected: Dict[int, RequestRejected] = dict(rejected or {})
        self.rids = [rid for rid in (range(n) if rids is None else rids)
                     if rid not in self.rejected]
        self.queue = deque(self.rids)
        self.owner = [-1] * n_slots
        self.last_tok = np.zeros((n_slots,), np.int32)
        # A family that generates by diffusion over blocks of ``block``
        # positions (0: a token a step): the step's input is what each
        # slot's next block already holds, -1 where a position is masked
        # (a prompt's last ``P mod block`` tokens after a seat, else
        # nothing), and ``block_log`` keeps per rid (token, committing
        # step) of every position its blocks computed.
        self.block = block if block > 1 else 0
        if self.block:
            self.last_tok = np.full((n_slots, block), -1, np.int32)
        self.block_log: List[List[tuple]] = [[] for _ in range(n)]
        self.block_chunks: List[tuple] = []     # (delivered, kept) a chunk
        self.emitted: List[List[int]] = [[] for _ in range(n)]
        self.done: List[Optional[object]] = [None] * n
        self.attempts = [0] * n
        self.ttft: List[Optional[float]] = [None] * n
        self.finish: List[Optional[float]] = [None] * n
        self.steps = self.prefills = self.requeues = self.peer_requeues = 0
        self.slots_shed = self.slots_revived = self.hang_dumps = 0
        self.decode_tokens = self.decode_slot_steps = 0
        self.itl_samples: List[float] = []
        self.qd_samples: List[int] = []
        self.occ_samples: List[float] = []
        # Rolling-window SLOs for the live telemetry plane, fed
        # alongside the whole-batch lists (sample_gauges publishes).
        self.slo = RollingSLO()
        for rid, rej in self.rejected.items():
            self.done[rid] = rej
            self.slo.note_reject(rej.reason)
        # Fleet-elastic capacity (docs/DESIGN.md §12): rank slots
        # ACTIVE when last looked; None = no native runtime, dormant.
        self._fleet_active_seen = _fleet_active()
        self.t0 = time.perf_counter()

    def active(self) -> bool:
        return any(o >= 0 for o in self.owner)

    def free_slot(self) -> Optional[int]:
        """The lowest idle slot, or None."""
        return self.owner.index(-1) if -1 in self.owner else None

    def restart(self, rid):
        """Back on the queue for a bit-equal replay: the emitted tokens
        are discarded and the replayed attempt re-earns its first
        token."""
        self.emitted[rid] = []
        self.block_log[rid] = []
        self.ttft[rid] = None
        self.queue.append(rid)

    def requeue(self, rid, exc, charge=True):
        """Put a failed request back on the queue, or re-raise past the
        retry budget. ``charge=False`` (peer loss) requeues without
        spending the request's retry budget: losing a rank is not the
        request's fault, and a long recovery must not burn victims out
        of the server."""
        if charge:
            self.attempts[rid] += 1
            if self.attempts[rid] > self.max_request_retries:
                raise RuntimeError(
                    f"request {rid} failed {self.attempts[rid]} time(s), "
                    f"past max_request_retries={self.max_request_retries}"
                ) from exc
        else:
            self.peer_requeues += 1
        self.requeues += 1
        reqlog.emit("requeue", rid, charged=bool(charge))
        self.restart(rid)

    def step_failed(self, exc, shed=True):
        """After a failed step: snapshot the comm plane first (the
        flight dump captures the wedged op/link state as the failure
        left it), then requeue every active slot's request, in slot
        order. A peer-loss failure does NOT charge the victims and,
        with ``shed``, retires one slot: the job's capacity shrank with
        the lost rank. The caller rebuilds its cache."""
        lost_peer = _peer_dead(exc)
        if _flight_dump_best_effort():
            self.hang_dumps += 1
        for b in range(self.n_slots):
            if self.owner[b] >= 0:
                rid, self.owner[b] = self.owner[b], -1
                self.requeue(rid, exc, charge=not lost_peer)
        if lost_peer and shed:
            self.shed_slot()
        self.last_tok[:] = 0

    def shed_slot(self):
        """Retire the highest idle slot for good (owner -2): a lost
        rank shrank the job's capacity, so the batch shrinks with it
        instead of hammering the survivors at the old width. Always
        keeps at least one slot alive — a server with zero slots is
        just an outage."""
        alive = [b for b in range(self.n_slots) if self.owner[b] != -2]
        idle = [b for b in alive if self.owner[b] == -1]
        if len(alive) <= 1 or not idle:
            return
        self.owner[max(idle)] = -2
        self.slots_shed += 1

    def revive(self) -> List[int]:
        """Return shed slots to service when the fleet view shows
        capacity back (a replacement joined). Returns the revived slot
        indices so the caller rebalances queued requests onto exactly
        those. A drop in ACTIVE rank slots just lowers the watermark:
        the NEXT join, not the leave before it, triggers revival."""
        if self._fleet_active_seen is None:
            return []
        act = _fleet_active()
        if act is None:
            return []
        revived = []
        if act > self._fleet_active_seen:
            for b in range(self.n_slots):
                if self.owner[b] == -2:
                    self.owner[b] = -1
                    revived.append(b)
            self.slots_revived += len(revived)
        self._fleet_active_seen = act
        return revived

    def seat(self, b, rid, first):
        """Slot b now serves rid, whose prefill emitted ``first``. Of a
        block family no token comes of a prefill: ``first`` is then the
        prompt's tail (fewer than ``block`` tokens), which lies,
        committed, at the start of the slot's first block, and the
        request's first token arrives with a chunk (:meth:`deliver`)."""
        self.owner[b] = rid
        if self.block:
            self.last_tok[b] = -1
            self.last_tok[b, :len(first)] = first
            self.prefills += 1
            return
        self.emitted[rid].append(first)
        if self.on_token is not None:
            self.on_token(rid, first)
        self.last_tok[b] = first
        self.prefills += 1
        self.ttft[rid] = time.perf_counter() - self.t0
        self.slo.note_ttft(self.ttft[rid])
        reqlog.emit("stream", rid, n=1, ttft_s=self.ttft[rid])

    def left(self) -> np.ndarray:
        """``[n_slots]`` int32: the tokens each slot's request still
        owes (``n_new`` less what it has emitted), 0 for a slot that
        owns none: at a chunk's start, the steps of the chunk in which
        the slot can deliver a token. An ``eos`` may end a request
        sooner; it is then only not known to. Of a block family the
        POSITIONS it still owes: with them the prompt's tokens that its
        next block holds (a block is live while it starts below)."""
        owed = np.asarray([self.n_new[rid] - len(self.emitted[rid])
                           if rid >= 0 else 0 for rid in self.owner],
                          np.int32)
        if self.block:
            owed += np.where(owed > 0, (self.last_tok >= 0).sum(axis=1), 0)
        return owed

    def slot_finished(self, b) -> bool:
        """Slot b's request has its ``n_new`` tokens, or ended on
        ``eos``."""
        out = self.emitted[self.owner[b]]
        return (len(out) >= self.n_new[self.owner[b]]
                or (self.eos is not None and bool(out)
                    and out[-1] == self.eos))

    def deliver(self, block, step_dt, at=None):
        """Consume one step's ``[chunk, B]`` token block, which took
        ``step_dt`` (each of the chunk's tokens shares it evenly). A
        slot that finishes mid-chunk idles: its further tokens are
        valid continuations past the request's end, dropped.

        Of a block family the rows are POSITIONS, ``block`` to a block,
        and ``at`` [chunk, B] the denoising step that committed each:
        the first positions of a slot's first block after a seat hold
        its prompt's tail and are nobody's token, a request that ends
        inside a block leaves the rest of that block undelivered (both
        are logged, ``block_log``), and a request's first token, and
        with it its TTFT, arrives here."""
        self.steps += 1
        self.decode_slot_steps += block.shape[0] * self.n_slots
        reqlog.emit("decode_step", step=self.steps, dt_s=step_dt,
                    active=sum(o >= 0 for o in self.owner))
        itl = step_dt / self.chunk
        W, delivered, logged = self.block, 0, 0
        for b in range(self.n_slots):
            if W:
                held = int((self.last_tok[b] >= 0).sum())
                self.last_tok[b] = -1
            else:
                held = 0
                self.last_tok[b] = block[-1, b]
            rid = self.owner[b]
            if rid < 0:
                continue
            got = 0
            for c in range(block.shape[0]):
                over = self.slot_finished(b)
                if over and not (W and c % W):
                    break
                tok = int(block[c, b])
                if W:
                    self.block_log[rid].append((tok, int(at[c, b])))
                    logged += 1
                if over or c < held:
                    continue
                self.emitted[rid].append(tok)
                if self.on_token is not None:
                    self.on_token(rid, tok)
                if self.ttft[rid] is None:
                    self.ttft[rid] = time.perf_counter() - self.t0
                    self.slo.note_ttft(self.ttft[rid])
                    reqlog.emit("stream", rid, n=1, ttft_s=self.ttft[rid])
                self.itl_samples.append(itl)
                self.slo.note_itl(itl)
                got += 1
            if got:
                self.decode_tokens += got
                delivered += got
                reqlog.emit("stream", rid, n=got, itl_s=itl)
        if W:
            self.block_chunks.append((delivered, logged - delivered))

    def finish_request(self, b) -> int:
        """Slot b's request is done: its output is prompt + emitted and
        the slot is idle (the caller frees the slot's cache). Returns
        the rid."""
        rid = self.owner[b]
        self.done[rid] = np.concatenate(
            [self.prompts[rid], np.asarray(self.emitted[rid], np.int32)])
        self.finish[rid] = time.perf_counter() - self.t0
        reqlog.emit("finish", rid, new_tokens=len(self.emitted[rid]),
                    latency_s=self.finish[rid])
        self.owner[b] = -1
        return rid

    def sample_gauges(self):
        """Once a scheduler iteration: queue depth, slot occupancy (at
        a chunk's START), and the live SLO fragment for the sampler."""
        self.qd_samples.append(len(self.queue))
        self.occ_samples.append(
            sum(o >= 0 for o in self.owner) / self.n_slots)
        self.slo.note_gauges(self.qd_samples[-1], self.occ_samples[-1])
        _tseries_annotate_best_effort(self.slo)

    def metrics(self, cls=ServingMetrics, **own):
        """The batch's telemetry; ``own`` are the caller's fields (a
        rejected request never ran and has no row)."""
        assert all(self.done[rid] is not None for rid in self.rids)
        wall = time.perf_counter() - self.t0
        per_request = []
        for rid in self.rids:
            nt = len(self.emitted[rid])
            lat = self.finish[rid] if self.finish[rid] is not None else wall
            per_request.append(RequestTelemetry(
                rid=rid,
                ttft_s=self.ttft[rid] if self.ttft[rid] is not None else lat,
                latency_s=lat,
                new_tokens=nt,
                tokens_per_s=nt / lat if lat > 0 else 0.0,
                retries=self.attempts[rid],
                block_log=self.block_log[rid]))
        total_new = sum(r.new_tokens for r in per_request)
        qd, occ = self.qd_samples, self.occ_samples
        return cls(
            requests=len(self.rids) + len(self.rejected),
            wall_s=wall,
            new_tokens=total_new,
            tokens_per_s=total_new / wall if wall > 0 else 0.0,
            steps=self.steps,
            prefills=self.prefills,
            requeues=self.requeues,
            peer_requeues=self.peer_requeues,
            slots_shed=self.slots_shed,
            slots_revived=self.slots_revived,
            hang_dumps=self.hang_dumps,
            rejections=len(self.rejected),
            rejection_reasons=dict(Counter(
                rej.reason for rej in self.rejected.values())),
            ttft_p50_s=_pct([r.ttft_s for r in per_request], 0.50),
            ttft_p99_s=_pct([r.ttft_s for r in per_request], 0.99),
            itl_p50_s=_pct(self.itl_samples, 0.50),
            itl_p99_s=_pct(self.itl_samples, 0.99),
            queue_depth_max=max(qd) if qd else 0,
            queue_depth_mean=sum(qd) / len(qd) if qd else 0.0,
            slot_occupancy_mean=sum(occ) / len(occ) if occ else 1.0,
            per_request=per_request,
            decode_slot_steps=self.decode_slot_steps,
            decode_tokens=self.decode_tokens,
            **own)


def make_server_fns(params, cfg, family, chunk: int = 1,
                    kv_int8: bool = False, sample_cfg=None):
    """Compile-once closures for the serve loop: returns (prefill_fn,
    step_fn, scatter_fn, chunk, kv_int8, sample_cfg) — the trailing
    values let serve_greedy/serve_sample verify a reused tuple matches
    the call (chunk is baked into step_fn's scan length, so a tuple
    built for chunk=8 silently mis-serves a chunk=1 call otherwise).
    ``family`` is the model module (models.transformer, models.llama,
    or models.moe_transformer — anything exposing
    prefill/decode_step/init_kv_cache with the shared cache layout).

    ``chunk`` > 1 runs that many decode steps per host call as one
    jitted lax.scan returning the [chunk, B] token block — the
    scheduler then reacts every chunk tokens instead of every token,
    amortizing the host->device dispatch (the difference between a
    driver-bound and a device-bound server; its size on the chip is
    not measured yet). The tokens are bit-identical to stepwise decoding; the
    cost is scheduling granularity — a finished slot idles until the
    chunk boundary.

    ``sample_cfg`` = (temperature, top_k, top_p) switches the step from
    greedy argmax to stochastic sampling: the step then carries a [B]
    per-slot key array and each slot draws with ITS OWN key per step,
    split exactly as decoding.sample_generate splits its single key —
    that discipline is what makes serve_sample's outputs equal the solo
    sampled runs."""
    from mpi_acx_tpu.backend import jit_bound

    def prefill(params, tokens, last):
        """[1, S_bucket], traced last index -> (logits [1,1,vocab],
        cache). The unembedding runs on the real prompt's final row
        alone (``last_index``): the full-bucket [1, S, vocab] logits —
        ~1/3 of prefill FLOPs at GPT-2 vocab — are never computed.
        One compile per bucket length (jit's own shape cache)."""
        return family.prefill(params, cfg, tokens, tokens.shape[1],
                              kv_int8=kv_int8, last_index=last)

    prefill_fn = jit_bound(prefill, params)

    if sample_cfg is None:
        def pick(logits, keys):      # greedy: keys unused, pass-through
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
    else:
        from mpi_acx_tpu.models.decoding import sample_logits
        temperature, top_k, top_p = sample_cfg

        def pick(logits, keys):
            # Mirror sample_generate: key, sub = split(key); draw with
            # sub — per slot, so slot b's stream equals the solo run's.
            splits = jax.vmap(jax.random.split)(keys)
            keys, subs = splits[:, 0], splits[:, 1]
            tok = jax.vmap(
                lambda lg, k: sample_logits(lg[None].astype(jnp.float32),
                                            k, temperature, top_k,
                                            top_p)[0])(logits, subs)
            return tok.astype(jnp.int32), keys

    # Donated carries: the loop always proceeds with the returned
    # cache, so XLA may update the slot buffers in place (on CPU the
    # donation is ignored, harmlessly).
    def step(params, cache, tok, keys):
        def one(carry, _):
            cache, tok, keys = carry
            logits, cache = family.decode_step(params, cfg, cache, tok)
            nxt, keys = pick(logits, keys)
            return (cache, nxt, keys), nxt
        (cache, _, keys), toks = lax.scan(one, (cache, tok, keys), None,
                                          length=chunk)
        return cache, toks, keys                     # toks [chunk, B]

    step_fn = jit_bound(step, params, donate_argnums=(1,))

    @partial(jax.jit, donate_argnums=(0,))
    def scatter_fn(slots, one, slot_idx, new_pos):
        """Land a freshly prefilled single-request cache (``one``, B=1,
        bucket-length max_len) into slot ``slot_idx`` of the slot
        cache; rows past the bucket keep the slot's old contents (never
        attended: they lie beyond ``new_pos`` until decode overwrites
        them). Int8 slot caches carry their scale buffers ('ks'/'vs')
        through the same per-key scatter."""
        for key in [k for k in ("k", "v", "ks", "vs") if k in slots]:
            src = one[key][:, 0]                    # [L, H, *, S_bucket]
            dst = lax.dynamic_index_in_dim(
                slots[key], slot_idx, 1, keepdims=False)  # [L, H, *, max_len]
            dst = lax.dynamic_update_slice(
                dst, src, (0, 0, 0, 0))
            slots[key] = lax.dynamic_update_index_in_dim(
                slots[key], dst, slot_idx, 1)
        slots["pos"] = slots["pos"].at[slot_idx].set(new_pos)
        return slots

    # chunk/kv_int8/sample_cfg ride along so the serve entry points can
    # reject a mismatched reuse (e.g. int8 slots + bf16-prefill
    # closures, a step scanning a different chunk length, or a step
    # jitted with different sampling params, fail deep in a trace — or
    # worse, silently — otherwise).
    return prefill_fn, step_fn, scatter_fn, chunk, kv_int8, sample_cfg


def _local_prefill(sample_cfg=None, key=None):
    """The prefill serve_greedy / serve_sample hand to ``_serve``: the
    prompt pass on this device, then the first token by argmax, or drawn
    on the host from request rid's own key stream ``fold_in(key, rid)``,
    split exactly as decoding.sample_generate splits."""
    from mpi_acx_tpu.models.decoding import sample_logits

    def prefill(prefill_fn, rid, padded, S):
        logits, one = prefill_fn(jnp.asarray(padded), S - 1)
        rkey = None
        if sample_cfg is None:
            first = int(jnp.argmax(logits[0, 0]))
        else:
            rkey, sub = jax.random.split(jax.random.fold_in(key, rid))
            first = int(sample_logits(
                logits[0, 0][None].astype(jnp.float32), sub,
                *sample_cfg)[0])
        reqlog.emit("prefill_end", rid, first_token=first)
        return first, one, rkey
    return prefill


def _serve(params, cfg, prompts, n_new, n_slots, max_len, family, eos,
           chunk, server_fns, kv_int8, sample_cfg, key, prefill,
           max_request_retries=2, shed_on_peer_loss=True):
    """The fixed-slot loop, written once: seed the slots, step, deliver
    the ``[chunk, B]`` block, retire and refill at the chunk boundary.
    The requests and their rules are the RequestBook's.

    Where a queued request's first token and one-request cache come
    from is the loop's INPUT: ``prefill(prefill_fn, rid, padded, S) ->
    (first, one, rkey)`` (``prefill_fn`` is the ``server_fns`` tuple's,
    ``rkey`` the slot's new sampling key or None; it emits the
    ``prefill_end`` event itself). serve_greedy / serve_sample pass
    ``_local_prefill``; disagg's loopback passes its wire handoff. The
    loop scatters ``one`` into the slot and seats the request, and
    requeues it when either raises. Sampling changes only how the step
    picks tokens (make_server_fns ``sample_cfg``) and that first draw.

    Degrades gracefully under step/prefill failure (the serving face of
    the runtime's retry plane): a request whose device step raised is
    re-queued from scratch — emitted tokens discarded, so the restart
    replays the same greedy/sampled path bit for bit — up to
    ``max_request_retries`` times before the failure is re-raised with
    the request id attached. A peer-loss failure is not charged and
    sheds one slot, unless the caller has no peer whose loss shrinks it
    (``shed_on_peer_loss=False``: the loopback)."""
    if family is None:
        from mpi_acx_tpu.models import transformer as family  # noqa: N813
    n_new = _per_request_n_new(prompts, n_new)
    rejected = _admit(prompts, n_new, chunk, max_len, cfg.max_seq)

    if server_fns is None:
        server_fns = make_server_fns(params, cfg, family, chunk=chunk,
                                     kv_int8=kv_int8,
                                     sample_cfg=sample_cfg)
    (prefill_fn, step_fn, scatter_fn, fns_chunk, fns_int8,
     fns_sample) = server_fns
    assert fns_chunk == chunk, \
        (f"server_fns built for chunk={fns_chunk}, this call uses "
         f"chunk={chunk} (the scan length is baked into step_fn)")
    assert fns_int8 == kv_int8, \
        "server_fns built with a different kv_int8 than this call"
    assert fns_sample == sample_cfg, \
        ("server_fns built for different sampling settings "
         f"({fns_sample} vs {sample_cfg})")

    def fresh_cache():
        """Zeroed slot cache and per-slot key streams (greedy: dummies
        the step passes through)."""
        slots = family.init_kv_cache(cfg, n_slots, max_len, kv_int8=kv_int8)
        slots["pos"] = jnp.zeros((n_slots,), jnp.int32)
        return slots, jax.random.split(
            key if key is not None else jax.random.key(0), n_slots)

    slots, keys = fresh_cache()
    book = RequestBook(prompts, n_new, n_slots, eos, chunk,
                       max_request_retries, rejected)

    def refill(b):
        """Returns True iff slot b now owns a request; a failed prefill
        re-queues the request instead of killing the server."""
        nonlocal slots, keys
        rid = book.queue.popleft()
        prompt = book.prompts[rid]
        S = len(prompt)
        padded = _padded(prompt, max_len, cfg.max_seq)
        # Causal-tracing bracket: any native op the prefill triggers
        # (multihost sharded serving pushes activations through MPIX
        # enqueues, the loopback its handoff) is span-tagged with this
        # request's id, so the request's TTFT decomposes offline
        # (acx_critpath.py).
        spanned = _span_app_begin_best_effort(rid)
        reqlog.emit("prefill_start", rid, prompt_len=S, bucket=padded.shape[1])
        try:
            first, one, rkey = prefill(prefill_fn, rid, padded, S)
            if rkey is not None:
                keys = keys.at[b].set(rkey)
            slots = scatter_fn(slots, one, b, S)
        except Exception as exc:  # noqa: BLE001 — any device failure
            book.requeue(rid, exc, charge=not _peer_dead(exc))
            return False
        finally:
            if spanned:
                _span_app_end_best_effort()
        reqlog.emit("seat", rid, slot=b, pos=S)
        book.seat(b, rid, first)
        return True

    def retire_finished(b):
        """Retire slot b's request if it has ended. The freed slot is
        parked at pos 0: an idle slot keeps stepping in the batch, and
        a stale pos walks toward max_len where the decode write would
        land out of bounds on a long-idle slot."""
        nonlocal slots
        if book.owner[b] < 0 or not book.slot_finished(b):
            return False
        book.finish_request(b)
        slots["pos"] = slots["pos"].at[b].set(0)
        return True

    def seed():
        """Fill every idle slot from the queue, retiring 1-token
        requests on the spot so a slot never enters the decode loop
        already finished."""
        while book.queue and (b := book.free_slot()) is not None:
            if refill(b):
                retire_finished(b)

    book.qd_samples.append(len(book.queue))
    seed()
    while book.active() or book.queue:
        book.sample_gauges()
        if book.queue:
            # Capacity may have returned (a replacement rank joined):
            # revive shed slots and rebalance the backlog onto them.
            for b in book.revive():
                if book.queue and refill(b):
                    retire_finished(b)
        if not book.active():
            # All slots idle with requests still queued: only reachable
            # after a failure re-queued them — reseed and keep serving.
            seed()
            continue
        step_t0 = time.perf_counter()
        try:
            slots, toks, keys = step_fn(slots, jnp.asarray(book.last_tok),
                                        keys)
        except Exception as exc:  # noqa: BLE001 — any device failure
            # step_fn donates the slot cache, so after a failed dispatch
            # its buffers cannot be trusted: the book re-queues every
            # active request (the queued-but-unstarted ones are
            # unaffected), the cache is rebuilt, and the loop goes on.
            book.step_failed(exc, shed=shed_on_peer_loss)
            slots, keys = fresh_cache()
            continue
        block = np.asarray(toks, np.int32)           # [chunk, B]
        # np.asarray forced the device sync, so this dt covers the real
        # device step; each of the chunk tokens shares it evenly — the
        # per-token cadence a streaming client would see.
        book.deliver(block, time.perf_counter() - step_t0)
        # Retire/refill happens only at chunk boundaries, the
        # granularity ``chunk`` buys.
        for b in range(n_slots):
            while retire_finished(b):
                if book.queue:
                    refill(b)

    return ServedBatch(book.done, book.metrics())


def serve_greedy(params, cfg, prompts: Sequence[np.ndarray], n_new,
                 n_slots: int, max_len: int, family=None,
                 eos: Optional[int] = None, chunk: int = 1,
                 server_fns=None,
                 kv_int8: bool = False,
                 max_request_retries: int = 2) -> ServedBatch:
    """Serve ``prompts`` (1-D int arrays, any lengths) through
    ``n_slots`` continuously-batched cache slots; each request decodes
    greedily for ``n_new`` tokens (an int, or one per request — the
    mixed-output-length workload is where continuous batching beats a
    static batch) or until ``eos``. Returns, per request, ``prompt +
    generated`` — bit-equal to that request's solo ``family.generate``
    run (per-slot positions, see module docstring). ``chunk`` trades
    scheduling granularity for host-dispatch amortization (see
    make_server_fns); outputs are identical for any chunk. Pass
    ``server_fns`` (a make_server_fns result for the same
    params/cfg/family/chunk/kv_int8 — the flags are checked) to reuse
    compiled programs across calls — a fresh call otherwise rebuilds
    its jit closures and re-traces.
    ``kv_int8`` serves from int8 slot caches (ops/kvquant.py) — the
    long-context regime where the cache stream dominates; outputs then
    equal the solo ``generate(..., kv_int8=True)`` runs bit for bit
    (same codes, same scales, same scale-on-scores read).
    ``max_request_retries`` bounds per-request restarts after a failed
    prefill/step (see _serve) — a transient device fault costs the
    failed requests a replay, not the server.

    The returned list is a ``ServedBatch``: a plain list of outputs
    carrying batch telemetry as ``.metrics`` (per-request TTFT and
    tokens/sec, inter-token latency percentiles, queue depth, slot
    occupancy, requeue counts — see ServingMetrics).
    """
    return _serve(params, cfg, prompts, n_new, n_slots, max_len, family,
                  eos, chunk, server_fns, kv_int8, None, None,
                  _local_prefill(),
                  max_request_retries=max_request_retries)


def serve_sample(params, cfg, prompts: Sequence[np.ndarray], n_new,
                 n_slots: int, max_len: int, key, family=None,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos: Optional[int] = None, chunk: int = 1,
                 server_fns=None,
                 kv_int8: bool = False,
                 max_request_retries: int = 2) -> ServedBatch:
    """Stochastic continuous batching (temperature / top-k / top-p).

    Request ``rid`` draws from its own key stream
    ``jax.random.fold_in(key, rid)`` with exactly
    decoding.sample_generate's split discipline, so each output equals
    the solo ``family.generate_sample(prompt, n,
    key=jax.random.fold_in(key, rid), ...)`` run bit for bit — the
    scheduler (slot assignment, refill order, chunking) cannot perturb
    any request's sample path. All other parameters (and the
    ``ServedBatch``/telemetry return) as serve_greedy.
    """
    sample_cfg = (temperature, top_k, top_p)
    return _serve(params, cfg, prompts, n_new, n_slots, max_len, family,
                  eos, chunk, server_fns, kv_int8, sample_cfg, key,
                  _local_prefill(sample_cfg, key),
                  max_request_retries=max_request_retries)


def _slo_admit_targets(slo_admit) -> tuple:
    """Resolve the SLO admission gate: ``slo_admit`` is (ttft_s, itl_s)
    rolling-p50 targets (either may be None), or None to read the
    ``ACX_SERVE_ADMIT_TTFT_MS`` / ``ACX_SERVE_ADMIT_ITL_MS`` knobs
    (unset/0 = gate off — the default, which keeps paged schedules
    identical to the fixed-slot path's)."""
    if slo_admit is not None:
        ttft_t, itl_t = slo_admit
        return (float(ttft_t) if ttft_t else None,
                float(itl_t) if itl_t else None)
    ttft_ms = float(os.environ.get("ACX_SERVE_ADMIT_TTFT_MS", "0") or 0)
    itl_ms = float(os.environ.get("ACX_SERVE_ADMIT_ITL_MS", "0") or 0)
    return (ttft_ms / 1e3 if ttft_ms > 0 else None,
            itl_ms / 1e3 if itl_ms > 0 else None)


# serve_paged_greedy's two prefill programs, jitted once a process (see
# kvpage.paged_decode_chunk, and its note on ``on_tpu``). Named, so the
# trace prints ``PjitFunction(paged_prefill)``.


# What they ask of the family they ask through its kvpage.PagedSpec:
# pages for the layers that keep pages and, for the layers that keep a
# fixed state, each whole page's tail and the state at the prompt's end.


@partial(jax.jit, static_argnames=("cfg", "family", "kv_int8", "on_tpu",
                                   "page_tokens"))
def paged_prefill(params, tokens, last_index, *, cfg, family, kv_int8,
                  on_tpu, page_tokens=None):
    kvpage.note_trace()
    return kvpage.prefill(params, cfg, kvpage.paged_spec(family, cfg), tokens,
                          last_index, kv_int8, page_tokens)


@partial(jax.jit, static_argnames=("cfg", "family", "kv_int8", "on_tpu",
                                   "page_tokens"))
def paged_suffix_prefill(params, suffix, hk, hv, tail, last_index, *, cfg,
                         kv_int8, on_tpu, family=None, page_tokens=None):
    kvpage.note_trace()
    return kvpage.prefill(params, cfg, kvpage.paged_spec(family, cfg), suffix,
                          last_index, kv_int8, page_tokens,
                          history=(hk, hv, tail))


# A waiting span that took more than this many times its group's median
# is a stall. Twice: what the record is to show is several times its
# span (a machine's ~110 ms stop against a prefill of 11-26 ms, 1.5 s
# lost against a chunk of 0.3-0.5 s, a program loaded where none should
# be), and spans of one group (a bucket's prefills, a call's chunks)
# differ by tens of percent. A constant, not a knob: two runs' stalls
# compare only under one rule.
STALL_FACTOR = 2.0


def stalled_spans(spans) -> List[tuple]:
    """``[(span, its group's median seconds)]`` for every waiting span
    of a ``serve_paged_greedy`` record that took more than STALL_FACTOR
    times the median of its group: the ``refill.prefill`` spans by
    ``(bucket, hit_pages)``, the call's first left out (it waits for the
    pool's zero fill, which the device runs first), and the
    ``chunk.step`` spans as one group."""
    groups: Dict[object, list] = {}
    first = True
    for sp in spans:
        if sp.name == "refill.prefill":
            if first:
                first = False
                continue
            key = (sp.ids.get("bucket"), sp.ids.get("hit_pages"))
        elif sp.name == "chunk.step":
            key = sp.name
        else:
            continue
        groups.setdefault(key, []).append(sp)
    out = []
    for group in groups.values():
        mid = _pct([sp.seconds for sp in group], 0.50)
        out += [(sp, mid) for sp in group
                if sp.seconds > STALL_FACTOR * mid]
    return sorted(out, key=lambda pair: pair[0].index)


def _request_paths(spans, per_request, t_entry) -> None:
    """Fill each RequestTelemetry's span-derived fields from the call's
    record. A request's path is the four ``refill.*`` spans of the
    refill that seated it (its LAST, where a preemption or a failure
    replayed it) and every chunk after that whose ``chunk.step`` lists
    it among the slots' owners; see RequestTelemetry for the fields."""
    seated, last = {}, {}           # rid -> {name: span} of a refill
    refills, chunks = [], []        # spans; [upload, step, deliver | None]
    for sp in spans:
        if sp.name.startswith("refill."):
            refills.append(sp)
            mine = last.setdefault(sp.ids["rid"], {})
            mine[sp.name] = sp
            if sp.name == "refill.seat":
                seated[sp.ids["rid"]] = dict(mine)
        elif sp.name == "chunk.upload":
            chunks.append([sp, None, None])
        elif sp.name == "chunk.step":
            chunks[-1][1] = sp
        elif sp.name == "chunk.deliver":
            chunks[-1][2] = sp
    owned: Dict[int, list] = {}
    for chunk in chunks:
        if chunk[2] is not None:    # a failed step delivered nothing
            for rid in chunk[1].ids.get("rid", ()):
                if rid >= 0:
                    owned.setdefault(rid, []).append(chunk)
    # The refill spans never overlap one another (one thread, none opened
    # inside another): seconds under them before t, by prefix sums.
    starts = [sp.t0 for sp in refills]
    before = [0.0]
    for sp in refills:
        before.append(before[-1] + sp.seconds)

    def under_refills(t):
        i = bisect_right(starts, t)
        return before[i] - (max(refills[i - 1].t1 - t, 0.0) if i else 0.0)

    for r in per_request:
        mine = seated.get(r.rid)
        if mine is None:
            continue
        match, pre, seat = (mine["refill.match"], mine["refill.prefill"],
                            mine["refill.seat"])
        r.queue_wait_s = match.t0 - t_entry
        r.prefill_s = pre.seconds
        r.refill_host_s = (match.seconds + mine["refill.scatter"].seconds
                           + seat.seconds)
        r.prefill_wait_s = pre.t1 - pre.handed
        took = [c for c in owned.get(r.rid, ()) if c[0].t0 >= seat.t1]
        r.chunks = len(took)
        if took:
            # Its own refill ended before ``seat.t1`` and it is never
            # refilled again: every refill span in between is another's.
            end = took[-1][2].t1
            r.decode_s = end - seat.t1
            r.decode_in_refill_s = under_refills(end) - under_refills(seat.t1)
            r.decode_in_chunk_s = sum(c[0].seconds + c[1].seconds
                                      for c in took)


def serve_paged_greedy(params, cfg, prompts: Sequence[np.ndarray], n_new,
                       n_slots: int, max_len: int, family=None,
                       eos: Optional[int] = None, chunk: int = 1,
                       kv_int8: bool = False,
                       page_tokens: Optional[int] = None,
                       n_pages: Optional[int] = None,
                       prefix_cache: bool = False,
                       slo_admit=None,
                       on_token=None,
                       max_request_retries: int = 2,
                       return_paged_state: bool = False,
                       n_snapshots: Optional[int] = None) -> ServedBatch:
    """Greedy continuous batching over a PAGED KV cache
    (models/kvpage.py): slots share a pool of ``page_tokens``-sized
    pages through per-slot block tables, so HBM-resident KV bytes
    scale with LIVE tokens, not ``n_slots * max_len``. On identical
    schedules (the defaults: no prefix cache, no SLO gate, enough
    pages) outputs are BIT-EQUAL to the fixed-slot ``serve_greedy`` —
    the paged attend gathers each slot's pages into the exact
    ``[B, max_len]`` layout the dense reference attends, and the paged
    decode step is the fixed step with table-routed writes (tested in
    tests/test_paged.py for bf16 and int8 caches alike).

    The loop is this function's own (seat, grow, preempt, step and the
    spans below); the requests, and the requeue / shed / revive /
    deliver / finish rules over them, are the RequestBook's, as in the
    fixed-slot loop. Beyond the fixed path it adds:

    * **Typed admission** — a request that cannot fit ``max_len``,
      ``cfg.max_seq``, or the page budget degrades to a
      :class:`RequestRejected` at its output index (and a
      ``rejections`` count in metrics) instead of an assert.
    * **Lazy page growth + preemption** — a slot owns only the pages
      its live tokens need; growth happens at chunk boundaries, and
      when the pool runs dry the LOWEST-priority request (highest rid
      = latest arrival) is preempted: its pages are freed and it
      requeues UNCHARGED (the PR 3 peer-loss rule — pressure is the
      server's fault, not the request's), replaying bit-equal when
      reseated.
    * **Radix prefix sharing** (``prefix_cache=True``) — full-page
      prompt prefixes are cached in a refcounted radix trie; a hit
      seats the shared pages (stored ONCE, never rewritten) and
      prefills only the suffix. Hit-path prefills use different tensor
      shapes than cold ones, so a hit's outputs are deterministic but
      not bitwise-pinned to the cold path (docs/DESIGN.md §19) — which
      is why the feature is opt-in.
    * **SLO-aware batch formation** — ``slo_admit=(ttft_s, itl_s)``
      (or the ``ACX_SERVE_ADMIT_*_MS`` knobs) defers REFILLS while the
      RollingSLO window's p50 violates a target and at least one
      request is in flight: trading queue wait (cheap, visible) for
      inter-token latency (the SLO a streaming client feels).
    * **Streaming output** — ``on_token(rid, token)`` fires for every
      token as it is consumed, first (prefill) token included.
      At-least-once semantics: a preempted or requeued request replays
      its stream from the start when re-served.

    ``page_tokens`` defaults to $ACX_KV_PAGE_TOKENS (128 — the
    flash-decode block granularity) stepped down to divide ``max_len``;
    ``n_pages`` defaults to ``n_slots * max_len / page_tokens``
    (capacity parity with the fixed-slot cache); ``n_snapshots``, for a
    family with state layers, is the size of its snapshot store
    (``kvpage.SnapshotStore``; default: a row for every page the
    family's rule gives one, so that none is evicted). The returned
    ServedBatch carries the paged counters (preemptions, prefix_hits,
    prefix_evictions, prefix_pages_reused, pages_hwm) in ``.metrics``;
    ``return_paged_state=True`` additionally exposes the live
    :class:`~mpi_acx_tpu.models.kvpage.PagedKV` as ``.paged_state``
    (tests and benches inspect allocator occupancy through it).

    **Two clocks, both ``time.perf_counter``.** ``wall_s``, ``ttft_s``,
    ``latency_s`` and the ``itl_*`` count from ``t0``, taken AFTER the
    set-up (pool allocated, programs wrapped), as they always have.
    ``call_s``, ``queue_wait_s`` and the phases count from function
    ENTRY, so ``call_s = wall_s +`` set-up and a caller's own TTFT is
    ``ttft_s + phase_s["serve.setup"]``.

    **Phases** (``profiling.Phases``): the call is tiled by the spans
    below, each a ``jax.profiler.TraceAnnotation`` (on the device
    trace's clock when the profiler runs; ``refill.*`` carry ``rid``,
    ``chunk.*`` and ``loop.other`` the chunk's number ``step``), a
    self-time counter in ``metrics.phase_s`` / ``phase_n`` (the self
    times sum to ``call_s``) and a record in ``metrics.spans``. Only two
    WAIT on the device, and each marks the moment it hands the device
    its program (``handed``: after the pad, the upload of the arguments
    and a hit's history gather)::

        serve.setup     entry -> first refill: admission, PagedKV (the
                        pool's allocation), the weights bound to the
                        process's programs (nothing is traced here)
        refill.match    SLO gate, prefix.match, alloc_evicting
        refill.prefill  pad, gather_history on a hit, the prefill's
                        dispatch, int(argmax): WAITS for the device;
                        the process's first use of a shape traces and
                        loads its program here (programs_traced)
        refill.scatter  scatter_prompt's DISPATCH (pages, and the
                        snapshots of a state family with them); its
                        device time lands in whichever span syncs next
                        (the next refill.prefill or chunk.step)
        refill.seat     pkv.seat (a state family's end state written to
                        the slot), prefix.insert, bookkeeping, the first
                        on_token
        chunk.grow      grow_for_chunk (preemptions inside) + COW guard
        chunk.upload    book.left(), pkv.device_state(left): the table,
                        ``pos`` and what each slot still owes go up
        chunk.step      step_fn dispatch, absorb, np.asarray(tokens):
                        WAITS for the device
        chunk.deliver   the token loop and its on_token calls
        chunk.retire    one per retired request: output, release
        loop.other      gauges, page stats, tseries fragment, fleet
                        checks, and the metrics at the end

    ``decode_slot_steps`` / ``decode_tokens`` / ``step_utilization``
    count, where the tokens are consumed, how many of the chunks'
    slot-steps delivered one.

    **A family that generates by diffusion over blocks**
    (``kvpage.PagedSpec.block`` = W > 1) runs through the same loop, book,
    pools, prefix cache and spans. What differs, all read from its spec:
    ``chunk`` is a multiple of W (a program call runs ``chunk / W``
    blocks a slot); a refill prefills the prompt's WHOLE BLOCKS (no head,
    no token: ``refill.prefill`` waits for the pages) and seats its last
    ``P mod W`` tokens in the slot's first block; a request's first token,
    and its TTFT, arrive with a chunk; ``left`` counts positions; a
    slot-step of the counters is a position (``ServingMetrics``:
    ``forwards_denoise`` / ``forwards_store``, ``block_positions_kept`` /
    ``_dead``, ``block_by_chunk``; a ``chunk.step`` record has
    ``blocks``; ``RequestTelemetry.block_log``).

    **The record** (``metrics.spans``, always kept: about five spans a
    request and six a chunk). Beyond the annotation's ids the record of
    a ``refill.prefill`` has ``bucket`` (the padded length it ran at)
    and ``hit_pages``, that of a ``chunk.step`` ``rid``: the owner of
    each slot at the chunk's start. From it, once, at the call's end:
    per request ``queue_wait_s`` (entry -> start of the refill that
    seated it), ``prefill_s``, ``refill_host_s``, ``prefill_wait_s``,
    ``decode_s`` and what of it lay under other requests' refills and
    under its own chunks (RequestTelemetry); per call ``stalls`` /
    ``stall_s`` (:func:`stalled_spans`). The programs JAX traced,
    lowered or loaded inside a span are in its ``programs``
    (``profiling.program_log``)."""
    from mpi_acx_tpu.ops.flash_decode import (select_paged_decode_attend,
                                              select_paged_kv_write)
    from mpi_acx_tpu.profiling import Phases

    ph = Phases()
    setup = ph("serve.setup")
    setup.__enter__()               # closed before the first refill
    traced_at_entry = kvpage.programs_traced()
    if family is None:
        from mpi_acx_tpu.models import transformer as family  # noqa: N813
    n_new = _per_request_n_new(prompts, n_new)

    pt = page_tokens or kvpage.default_page_tokens(max_len)
    assert max_len % pt == 0, \
        f"page_tokens={pt} must divide max_len={max_len}"
    max_pages = max_len // pt
    if n_pages is None:
        n_pages = n_slots * max_pages
    ttft_target, itl_target = _slo_admit_targets(slo_admit)

    rejected = _admit(prompts, n_new, chunk, max_len, cfg.max_seq,
                      page_budget=n_pages, page_tokens=pt)

    pkv = kvpage.PagedKV(cfg, family, n_slots, max_len, pt, n_pages,
                         kv_int8=kv_int8, prefix_cache=prefix_cache,
                         n_snapshots=n_snapshots)

    # One compile per (bucket) / (suffix bucket, history length) a
    # PROCESS: the module-level programs, in jit's own cache. The
    # weights are arguments; what a trace reads from the process is in
    # the static key.
    on_tpu = backend.on_tpu()
    # (the page size is in the prefills' key only where they cut tails
    # at page ends)
    tail_pt = pt if pkv.spec.n_state_layers else None
    prefill_fn = partial(paged_prefill, params, cfg=cfg, family=family,
                         kv_int8=kv_int8, on_tpu=on_tpu, page_tokens=tail_pt)
    suffix_prefill_fn = partial(paged_suffix_prefill, params, cfg=cfg,
                                family=family, kv_int8=kv_int8,
                                on_tpu=on_tpu, page_tokens=tail_pt)

    step_fn = kvpage.make_paged_step_fn(params, cfg, family, chunk, pt)
    # A family that generates by diffusion over blocks (PagedSpec.block;
    # 0: a token a slot a step): ``chunk`` stays the tokens a slot a
    # program call, a whole number of blocks.
    W = pkv.spec.block if pkv.spec.block > 1 else 0
    assert not W or chunk % W == 0, \
        f"chunk={chunk} must be a multiple of the family's block of {W}"

    keys = jax.random.split(jax.random.key(0), n_slots)  # greedy dummies
    # The requests and their rules (module docstring of RequestBook);
    # its clock ``t0`` starts here, after the set-up.
    book = RequestBook(prompts, n_new, n_slots, eos, chunk,
                       max_request_retries, rejected, on_token=on_token,
                       block=W)
    queue, owner, slo = book.queue, book.owner, book.slo
    n_preempts = n_slo_defer = pages_walked = pages_dead = pages_grid = 0
    rewritten = staged = snapshot_seats = 0
    state_steps: List[int] = []     # delivering slot-steps, a chunk
    walked_by_chunk: List[int] = []
    # Requests currently evicted by page pressure: membership here turns
    # the next successful seat into a journey "resume" event.
    preempted_rids: set = set()

    def _slo_defers() -> bool:
        """SLO-aware batch formation: with a target set, a violating
        rolling window defers refills while work is in flight —
        admitting another prompt would push the ITL every live stream
        sees further past target for queue wait nobody measures."""
        if ttft_target is None and itl_target is None:
            return False
        if not book.active():
            return False            # an empty server always admits
        live = slo.live_slos()
        if (itl_target is not None and live["itl_n"]
                and live["itl_p50_s"] > itl_target):
            return True
        return (ttft_target is not None and live["ttft_n"]
                and live["ttft_p50_s"] > ttft_target)

    def refill(b):
        """Seat the queue head in slot b. Returns True iff the slot
        now owns a request; False covers three distinct paths: the SLO
        gate deferred (request left at the queue head), the pool could
        not cover the prompt (ditto — a retire will free pages), or
        the prefill failed (request re-queued via the retry rules)."""
        nonlocal n_slo_defer, snapshot_seats
        rid = queue[0]
        prompt = book.prompts[rid]
        with ph("refill.match", rid=rid):
            if _slo_defers():
                n_slo_defer += 1
                return False
            queue.popleft()
            S = len(prompt)
            # What a prefill stores: the prompt, or of a block family
            # its whole blocks (the rest goes into the first block
            # generated, whose K/V depend on what is generated there)
            body = S - S % W if W else S
            hit_pages = (pkv.prefix.match(prompt)
                         if pkv.prefix is not None else [])
            if hit_pages:
                reqlog.emit("prefix_hit", rid, pages=len(hit_pages))
            n_fresh = kvpage.pages_needed(S, pt) - len(hit_pages)
            fresh = pkv.alloc_evicting(n_fresh)
            if fresh is None:
                # Page pressure at admission: put the request BACK at
                # the head (arrival order preserved) and release the
                # trie refs the failed match took; a later retire frees
                # pages.
                for p in hit_pages:
                    pkv.alloc.decref(p)
                queue.appendleft(rid)
                return False
        spanned = False
        try:
            with ph("refill.prefill", rid=rid) as pre:
                spanned = _span_app_begin_best_effort(rid)
                reqlog.emit("prefill_start", rid, prompt_len=S,
                            hit_pages=len(hit_pages),
                            fresh_pages=len(fresh))
                if hit_pages:
                    # Radix hit: prefill ONLY the suffix against the
                    # cached pages' gathered history (with state layers
                    # the match ends at a page that holds a snapshot).
                    P = len(hit_pages) * pt
                    suffix = prompt[P:body]
                    padded = _padded(suffix, max_len - P, cfg.max_seq - P)
                    hk, hv = pkv.gather_history(hit_pages)
                    args = (jnp.asarray(padded), hk, hv,
                            pkv.restore_tail(hit_pages[-1]), len(suffix) - 1)
                    run = suffix_prefill_fn
                else:
                    padded = _padded(prompt[:body], max_len, cfg.max_seq)
                    args, run = (jnp.asarray(padded), body - 1), prefill_fn
                pre.ids.update(bucket=padded.shape[1],
                               hit_pages=len(hit_pages))
                pre.hand_over()
                # (a block family's prompt may leave its prefill nothing:
                # shorter than a block, or all of its whole blocks hit)
                logits, one = run(*args) if args[-1] >= 0 else (None, {})
                # The pages go to the pool (snapshots of the state at
                # whole prompt pages' ends with them), the fixed state
                # at the prompt's end to the slot.
                end = one.get("end")
                one = {k: v for k, v in one.items()
                       if k not in ("pos", "end")}
                if W:
                    # No token comes of a block family's prefill: the
                    # prompt's tail goes, committed, into the slot's
                    # first block. The host waits for the pages as it
                    # waits for a token.
                    first = prompt[body:]
                    jax.block_until_ready(one)
                else:
                    first = int(jnp.argmax(logits[0, 0]))   # the host waits
                reqlog.emit("prefill_end", rid,
                            first_token=-1 if W else first)
            with ph("refill.scatter", rid=rid):
                if one:
                    pkv.scatter_prompt(one, fresh,
                                       whole=(S - len(hit_pages) * pt) // pt)
        except Exception as exc:  # noqa: BLE001 — any device failure
            for p in hit_pages + fresh:
                pkv.alloc.decref(p)
            book.requeue(rid, exc, charge=not _peer_dead(exc))
            return False
        finally:
            if spanned:
                _span_app_end_best_effort()
        with ph("refill.seat", rid=rid):
            pkv.seat(b, hit_pages, fresh, body, rid=rid, state=end)
            snapshot_seats += bool(hit_pages and pkv.snaps is not None)
            if pkv.prefix is not None:
                pkv.prefix.insert(prompt, pkv.pages[b])
            if rid in preempted_rids:
                preempted_rids.discard(rid)
                slo.note_resume()
                reqlog.emit("resume", rid, slot=b)
            book.seat(b, rid, first)
        return True

    def retire_finished(b):
        """Retire slot b's request if it has ended: its pages go back
        to the pool and the slot is parked."""
        if owner[b] < 0 or not book.slot_finished(b):
            return False
        with ph("chunk.retire", step=book.steps, rid=owner[b]):
            pkv.release(b, rid=book.finish_request(b))
        return True

    def preempt(b):
        """Page-pressure eviction: requeue slot b's request UNCHARGED
        (server pressure is not the request's fault — the peer-loss
        rule) with its pages freed; the replay is bit-equal."""
        nonlocal n_preempts
        rid, owner[b] = owner[b], -1
        pkv.release(b)
        book.restart(rid)
        n_preempts += 1
        pkv.preemptions += 1
        preempted_rids.add(rid)
        slo.note_preempt()
        reqlog.emit("preempt", rid, slot=b)

    def grow_for_chunk():
        """Before each step: every active slot's table must cover this
        chunk's writes (positions pos..pos+chunk-1). Pool dry even
        after trie eviction -> preempt the latest arrival and rescan;
        admission guarantees a LONE request always fits, so the loop
        terminates (each preemption strictly shrinks the active set)."""
        while True:
            for b in range(n_slots):
                if owner[b] < 0:
                    continue
                need = (int(pkv.pos[b]) + chunk - 1) // pt + 1
                if not pkv.grow(b, need):
                    victims = [s for s in range(n_slots) if owner[s] >= 0]
                    if len(victims) <= 1:
                        raise RuntimeError(
                            "page pool dry for a lone request — "
                            "admission should have rejected it")
                    preempt(max(victims, key=lambda s: owner[s]))
                    break
            else:
                return

    def seed() -> bool:
        """Fill idle slots from the queue head until one refill does
        not seat (deferred, short on pages, or failed), retiring
        1-token requests on the spot. True iff any request was
        seated."""
        progressed = False
        while queue and (b := book.free_slot()) is not None:
            if not refill(b):
                break
            progressed = True
            retire_finished(b)
        return progressed

    def _publish():
        kvpage.publish_page_stats_best_effort(
            pkv.alloc.free_count, pkv.alloc.shared_count(),
            pkv.prefix.hits if pkv.prefix else 0,
            pkv.prefix.evictions if pkv.prefix else 0,
            pkv.preemptions)

    book.qd_samples.append(len(queue))
    setup.__exit__(None, None, None)
    seed()

    stalls = 0
    while book.active() or queue:
        step_no = book.steps + 1    # the chunk this pass leads up to
        with ph("loop.other", step=step_no):
            book.sample_gauges()
            _publish()
            if queue:
                for b in book.revive():
                    if queue and refill(b):
                        retire_finished(b)
        if not book.active():
            # All slots idle with requests queued (failure requeues, a
            # deferred seed, or total preemption): reseed. The SLO gate
            # never defers an empty server and admission bounds every
            # queued request, so a stall here means a real bug — bound
            # it instead of spinning.
            stalls = 0 if seed() else stalls + 1
            if stalls > len(prompts) + n_slots + 2:
                raise RuntimeError(
                    "paged scheduler stalled: queue non-empty, no slot "
                    "seatable (pool exhausted below a single request?)")
            continue
        stalls = 0
        with ph("chunk.grow", step=step_no):
            grow_for_chunk()
            # COW guard (unreachable under the radix policy —
            # defensive): the pages this chunk writes must be privately
            # owned.
            for b in range(n_slots):
                if owner[b] < 0:
                    continue
                for j in range(int(pkv.pos[b]) // pt,
                               (int(pkv.pos[b]) + chunk - 1) // pt + 1):
                    if j < len(pkv.pages[b]):
                        pkv.ensure_writable(b, j)
        if not book.active():
            continue                # grow_for_chunk preempted everyone
        with ph("chunk.upload", step=step_no) as upload:
            left = book.left()
            walked = pkv.live_pages(chunk, left)
            pages_walked += walked
            pages_dead += pkv.live_pages(chunk) - walked
            pages_grid += n_slots * max_pages * chunk
            rewritten += pkv.chunk_rewrites(chunk)
            staged += chunk * n_slots
            state = pkv.device_state(left)
        with ph("chunk.step", step=step_no) as stepped:
            stepped.ids["rid"] = tuple(owner)
            if W:
                stepped.ids["blocks"] = chunk // W
            try:
                last_tok = jnp.asarray(book.last_tok)
                stepped.hand_over()
                state, toks, keys = step_fn(state, last_tok, keys)
                pkv.absorb(state)
                if pkv.held is not None:
                    state_steps.append(int(np.clip(left, 0, chunk).sum()))
            except Exception as exc:  # noqa: BLE001 — any device failure
                book.step_failed(exc)
                # The step donated the pool buffers: rebuild from zeros and
                # drop every reference (the prefix cache's pages lived there).
                pkv.reset_pool()
                continue
            block = np.asarray(toks, np.int32)       # [chunk, B]: waits
            # (under a block family's tokens the step that committed each)
            block, at = block if W else (block, None)
        with ph("chunk.deliver", step=step_no):
            # The spans' readings are the step's time.
            book.deliver(block, upload.seconds + stepped.seconds, at)
            walked_by_chunk.append(walked)
        for b in range(n_slots):
            while retire_finished(b):
                if queue:
                    refill(b)

    with ph("loop.other", step=book.steps) as tail:
        _publish()
        metrics = book.metrics(
            preemptions=n_preempts,
            prefix_hits=pkv.prefix.hits if pkv.prefix else 0,
            prefix_evictions=pkv.prefix.evictions if pkv.prefix else 0,
            prefix_pages_reused=(pkv.prefix.pages_reused if pkv.prefix
                                 else 0),
            pages_hwm=pkv.pages_hwm,
            paged_kv_write=select_paged_kv_write(cfg.decode_flash,
                                                 pt).__name__,
            paged_decode_attend=select_paged_decode_attend(
                cfg.decode_flash, pt).__name__,
            paged_operator=pkv.spec.built("operator"),
            paged_ffn=pkv.spec.built("ffn"),
            # (a chunk's tally ends with the pairs its mask left out)
            **dict(zip(("moe_assignments", "moe_experts_live",
                        "moe_load_max", "moe_layer_steps",
                        "moe_pairs_held", "moe_group_hits"),
                       map(sum, zip(*(c[:-1] for c in pkv.moe_chunks))))),
            moe_pairs_dead=sum(c[-1] for c in pkv.moe_chunks),
            moe_experts=pkv.spec.n_experts,
            moe_experts_held=(pkv.spec.experts_held or (0, 0))[1],
            kv_bytes_token=sum(
                p.shape[0] * p.shape[2] * p.shape[3] * p.dtype.itemsize
                for p in pkv.pool.values()),
            moe_by_chunk=list(pkv.moe_chunks),
            moe_row_dim=pkv.spec.moe_row_dim,
            conv_tail_restores=pkv.tail_restores,
            state_bytes_slot=pkv.spec.state_bytes_slot,
            state_snapshot_seats=snapshot_seats,
            state_steps_by_chunk=state_steps,
            state_steps_dead=sum(pkv.state_dead_chunks),
            **({} if pkv.snaps is None else dict(
                state_snapshots_taken=pkv.snaps.taken,
                state_snapshot_rows_hwm=pkv.snaps.rows_hwm,
                state_snapshot_evictions=pkv.snaps.evictions)),
            attend_pages_walked=pages_walked,
            attend_pages_dead=pages_dead,
            attend_pages_grid=pages_grid,
            kv_page_rewrites=rewritten,
            kv_tokens_staged=staged,
            **({} if not W else dict(
                block_length=W, denoise_steps=pkv.spec.denoise_steps,
                forwards_denoise=(book.steps * (chunk // W)
                                  * pkv.spec.denoise_steps),
                forwards_store=book.steps * (chunk // W),
                block_positions_kept=sum(k for _, k in book.block_chunks),
                block_positions_dead=sum(
                    chunk * n_slots - d - k for d, k in book.block_chunks),
                block_by_chunk=[
                    (chunk // W * pkv.spec.denoise_steps, chunk // W,
                     chunk * n_slots, d, k, chunk * n_slots - d - k, w)
                    for (d, k), w in zip(book.block_chunks,
                                         walked_by_chunk)])),
            slo_deferrals=n_slo_defer,
            programs_traced=kvpage.programs_traced() - traced_at_entry)
        _request_paths(ph.spans, metrics.per_request, setup.t0)
        stalled = stalled_spans(ph.spans)
        metrics.stalls = len(stalled)
        metrics.stall_s = sum(sp.seconds - mid for sp, mid in stalled)
        metrics.spans = ph.spans
    # Filled in once the last span has closed: its end is the call's.
    metrics.call_s = tail.t1 - setup.t0
    metrics.phase_s, metrics.phase_n = ph.seconds, ph.count
    batch = ServedBatch(book.done, metrics)
    if return_paged_state:
        batch.paged_state = pkv
    return batch
