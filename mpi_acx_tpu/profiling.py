"""Device-side profiling and the span record of a host loop.

The native runtime has its own op-lifecycle Chrome trace (ACX_TRACE,
src/core/trace.cc — the host plane's observability); this module is the
device half: XLA/TPU profiler capture (:func:`trace`), the spans of a
host loop kept in memory (:class:`Phases`), and which programs the
process traced, lowered and loaded, when and for how long
(:func:`program_log`). The reference's only observability is
printf-with--DDEBUG (SURVEY.md §5.1/§5.5) — both halves here exceed it.

Timing rule: host-side per-call timing of sub-ms device work measures
dispatch RTT, not the device. A span that waits for the device ends
after a host read of the program's result (``int(...)``,
``np.asarray(...)``); for sub-ms kernels use a device-side rep loop, or
the profiler's trace, instead.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Dict, List, NamedTuple

import jax


@contextlib.contextmanager
def trace(logdir: str):
    """Capture an XLA profiler trace into ``logdir`` (TensorBoard's
    profile plugin / xprof format). Wrap the region of interest:

        with profiling.trace("/tmp/prof"):
            jax.block_until_ready(step(params, batch))
    """
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Program(NamedTuple):
    """One entry of :func:`program_log`: JAX reported that it spent
    ``seconds`` on the program ``fun_name`` (``jit(f)`` for a lowering
    or a load, the bare ``f`` for a trace), ending at ``t_end`` on
    ``time.perf_counter``. ``kind``: ``trace`` (Python ->
    jaxpr), ``lower`` (jaxpr -> MLIR), ``load`` (compiled, or fetched
    from the persistent cache, and loaded onto the device) and ``fetch``
    (the part of the load that follows it spent reading the persistent
    cache: inside ``load``, so never added to it)."""

    fun_name: str
    kind: str
    seconds: float
    t_end: float


_KINDS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "load",
          "/jax/compilation_cache/cache_retrieval_time_sec": "fetch"}
_log: List[Program] = []
_installed = False
# Per thread: ``phases``, a weak reference to the Phases made last in it
# (its innermost open span takes the thread's entries), and ``fetched``,
# a cache retrieval whose load has not been reported yet (JAX reports
# the retrieval without a name, just before the load that contains it).
_tls = threading.local()


def _on_duration(event: str, seconds: float, fun_name: str = "", **_):
    kind = _KINDS.get(event)
    if kind is None:
        return
    if kind == "fetch":
        _tls.fetched = seconds
        return
    now = time.perf_counter()
    new = [Program(fun_name, kind, seconds, now)]
    if kind == "load" and getattr(_tls, "fetched", None) is not None:
        new.insert(0, Program(fun_name, "fetch", _tls.fetched, now))
        _tls.fetched = None
    _log.extend(new)
    ph = getattr(_tls, "phases", lambda: None)()
    if ph is not None and ph._open:
        span = ph._open[-1]
        span.programs = (*span.programs, *new)


def install_program_listener() -> None:
    """Start the process's :func:`program_log` (once; later calls do
    nothing). ``backend.enable_compile_cache`` calls it before a
    process's first program, the first ``Phases()`` otherwise."""
    global _installed
    if not _installed:
        _installed = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def program_log() -> List[Program]:
    """Every program this process traced, lowered or loaded since
    :func:`install_program_listener`, in order: the process's own list
    (a caller that wants a mark takes its length). The entries that fell
    inside a span of the :class:`Phases` its thread made last are in
    that span's ``programs`` as well."""
    return _log


def _self_seconds(entries):
    """``[(entry, its seconds less the entries reported inside it)]``
    without the fetches: a jitted function called under another's trace
    is traced, and reported, inside the outer's duration, so the
    durations as reported sum to more than the time that passed. An
    entry began at ``t_end - seconds``; the entries are in order of
    their ends, so what began after a later entry's start lies in it."""
    out, loose = [], []         # loose: entries no outer one has claimed
    for e in entries:
        if e.kind == "fetch":
            continue
        own = e.seconds
        while loose and loose[-1].t_end - loose[-1].seconds >= (
                e.t_end - e.seconds):
            own -= loose.pop().seconds
        loose.append(e)
        out.append((e, max(own, 0.0)))
    return out


def _program_name(fun_name: str) -> str:
    """A trace reports ``f``, its lowering and load ``jit(f)``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def programs_by_name(entries) -> Dict[str, Dict[str, float]]:
    """``entries`` summed by program and kind: ``{name: {"trace": s,
    "lower": s, "load": s, "fetch": s, "loads": n}}``, absent kinds left
    out, the program that took longest first; self seconds (see
    :func:`program_seconds`), so the rows sum to it; ``fetch`` is the
    part of ``load`` spent reading the persistent cache and is in no
    sum."""
    out: Dict[str, Dict[str, float]] = {}

    def add(e, seconds):
        row = out.setdefault(_program_name(e.fun_name), {})
        row[e.kind] = row.get(e.kind, 0.0) + seconds
        return row
    for e, own in _self_seconds(entries):
        row = add(e, own)
        if e.kind == "load":
            row["loads"] = row.get("loads", 0) + 1
    for e in entries:
        if e.kind == "fetch":
            add(e, e.seconds)
    return dict(sorted(out.items(), key=lambda kv: -sum(
        kv[1].get(kind, 0.0) for kind in ("trace", "lower", "load"))))


def program_seconds(entries) -> float:
    """Seconds that passed while ``entries`` were traced, lowered and
    loaded: nested traces counted once, ``fetch`` inside ``load``."""
    return sum(own for _, own in _self_seconds(entries))


class _Span:
    """One :class:`Phases` span, and once it has closed its record:
    ``name``, ``ids``, ``t0``/``t1`` (its two clock readings; a caller
    that needs the boundary reads it here instead of reading the clock
    again) and ``seconds`` their difference (children included),
    ``index`` (its place in ``Phases.spans``) and ``parent`` (the index
    of the span it was opened under, None at the top), ``handed`` (the
    reading :meth:`hand_over` took, else None) and ``programs`` (the
    :class:`Program` entries reported while it was its thread's
    innermost open span). ``ids`` is the caller's own dict: a key added
    after opening is in the record and not in the profiler's span."""

    __slots__ = ("_ph", "_note", "_children", "name", "ids", "t0", "t1",
                 "seconds", "index", "parent", "handed", "programs")

    def __init__(self, ph, name, ids):
        self._ph, self.name, self.ids = ph, name, ids
        self._note = jax.profiler.TraceAnnotation(name, **ids)
        self.t0 = self.t1 = self.seconds = self._children = 0.0
        self.parent = self.handed = None
        self.programs = ()

    def __enter__(self):
        ph = self._ph
        self._note.__enter__()
        if ph._open:
            self.parent = ph._open[-1].index
        self.index = len(ph.spans)
        ph.spans.append(self)
        ph._open.append(self)
        self.t0 = ph._clock()
        return self

    def hand_over(self) -> None:
        """Mark the moment this span hands the device the program it
        then waits for: one more clock reading, ``handed``. A mark, not
        a child span: the self times do not move."""
        self.handed = self._ph._clock()

    def __exit__(self, *exc):
        ph = self._ph
        t1 = ph._clock()
        while ph._open[-1] is not self:     # left open under this one
            ph._open[-1]._close(t1)
        self._close(t1)
        return False

    def _close(self, t1):
        ph = self._ph
        self.t1 = t1
        self._note.__exit__(None, None, None)
        self.seconds = t1 - self.t0
        ph._open.pop()
        if ph._open:
            ph._open[-1]._children += self.seconds
        ph.seconds[self.name] = (ph.seconds.get(self.name, 0.0)
                                 + self.seconds - self._children)
        ph.count[self.name] = ph.count.get(self.name, 0) + 1
        # The record keeps no handle of the profiler and no cycle.
        self._ph = self._note = None


class Phases:
    """Named phases of a host loop, as spans on the device trace's clock,
    as counters, and as a record that outlives the profiler, from one
    ``with``:

        ph = Phases()
        with ph("refill.prefill", rid=rid) as span:
            ...
            span.hand_over()        # optional: program handed over here
            ...
        ph.seconds["refill.prefill"], ph.count["refill.prefill"]
        ph.spans                    # every span, in order of opening

    ``ph(name, **ids)`` opens ``jax.profiler.TraceAnnotation(name,
    **ids)``: while the profiler runs, the span sits on the host's
    ``python`` line of the same ``.xplane.pb`` as the device's ops (one
    clock), nested under the span that was open, carrying ``ids``; with
    the profiler off it costs a flag test. It also adds the span's SELF
    time (its duration on ``clock``, less the spans opened inside it)
    to ``seconds[name]`` and one to ``count[name]``, so the self times
    of spans that tile a call sum to the call. And the span object
    itself stays in ``spans`` (:class:`_Span`: readings, parent, ids,
    the hand-over mark, the programs JAX loaded inside it): in memory,
    always, no second clock reading; bounded by what the loop opens.
    A span closes the spans still open under it. One thread."""

    def __init__(self, clock=time.perf_counter):
        self.seconds: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        self.spans: List[_Span] = []
        self._clock = clock
        self._open: List[_Span] = []
        _tls.phases = weakref.ref(self)
        install_program_listener()

    def __call__(self, name: str, **ids) -> _Span:
        return _Span(self, name, ids)
