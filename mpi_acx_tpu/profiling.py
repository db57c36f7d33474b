"""Device-side profiling and step timing for the TPU compute layer.

The native runtime has its own op-lifecycle Chrome trace (ACX_TRACE,
src/core/trace.cc — the host plane's observability); this module is the
device half: XLA/TPU profiler capture and honest wall-clock step
statistics. The reference's only observability is printf-with--DDEBUG
(SURVEY.md §5.1/§5.5) — both halves here exceed it.

Timing rule: host-side per-call timing of sub-ms device work measures
dispatch RTT, not the device. ``StepTimer`` forces a ``block_until_ready`` sync per
step so each sample is a true device round-trip; for sub-ms kernels use
a device-side rep loop, or the profiler's trace, instead.
"""

from __future__ import annotations

import contextlib
import json
import math
import time
from typing import Any, Dict, List, Optional

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


def annotate(name: str):
    """Named sub-region inside a trace (shows as a span in the viewer):

        with profiling.annotate("attention"):
            o = flash_attention(q, k, v)
    """
    return jax.profiler.TraceAnnotation(name)


class _Span:
    """One open :class:`Phases` span. After the block, ``t0``/``t1`` are
    its two clock readings and ``seconds`` their difference (children
    included): a caller that needs the boundary reads it here instead of
    reading the clock again."""

    __slots__ = ("_ph", "name", "_note", "t0", "t1", "seconds", "_children")

    def __init__(self, ph, name, ids):
        self._ph, self.name = ph, name
        self._note = jax.profiler.TraceAnnotation(name, **ids)
        self.t0 = self.t1 = self.seconds = self._children = 0.0

    def __enter__(self):
        self._note.__enter__()
        self._ph._open.append(self)
        self.t0 = self._ph._clock()
        return self

    def __exit__(self, *exc):
        ph = self._ph
        self.t1 = ph._clock()
        self._note.__exit__(*exc)
        self.seconds = self.t1 - self.t0
        ph._open.pop()
        if ph._open:
            ph._open[-1]._children += self.seconds
        ph.seconds[self.name] = (ph.seconds.get(self.name, 0.0)
                                 + self.seconds - self._children)
        ph.count[self.name] = ph.count.get(self.name, 0) + 1
        return False


class Phases:
    """Named phases of a host loop, as spans on the device trace's clock
    and as counters, from one ``with``:

        ph = Phases()
        with ph("refill.prefill", rid=rid) as span:
            ...
        ph.seconds["refill.prefill"], ph.count["refill.prefill"]

    ``ph(name, **ids)`` opens ``jax.profiler.TraceAnnotation(name,
    **ids)``: while the profiler runs, the span sits on the host's
    ``python`` line of the same ``.xplane.pb`` as the device's ops (one
    clock), nested under the span that was open, carrying ``ids``; with
    the profiler off it costs a flag test. It also adds the span's SELF
    time (its duration on ``clock``, less the spans opened inside it)
    to ``seconds[name]`` and one to ``count[name]``, so the self times
    of spans that tile a call sum to the call. The profiler's buffer is
    the only span store; nothing is written. One thread."""

    def __init__(self, clock=time.perf_counter):
        self.seconds: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        self._clock = clock
        self._open: List[_Span] = []

    def __call__(self, name: str, **ids) -> _Span:
        return _Span(self, name, ids)


class StepTimer:
    """Wall-clock statistics over training/serving steps.

    Each timed region ends with ``jax.block_until_ready`` on the value
    handed to ``stop`` (or the region's result), so a sample covers the
    full device execution, not just dispatch. Percentiles use the sorted
    sample list (no interpolation — honest for small n).

        timer = StepTimer()
        for batch in data:
            with timer.step() as t:
                loss, params = train_step(params, batch)
                t.sync(loss)
        print(timer.summary())
    """

    class _Region:
        def __init__(self):
            self._value = None
            self._synced = False

        def sync(self, value: Any):
            """Register the value whose readiness ends the step."""
            self._value = value
            self._synced = True

    def __init__(self):
        self.samples: List[float] = []

    @contextlib.contextmanager
    def step(self):
        region = StepTimer._Region()
        t0 = time.perf_counter()
        yield region
        if not region._synced:
            # Without a sync point the sample would measure async DISPATCH
            # only — the exact pitfall this class exists to prevent
            # (module docstring). Fail loudly rather than record it.
            raise RuntimeError(
                "StepTimer.step() region ended without sync(value); the "
                "sample would time dispatch, not the device step")
        jax.block_until_ready(region._value)
        self.samples.append(time.perf_counter() - t0)

    def _pct(self, p: float) -> float:
        s = sorted(self.samples)
        if not s:
            return 0.0
        # Nearest-rank percentile: the ceil(p*n)-th smallest sample.
        return s[max(0, math.ceil(p * len(s)) - 1)]

    def reset(self) -> None:
        """Drop all recorded samples (e.g. after a warmup phase, so the
        compile-step outlier doesn't poison the percentiles)."""
        self.samples = []

    def summary(self) -> Dict[str, float]:
        if not self.samples:
            return {"steps": 0}
        n = len(self.samples)
        return {
            "steps": n,
            "mean_s": sum(self.samples) / n,
            "min_s": min(self.samples),
            "p50_s": self._pct(0.50),
            "p90_s": self._pct(0.90),
            "p99_s": self._pct(0.99),
            "max_s": max(self.samples),
        }

    def dump(self, path: str, extra: Optional[Dict[str, Any]] = None):
        """Write summary + raw samples as JSON."""
        out = dict(self.summary(), samples=self.samples, **(extra or {}))
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        return out
