"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
the operations that took most of it, the idle gaps named by what the
host was doing, and the summed time of any named operation.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU
trace looks like (read by hand from v5e traces, PR 23): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per
executed HLO operation. An event's name is the instruction's whole HLO
text (``%fusion.12 = bf16[8,1024]{...} fusion(...), kind=...``); a
Pallas kernel is a ``custom-call`` with ``custom_call_target=
"tpu_custom_call"`` named after the function that made it
(``%flash_attention_lse.16``). Control flow (``while``) is an event
that CONTAINS its body's events, so durations are reduced to self time.
Host threads are lines of the plane ``/host:CPU``; the line ``python``
holds ``jax.profiler.TraceAnnotation`` spans under their own names and
JAX's own (``PjitFunction(step)``, ``np.asarray(jax.Array)``). The
benchmark brackets the traced region with the span ``WINDOW_SPAN``;
busy time is clipped to it.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW_SPAN = "bench:window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_LINE = "python"
MIN_GAP_NS = 20_000
MAX_GAPS_NAMED = 4000
TOP = 10


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path: str) -> dict:
    """{'devices': {plane: [(name, start_ns, dur_ns)]},
        'host': [(name, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += _events(line)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            lines = list(plane.lines)
            named = [l for l in lines if l.name == HOST_LINE]
            for line in named or lines:
                host += _events(line)
    return {"devices": devices, "host": host}


def short_name(hlo: str) -> str:
    """``%fusion.12 fusion bf16[8,1024]`` from an instruction's HLO text
    (its name, opcode and result shape), ``pallas`` for a Mosaic kernel."""
    m = re.match(r"(%[^ ]+) = (.*)", hlo)
    if not m:
        return hlo[:120]
    name, rest = m.groups()
    if "tpu_custom_call" in rest:
        op = "pallas"
    else:
        depth, op = 0, ""
        for tok in re.finditer(r"[()]| ([a-z][\w\-]*)\(", rest):
            if tok.group(1) and depth == 0:
                op = tok.group(1)
                break
            depth += {"(": 1, ")": -1}.get(tok.group(0), 0)
    shape = re.match(r"\(?(\w+\[[\d,]*\])", rest)
    return " ".join(x for x in (name, op, shape.group(1) if shape else "")
                    if x)[:120]


def self_times(ops):
    """[(name, self seconds)]: an event's duration less its direct
    children's (events that start and end inside it)."""
    out, stack = [], []
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            out.append((stack[-1][0], stack.pop()[2] / 1e9))
        if stack and s + d <= stack[-1][1]:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    out += [(n, d / 1e9) for n, _, d in stack]
    return out


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(trace: dict):
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ops = [(s, s + d) for evs in trace["devices"].values()
           for _, s, d in evs]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in ops), max(e for _, e in ops)


def _name_gaps(gaps, host, lo, hi):
    """Seconds of idle gap per host span: each gap goes to the span
    that started last before the gap's middle and still runs there."""
    host = sorted((s, s + d, n) for n, s, d in host
                  if n != WINDOW_SPAN and s + d >= lo and s <= hi and d > 0)
    starts = np.array([h[0] for h in host])
    named: dict = {}
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:MAX_GAPS_NAMED]:
        mid, name = (g0 + g1) / 2, "(no host span)"
        i = int(np.searchsorted(starts, mid)) - 1
        for j in range(i, max(i - 64, -1), -1):
            if host[j][1] >= mid:
                name = host[j][2]
                break
        named[name] = named.get(name, 0.0) + (g1 - g0) / 1e9
    return named


def reduce(trace: dict) -> dict:
    """busy_s and window_s (averaged over the chips traced), the top
    device operations and idle gaps, and every operation's seconds."""
    lo, hi = _window(trace)
    if not trace["devices"] or not any(trace["devices"].values()):
        raise ValueError("the trace holds no device operation")
    busy, op_s, gaps = [], {}, []
    for ops in trace["devices"].values():
        clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
                   if s + d > lo and s < hi]
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, secs in self_times([e for e in ops
                                      if e[1] + e[2] > lo and e[1] < hi]):
            op_s[name] = op_s.get(name, 0.0) + secs
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] - edges[i] >= MIN_GAP_NS]
    n = len(trace["devices"])
    named = _name_gaps(gaps, trace["host"], lo, hi)
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    op_s = {k: v / n for k, v in op_s.items()}
    short: dict = {}
    for k, v in op_s.items():
        short[short_name(k)] = short.get(short_name(k), 0.0) + v
    return {"busy_s": sum(busy) / n, "window_s": (hi - lo) / 1e9,
            "op_seconds": op_s,
            "breakdown": {"device_ops": top(short),
                          "idle_gaps": top({k: v / n
                                            for k, v in named.items()})}}


def op_seconds(reduced: dict, *contains: str) -> float:
    """Summed self seconds of the operations whose HLO text holds every
    string of ``contains``."""
    return sum(v for k, v in reduced["op_seconds"].items()
               if all(c in k for c in contains))


def op_calls(trace: dict, *contains: str) -> int:
    """How many events inside the window hold every string."""
    lo, hi = _window(trace)
    return sum(1 for ops in trace["devices"].values() for k, s, d in ops
               if s + d > lo and s < hi and all(c in k for c in contains))


def reduce_dir(logdir: str) -> dict:
    trace = load(find_xplane(logdir))
    return dict(reduce(trace), trace=trace)
