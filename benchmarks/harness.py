"""What every run of the benchmark shares: the manifest and the files it
names, the look for a chip, compile events, the arithmetic of the
end-to-end metrics, and the one result line.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files it names, all found by
    name: ``configs/<config>.json`` (through the manifest's ``file``),
    ``traffic/<traffic>.json``, ``layer_metrics/<metric>.py``."""

    def __init__(self, workload: str, root: str = ROOT, here: str = HERE):
        self.root, self.here = root, here
        self.manifest = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
        self.cell = cells[workload]
        self.name = workload
        cfg = {c["name"]: c for c in self.manifest["configs"]}[
            self.cell["config"]]
        self.config = load_json(root, cfg["file"])
        self.traffic = load_json(here, "traffic",
                                 self.cell["traffic"] + ".json")

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self) -> list:
        return [m for m in self.manifest["per_layer"] if self._reports(m)]

    def reader_path(self, metric: str) -> str:
        """``layer_metrics/<metric>.py``, or for a metric split by the
        end-to-end metric it moves (``<reader>.<variant>``) with no file
        of its full name, ``layer_metrics/<reader>.py``."""
        for name in (metric, metric.rpartition(".")[0]):
            path = os.path.join(self.here, "layer_metrics", name + ".py")
            if name and os.path.isfile(path):
                return path
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")

    def read_layer_metrics(self, run) -> dict:
        """Each per-layer metric of this cell through its own reader,
        ``read(run) -> number or None`` in the file :meth:`reader_path`
        finds. A reader that finds nothing to read returns None and the
        metric is left out of the line."""
        out = {}
        for m in self.per_layer():
            path = self.reader_path(m["name"])
            spec = importlib.util.spec_from_file_location(
                "layer_metric_" + m["name"].replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            value = mod.read(run)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


def gpt2_program_config(c: dict, dtype: str):
    """The program's own config object for a GPT-2 configuration file;
    ``dtype`` is what the entry computes in."""
    import jax.numpy as jnp
    from mpi_acx_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["n_embd"], n_heads=c["n_head"],
        n_layers=c["n_layer"], d_ff=c["n_inner"], max_seq=c["n_positions"],
        dtype=jnp.dtype(dtype))


def start_trace(logdir: str):
    """Start the profiler into an emptied ``logdir`` (host Python calls
    not traced: they swamp the file) and open the window span; returns
    the span for :func:`stop_trace`."""
    import shutil

    import jax

    from benchmarks.trace_reduce import WINDOW_SPAN
    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
    span.__enter__()
    return span


def stop_trace(span) -> None:
    import jax
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


def require_chips(n: int) -> dict:
    """The device as JAX reports it; raises NoChip on anything but a TPU
    with at least ``n`` chips. There is no CPU fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"need {n} TPU chip(s); JAX reports {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return describe_device()


def describe_device() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does
    not say, as on the CPU)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class Watch:
    """Compile traffic of a block, from JAX's monitoring events: real
    compilations (persistent-cache misses), programs loaded from the
    persistent cache (hits — a re-traced program costs one), and the
    seconds spent in either."""

    _live: list = []
    _installed = False

    @classmethod
    def install(cls):
        if cls._installed:
            return
        import jax

        def on_event(event, **_):
            for w in cls._live:
                if event.endswith("/cache_hits"):
                    w.hits += 1
                elif event.endswith("/cache_misses"):
                    w.misses += 1

        def on_duration(event, secs, **_):
            if event.endswith("/backend_compile_duration"):
                for w in cls._live:
                    w.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._installed = True

    def __enter__(self):
        Watch.install()
        self.hits = self.misses = 0
        self.compile_s = 0.0
        self._t0 = time.perf_counter()
        Watch._live.append(self)
        return self

    def __exit__(self, *exc):
        Watch._live.remove(self)
        self.wall_s = time.perf_counter() - self._t0


def percentile(samples, p: float) -> float:
    """Nearest-rank percentile: the ceil(p * n)-th smallest sample."""
    s = sorted(samples)
    if not s:
        raise ValueError("percentile of no samples")
    return s[max(0, math.ceil(p * len(s)) - 1)]


class TokenLog:
    """The ``on_token`` record of one burst: per request the host time
    of its first and last token and how many came, and every token as
    ``(time, request, index in the request)`` in order of delivery."""

    def __init__(self, n_requests: int, t_handed: float,
                 clock=time.perf_counter):
        self.t_handed = t_handed
        self.first = [None] * n_requests
        self.last = [None] * n_requests
        self.count = [0] * n_requests
        self.events = []
        self._clock = clock

    def on_token(self, rid: int, token: int) -> None:
        now = self._clock()
        if self.first[rid] is None:
            self.first[rid] = now
        self.last[rid] = now
        self.events.append((now, rid, self.count[rid]))
        self.count[rid] += 1

    def deliveries(self, gap_s: float = 0.02, pauses=()) -> list:
        """Decode tokens (index >= 1; index 0 is the prefill's) grouped
        into deliveries: the tokens of one decode chunk reach
        ``on_token`` within microseconds of each other, chunks are far
        apart. ``pauses`` are (start, end) host intervals the callback
        itself spent elsewhere (starting or stopping the profiler); they
        do not split a delivery.
        [(time of its first token, [(request, index), ...])]"""
        out = []
        for t, rid, idx in self.events:
            if idx == 0:
                continue
            paused = (sum(max(0.0, min(t, e) - max(out[-1][2], s))
                          for s, e in pauses) if out else 0.0)
            if not out or t - out[-1][2] - paused > gap_s:
                out.append([t, [], t])
            out[-1][1].append((rid, idx))
            out[-1][2] = t
        return [(t0, toks) for t0, toks, _ in out]

    def ttft_s(self) -> list:
        """Per request, burst handed over -> first token (None: never)."""
        return [None if f is None else f - self.t_handed
                for f in self.first]

    def tpot_s(self) -> list:
        """Per request with >= 2 tokens: (last - first) / (tokens - 1)."""
        return [(l - f) / (c - 1)
                for f, l, c in zip(self.first, self.last, self.count)
                if c >= 2]


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown: dict | None = None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def say(what: str, **facts) -> None:
    """One labelled JSON line on stdout (never the last one)."""
    print(json.dumps({"note": what, **facts}), flush=True)


def check_line(name: str, value, limit, ok: bool) -> bool:
    """Print one compared number beside its limit; returns ``ok``."""
    say("check", name=name, value=value, limit=limit, ok=bool(ok))
    return bool(ok)
