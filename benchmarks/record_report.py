#!/usr/bin/env python3
"""What a serving cell's span record says about one plain window.

    python3 benchmarks/record_report.py --workload <cell> --seed <n> \\
        [--seconds 30] [--out chiprun_out/record_<cell>.json]

One untraced run of the cell, exactly as ``benchmarks/run.py`` makes it
(the same entry, weights, traffic and comparison; its result line is
printed too), and then, from ``ServingMetrics.spans`` of the window's
calls and ``profiling.program_log()``:

* ``tpot_split``: the window's requests with two tokens or more pooled,
  their first-to-last-token seconds split into their own chunks, other
  requests' refills and the rest; and the record's own p95 of a
  request's gap between tokens beside the benchmark's ``tpot_p95_ms``;
* ``readers``: the four record metrics read by their reader files,
  whether or not ``BENCHMARK.json`` lists them for this cell;
* ``stalls``: every waiting span that took more than twice its like
  (``serving.stalled_spans``), with its call, ``rid`` or ``step``, size,
  the median it is measured against and the programs loaded inside it;
* ``chunk_steps`` / ``prefill_buckets``: every ``chunk.step`` of the
  window, and the prefills by bucket (count, median, longest);
* ``setup_programs``: the programs loaded before the window, by name
  (seconds traced, lowered, loaded), and each entry of 0.2 s or more in
  order, summing to ``compile_setup_load_s``.

A tool for a builder with a chip, not a cell: it changes no number the
driver reads. The tables go to ``--out`` whole and to stdout as notes.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

READERS = ("sched_tpot_refill_share", "step_prefill_wait_ms",
           "sched_stall_ms", "compile_setup_load_s", "step_prefill_ms",
           "sched_host_share")


def read_metric(cell, name, run):
    spec = importlib.util.spec_from_file_location(
        "record_report_" + name, cell.reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def tpot_split(bursts) -> dict:
    """Pooled over the requests with two tokens or more."""
    reqs = [r for b in bursts for r in b.outs.metrics.per_request
            if r.new_tokens >= 2]
    whole = sum(r.decode_s for r in reqs)
    chunk = sum(r.decode_in_chunk_s for r in reqs)
    refill = sum(r.decode_in_refill_s for r in reqs)
    gaps = [r.decode_s / (r.new_tokens - 1) for r in reqs]
    return {"requests": len(reqs), "decode_s": whole,
            "own_chunks_share": 100.0 * chunk / whole,
            "others_refills_share": 100.0 * refill / whole,
            "rest_share": 100.0 * (whole - chunk - refill) / whole,
            "record_tpot_p50_ms": 1e3 * harness.percentile(gaps, 0.50),
            "record_tpot_p95_ms": 1e3 * harness.percentile(gaps, 0.95),
            "chunks_a_request": sum(r.chunks for r in reqs) / len(reqs)}


def span_tables(bursts, t_window) -> dict:
    from mpi_acx_tpu.models import serving
    stalls, steps, buckets = [], [], {}
    for i, b in enumerate(bursts):
        spans = b.outs.metrics.spans
        for sp, mid in serving.stalled_spans(spans):
            stalls.append({
                "call": i, "span": sp.name,
                "rid": sp.ids.get("rid") if sp.name != "chunk.step" else None,
                "step": sp.ids.get("step"), "bucket": sp.ids.get("bucket"),
                "at_s": round(sp.t0 - t_window, 3),
                "ms": round(1e3 * sp.seconds, 3),
                "median_ms": round(1e3 * mid, 3),
                "wait_ms": round(1e3 * (sp.t1 - sp.handed), 3),
                "programs": [[e.fun_name, e.kind, round(e.seconds, 3)]
                             for e in sp.programs]})
        for sp in spans:
            if sp.name == "chunk.step":
                steps.append({
                    "call": i, "step": sp.ids["step"],
                    "at_s": round(sp.t0 - t_window, 3),
                    "ms": round(1e3 * sp.seconds, 3),
                    "owned": sum(r >= 0 for r in sp.ids["rid"])})
            elif sp.name == "refill.prefill":
                buckets.setdefault(
                    f'{sp.ids.get("bucket")}/{sp.ids.get("hit_pages")}',
                    []).append(sp.seconds)
    return {"stalls": stalls, "chunk_steps": steps,
            "prefill_buckets": {
                k: {"n": len(v), "median_ms": round(
                    1e3 * statistics.median(v), 3),
                    "max_ms": round(1e3 * max(v), 3)}
                for k, v in sorted(buckets.items())}}


def setup_programs(t_window) -> dict:
    from mpi_acx_tpu import profiling
    before = [e for e in profiling.program_log() if e.t_end <= t_window]
    return {"seconds": profiling.program_seconds(before),
            "entries": len(before),
            "by_name": {n: {k: round(v, 3) for k, v in r.items()}
                        for n, r in
                        profiling.programs_by_name(before).items()
                        if sum(r.get(k, 0.0) for k in
                               ("trace", "lower", "load")) >= 0.05},
            "long_entries": [
                {"fun_name": e.fun_name, "kind": e.kind,
                 "s": round(e.seconds, 3),
                 "ended_at_s": round(e.t_end - T_START, 3)}
                for e in before if e.seconds >= 0.2]}


def report(cell, seed, seconds, t_start=T_START,
           chip=harness.require_chips) -> tuple:
    """(the tables, the run's result line); ``chip`` is the look for a
    chip (tests replace it)."""
    device = chip(cell.cell["chips"])
    from mpi_acx_tpu import backend
    backend.enable_compile_cache()
    entry = importlib.import_module(
        "benchmarks.entries." + cell.config["entry"])
    run = entry.run(cell, seed, seconds, False, t_start)
    t_window = run["window_watch"]._t0
    tables = {"workload": cell.name, "seed": seed,
              "end_to_end": run["end_to_end"], "correct": run["correct"],
              "tpot_split": tpot_split(run["bursts"]),
              "readers": {n: read_metric(cell, n, run) for n in READERS},
              **span_tables(run["bursts"], t_window),
              "setup_programs": setup_programs(t_window)}
    units = {m["name"]: m["unit"] for m in cell.end_to_end()}
    line = harness.result_line(
        run["correct"], run["attempted"], run["failed"],
        {k: {"value": float(v), "unit": units[k]}
         for k, v in run["end_to_end"].items() if k in units},
        dict(device, memory_peak_bytes=run["memory_peak_bytes"]))
    return tables, line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    if "serve" not in cell.config:
        print("benchmarks/record_report.py: not a serving cell",
              file=sys.stderr)
        return 2
    try:
        tables, line = report(cell, a.seed, a.seconds)
    except harness.NoChip as e:
        print(f"benchmarks/record_report.py: {e}", file=sys.stderr)
        return 3
    out = a.out or os.path.join(ROOT, "chiprun_out",
                                f"record_{a.workload}_{a.seed}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(tables, f, indent=1)
    for key in ("tpot_split", "readers", "stalls", "prefill_buckets",
                "chunk_steps", "setup_programs"):
        harness.say(key, value=tables[key])
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
