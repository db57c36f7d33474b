#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It owns the chip, fails when JAX finds none (no
CPU fallback), makes weights and traffic from ``--seed``, warms up every
shape the cell's traffic uses (set-up), measures for ``--seconds``,
compares what the timed path produced with the plain reference, and
prints as its last line the result object BENCHMARK.json's contract
describes. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a run with the profiler on for
a piece of the window.

The cell, its configuration, its traffic mix and its per-layer metrics
are all files found by the names in BENCHMARK.json; the driver for a
configuration is ``entries/<entry>.py``, named by the configuration.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402


def measure(cell, seed, seconds, trace, t_start=T_START,
            chip=harness.require_chips) -> str:
    """The whole of a run after argument parsing; returns the result
    line. ``chip`` is the look for a chip (tests replace it)."""
    device = chip(cell.cell["chips"])
    from mpi_acx_tpu import backend
    backend.enable_compile_cache()
    entry = importlib.import_module(
        "benchmarks.entries." + cell.config["entry"])
    run = entry.run(cell, seed, seconds, trace, t_start)
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    breakdown = None
    if trace:
        from benchmarks import flops, trace_reduce
        run["reduced"] = trace_reduce.reduce_dir(run["trace_dir"])
        shutil.rmtree(run["trace_dir"], ignore_errors=True)
        run["peaks"] = flops.peaks(device["kind"])
        device["busy_s"] = run["reduced"]["busy_s"]
        device["window_s"] = run["reduced"]["window_s"]
        breakdown = run["reduced"]["breakdown"]
        metrics = cell.read_layer_metrics(run)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in run["end_to_end"].items() if k in units}
    return harness.result_line(run["correct"], run["attempted"],
                               run["failed"], metrics, device, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = harness.Cell(a.workload)
    try:
        line = measure(cell, a.seed, a.seconds, bool(a.trace))
    except harness.NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
