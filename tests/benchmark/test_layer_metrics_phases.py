"""CPU tests of the six per-layer readers that read the serve loop's
phase spans and counters (``ServingMetrics.phase_s`` / ``call_s`` /
``decode_*``, ``RequestTelemetry.queue_wait_s`` / ``prefill_s`` /
``refill_host_s``): each on a hand-made ``run`` against a hand count, on
a run of a program that records none of it (nothing to read, no error),
and their manifest entries against the rules every entry is held to.
"""

import importlib.util
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
SERVING = ["xl_chat_burst", "xl_shared_prefix"]
NEW = {"entry_setup_ms": ("Entry", "program_span", "ttft_p95_ms"),
       "sched_queue_wait_p95_ms": ("Scheduler", "program_span",
                                   "ttft_p95_ms"),
       "sched_step_utilization": ("Scheduler", "program_counter",
                                  "serve_tok_s"),
       "sched_host_share": ("Scheduler", "program_span", "serve_tok_s"),
       "sched_refill_host_ms": ("Scheduler", "program_span", "ttft_p95_ms"),
       "step_prefill_ms": ("Step programs", "program_span", "ttft_p95_ms")}


def _read(metric, run):
    path = harness.Cell(SERVING[0]).reader_path(metric)
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _burst(**metrics):
    return types.SimpleNamespace(outs=types.SimpleNamespace(
        metrics=types.SimpleNamespace(**metrics)))


def _req(wait, prefill, host):
    return types.SimpleNamespace(queue_wait_s=wait, prefill_s=prefill,
                                 refill_host_s=host)


def _run(traced=None):
    """Two bursts of a window, by hand. Burst 1: 10 s call, 7 s in the
    two device-waiting phases; burst 2: 20 s, 15 s."""
    one = _burst(
        call_s=10.0, decode_tokens=30, decode_slot_steps=64,
        phase_s={"serve.setup": 0.30, "refill.prefill": 2.0,
                 "chunk.step": 5.0, "refill.seat": 0.5, "loop.other": 2.2},
        per_request=[_req(0.3, 0.050, 0.004), _req(0.4, 0.060, 0.002),
                     _req(5.0, 0.040, 0.010)])
    two = _burst(
        call_s=20.0, decode_tokens=50, decode_slot_steps=96,
        phase_s={"serve.setup": 0.50, "refill.prefill": 3.0,
                 "chunk.step": 12.0, "loop.other": 4.5},
        per_request=[_req(0.5, 0.070, 0.006), _req(9.0, 0.020, 0.008)])
    return {"bursts": [one, two], "traced": traced}


@pytest.mark.parametrize("metric, want", [
    ("entry_setup_ms", 400.0),              # median of 300 and 500
    # 5 requests: nearest rank ceil(.95 * 5) = 5th smallest wait
    ("sched_queue_wait_p95_ms", 9000.0),
    ("sched_step_utilization", 100.0 * 80 / 160),
    ("sched_host_share", 100.0 * (30.0 - 22.0) / 30.0),
    ("sched_refill_host_ms", 6.0),          # median of 2, 4, 6, 8, 10
    ("step_prefill_ms", 50.0),              # median of 20, 40, 50, 60, 70
])
def test_reader_against_a_hand_count(metric, want):
    assert _read(metric, _run()) == pytest.approx(want, rel=1e-12)


def test_host_share_leaves_out_the_benchmarks_own_profiler_pauses():
    """A traced run starts and stops the profiler inside ``on_token``,
    inside the program's spans: 1.5 s + 0.5 s of the calls' 30 s are
    the benchmark's and count on neither side."""
    traced = (100.0, 105.0, [(99.0, 100.5), (105.0, 105.5)])
    assert _read("sched_host_share", _run(traced)) == pytest.approx(
        100.0 * (28.0 - 22.0) / 28.0, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_finds_nothing_in_a_program_without_phases(metric):
    """The parent of the PR that brought the phases is measured with
    these files too: its metrics object has none of the fields, and the
    reader says so by returning nothing."""
    bare = types.SimpleNamespace(rid=0, ttft_s=0.1)
    run = {"bursts": [_burst(per_request=[bare], slot_occupancy_mean=0.7)],
           "traced": None}
    assert _read(metric, run) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_manifest_entry_of_a_phase_metric(metric):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    layer, source, moves = NEW[metric]
    assert (entry["layer"], entry["source"], entry["moves"]) == (
        layer, source, moves)
    assert entry["workloads"] == SERVING
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the rules of test_benchmark_harness.test_metric_entry_is_well_formed
    # hold for it too (that test is parametrised over the manifest), and
    # every cell that lists it finds its reader by name
    for cell in entry["workloads"]:
        c = harness.Cell(cell)
        assert metric in [m["name"] for m in c.per_layer()]
        assert os.path.basename(c.reader_path(metric)) == metric + ".py"
    target, = [m for m in MANIFEST["end_to_end"] if m["name"] == moves]
    assert set(entry["workloads"]) <= set(target["workloads"])


def test_the_new_entries_are_appended_and_the_old_ones_untouched():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[10:16] == ["entry_setup_ms", "sched_queue_wait_p95_ms",
                            "sched_step_utilization", "sched_host_share",
                            "sched_refill_host_ms", "step_prefill_ms"]
    assert names[:10] == [
        "entry_first_token_ms", "sched_slot_occupancy",
        "sched_prefix_token_share", "step_decode_ms", "train_step_ms",
        "train_mfu", "compiles_in_window.serve", "compiles_in_window.train",
        "kernel_decode_attend_roofline", "kernel_flash_attn_roofline"]
