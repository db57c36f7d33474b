"""CPU tests of the four per-layer readers that read the serve loop's
span record (``ServingMetrics.spans`` / ``stall_s``,
``RequestTelemetry.decode_s`` / ``decode_in_refill_s`` /
``prefill_wait_s``) and the process's program log
(``profiling.program_log``): each on a hand-made ``run`` against a hand
count, on a run of a program that keeps none of it (nothing to read, no
error), on a real tiny serve call, and their manifest entries against
the rules every entry is held to.
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
XL = ["xl_chat_burst", "xl_shared_prefix"]
# name -> (unit, better, source, layer, moves)
NEW = {
    "sched_tpot_refill_share": ("%", "lower", "program_span", "Scheduler",
                                "tpot_p95_ms"),
    "step_prefill_wait_ms": ("ms", "lower", "program_span", "Step programs",
                             "ttft_p95_ms"),
    "sched_stall_ms": ("ms", "lower", "program_span", "Scheduler",
                       "serve_tok_s"),
    "compile_setup_load_s": ("s", "lower", "program_counter", "Compile",
                             "setup_s"),
}


def _read(metric, run):
    path = harness.Cell(XL[0]).reader_path(metric)
    spec = importlib.util.spec_from_file_location("reader_" + metric, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _req(tokens, decode, in_refill, wait):
    return types.SimpleNamespace(new_tokens=tokens, decode_s=decode,
                                 decode_in_refill_s=in_refill,
                                 prefill_wait_s=wait)


def _burst(t0, t1, stall_s, per_request):
    ends = [types.SimpleNamespace(t0=t0, t1=t0 + 1.0),
            types.SimpleNamespace(t0=t1 - 1.0, t1=t1)]
    return types.SimpleNamespace(outs=types.SimpleNamespace(
        metrics=types.SimpleNamespace(spans=ends, stall_s=stall_s,
                                      per_request=per_request)))


def _run(traced=None, log=None, monkeypatch=None):
    """Two calls of a window that starts at 100 s on the host's clock.
    Call 1 (100-110): three requests, one of a single token; call 2
    (110-130): two."""
    one = _burst(100.0, 110.0, 0.25, [
        _req(5, 2.0, 0.5, 0.010), _req(1, 0.0, 0.0, 0.030),
        _req(9, 4.0, 1.5, 0.020)])
    two = _burst(110.0, 130.0, 0.0, [
        _req(3, 6.0, 1.0, 0.050), _req(2, 8.0, 3.0, 0.040)])
    if monkeypatch is not None:
        from mpi_acx_tpu import profiling
        monkeypatch.setattr(profiling, "program_log", lambda: log)
    return {"bursts": [one, two], "traced": traced,
            "window_watch": types.SimpleNamespace(_t0=100.0)}


def _log():
    from mpi_acx_tpu.profiling import Program as P
    return [P("make_weights", "trace", 1.0, 20.0),
            P("inner", "trace", 0.5, 40.0),             # inside the next
            P("paged_prefill", "trace", 2.0, 41.0),
            P("jit(paged_prefill)", "lower", 1.0, 42.0),
            P("jit(paged_prefill)", "fetch", 3.0, 50.0),    # inside its load
            P("jit(paged_prefill)", "load", 4.0, 50.0),
            P("jit(_scatter)", "load", 7.0, 100.5)]     # inside the window


@pytest.mark.parametrize("metric, want", [
    # requests of >= 2 tokens: (0.5 + 1.5 + 1.0 + 3.0) / (2 + 4 + 6 + 8)
    ("sched_tpot_refill_share", 100.0 * 6.0 / 20.0),
    ("step_prefill_wait_ms", 30.0),         # median of 10, 20, 30, 40, 50
    ("sched_stall_ms", 250.0),
    # 1 + 2 (the nested 0.5 inside it) + 1 + 4 (its fetch inside it)
    ("compile_setup_load_s", 8.0),
])
def test_reader_against_a_hand_count(metric, want, monkeypatch):
    run = _run(log=_log(), monkeypatch=monkeypatch)
    assert _read(metric, run) == pytest.approx(want, rel=1e-12)


def test_refill_share_leaves_out_the_burst_the_profiler_paused_in():
    """A traced run starts and stops the profiler inside ``on_token``,
    inside the first call's spans: that call's requests are not pooled."""
    traced = (103.0, 105.0, [(102.0, 103.0), (105.0, 105.5)])
    assert _read("sched_tpot_refill_share", _run(traced)) == pytest.approx(
        100.0 * 4.0 / 14.0, rel=1e-12)
    # pauses outside every call move nothing
    traced = (90.0, 95.0, [(89.0, 90.0), (95.0, 95.5)])
    assert _read("sched_tpot_refill_share", _run(traced)) == pytest.approx(
        30.0, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_reader_finds_nothing_in_a_program_without_the_record(
        metric, monkeypatch):
    """The parent of the PR that brought the record is measured with
    these files too: its metrics have none of the fields and its
    ``profiling`` no log, and the reader says so by returning nothing."""
    from mpi_acx_tpu import profiling
    monkeypatch.delattr(profiling, "program_log")
    bare = types.SimpleNamespace(rid=0, ttft_s=0.1, new_tokens=4,
                                 prefill_s=0.01)
    run = {"bursts": [types.SimpleNamespace(outs=types.SimpleNamespace(
        metrics=types.SimpleNamespace(per_request=[bare], call_s=1.0,
                                      phase_s={"chunk.step": 0.5})))],
           "traced": None,
           "window_watch": types.SimpleNamespace(_t0=100.0)}
    assert _read(metric, run) is None


def test_readers_on_a_real_serve_call_and_the_process_own_log():
    """A tiny GPT-2 through ``serve_paged_greedy`` on the CPU: the four
    readers read the program's own objects, the wait lies inside the
    prefill span, and the set-up's programs are seconds of the log that
    ended before the window's start."""
    import time

    import jax
    import numpy as np

    from mpi_acx_tpu import profiling
    from mpi_acx_tpu.models import serving
    from mpi_acx_tpu.models import transformer as tfm
    cfg = tfm.tiny_config(vocab=67, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_seq=64)
    params = tfm.init_params(jax.random.key(1), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 67, n).astype(np.int32)
               for n in (5, 9, 3, 12, 7)]
    t_before = time.perf_counter()

    def call():
        outs = serving.serve_paged_greedy(
            params, cfg, prompts, [6, 3, 9, 2, 5], n_slots=2, max_len=32,
            family=tfm, chunk=4, page_tokens=8)
        return types.SimpleNamespace(outs=outs)
    warm = call()
    watch = types.SimpleNamespace(_t0=time.perf_counter())
    run = {"bursts": [call(), call()], "traced": None,
           "window_watch": watch}
    share = _read("sched_tpot_refill_share", run)
    assert 0.0 < share < 100.0              # 2 slots, 5 requests: some wait
    wait = _read("step_prefill_wait_ms", run)
    spans = [r.prefill_s for b in run["bursts"]
             for r in b.outs.metrics.per_request]
    assert 0.0 < wait <= 1e3 * max(spans)
    assert all(r.prefill_wait_s <= r.prefill_s for b in run["bursts"]
               for r in b.outs.metrics.per_request)
    assert _read("sched_stall_ms", run) == pytest.approx(1e3 * sum(
        b.outs.metrics.stall_s for b in run["bursts"]))
    # the warm-up call loaded this configuration's programs inside its
    # spans; the window's calls loaded none
    loaded = [e for s in warm.outs.metrics.spans for e in s.programs]
    assert loaded and {e.kind for e in loaded} >= {"trace", "lower", "load"}
    assert not any(s.programs for b in run["bursts"]
                   for s in b.outs.metrics.spans)
    assert warm.outs.metrics.programs_traced > 0
    total = _read("compile_setup_load_s", run)
    mine = profiling.program_seconds(loaded)
    assert 0.0 < mine <= total + 1e-9
    assert mine <= watch._t0 - t_before


@pytest.mark.parametrize("metric", sorted(NEW))
def test_manifest_entry_of_a_record_metric(metric):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    unit, better, source, layer, moves = NEW[metric]
    assert entry == {"name": metric, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": entry["workloads"]}
    # ISSUE 37 lists further cells for three of them; the cells' own
    # tests (test_lfm2_cell.py, test_jamba_cell.py,
    # test_flash_attn_bwd_reader.py) hold those cells' per-layer sets to
    # exactly what they have, and this PR edits no benchmark file
    assert entry["workloads"] == XL
    for cell in entry["workloads"]:
        c = harness.Cell(cell)
        assert metric in [m["name"] for m in c.per_layer()]
        assert os.path.basename(c.reader_path(metric)) == metric + ".py"
    target, = [m for m in MANIFEST["end_to_end"] if m["name"] == moves]
    assert set(entry["workloads"]) <= set(target.get("workloads", XL))
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in NEW}
    assert layer in layers                   # no new layer is named


def test_the_new_entries_are_appended_and_the_old_ones_untouched():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[21:] == ["sched_tpot_refill_share", "step_prefill_wait_ms",
                          "sched_stall_ms", "compile_setup_load_s"]
    assert names[:21] == [
        "entry_first_token_ms", "sched_slot_occupancy",
        "sched_prefix_token_share", "step_decode_ms", "train_step_ms",
        "train_mfu", "compiles_in_window.serve", "compiles_in_window.train",
        "kernel_decode_attend_roofline", "kernel_flash_attn_roofline",
        "entry_setup_ms", "sched_queue_wait_p95_ms",
        "sched_step_utilization", "sched_host_share", "sched_refill_host_ms",
        "step_prefill_ms", "kernel_moe_experts_roofline",
        "step_moe_live_expert_share", "kernel_flash_attn_bwd_roofline",
        "kernel_ssm_update_roofline", "kernel_ssm_scan_roofline"]
    # the cells that do not list them report what they reported
    for cell, n in (("medium_train_1k", 5), ("lfm2_chat_burst", 6),
                    ("jamba2_reason_burst", 6)):
        assert len(harness.Cell(cell).per_layer()) == n


# -- benchmarks/record_report.py, rehearsed at a size a test run holds -----

@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """The benchmark's DATA with a dummy serving cell added as files and
    manifest entries, listed wherever ``xl_shared_prefix`` is."""
    import copy
    import json
    import shutil
    root = str(tmp_path_factory.mktemp("record_root"))
    here = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(here, d))
    man = copy.deepcopy(MANIFEST)
    config = harness.load_json(ROOT, "benchmarks", "configs",
                               "gpt2_xl_serve.json")
    config.update(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_inner=128,
        n_positions=128,
        serve=dict(n_slots=2, max_len=128, chunk=4, kv_int8=False,
                   page_tokens=16, n_pages=16, prefix_cache=True),
        check={"served_requests": 4, "kv_prompts": 2, "kv_pages": 2},
        limits={"widest_gap": 0.16, "kv_page_rms": 0.0041})
    mix = {"kind": "serve_bursts", "burst_requests": 6,
           "prefixes": {"count": 2, "tokens": 32, "zipf_s": 1.0},
           "body": {"dist": "uniform", "min": 3, "max": 20},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
           "total_max": 124, "pair_seed": 1,
           "warmup": [{"prefix": 0, "body": 5, "out": 5},
                      {"prefix": 0, "body": 9, "out": 5}]}
    for name, obj in (("configs/tiny_record", config),
                      ("traffic/tiny_record", mix)):
        with open(os.path.join(here, name + ".json"), "w") as f:
            json.dump(obj, f)
    man["configs"].append({
        "name": "tiny_record", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_record.json", "why": "test"})
    man["workloads"].append({
        "name": "tiny_record_cell", "config": "tiny_record",
        "traffic": "tiny_record", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "xl_shared_prefix" in m.get("workloads", []):
            m["workloads"].append("tiny_record_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return harness.Cell("tiny_record_cell", root=root, here=here)


def test_record_report_of_a_tiny_cell(tiny_cell):
    """The tool a builder runs on the chip, whole, on the CPU: the run is
    the benchmark's own (its line says ``correct``), the split's three
    shares are the whole, the record's TPOT is the benchmark's to within
    the deliver loop, every chunk of the window is listed, and the
    set-up's programs sum to the reader's number."""
    import json
    import time

    from benchmarks import record_report
    tables, line = record_report.report(
        tiny_cell, 2 ** 31 + 77, 0.5, time.perf_counter(),
        chip=lambda n: harness.describe_device())
    line = json.loads(line)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "ttft_p95_ms",
                                    "tpot_p95_ms", "setup_s"}
    split = tables["tpot_split"]
    assert split["requests"] > 0 and split["chunks_a_request"] >= 1
    assert (split["own_chunks_share"] + split["others_refills_share"]
            + split["rest_share"]) == pytest.approx(100.0)
    assert min(split["own_chunks_share"], split["others_refills_share"],
               split["rest_share"]) >= 0.0
    # a request's first token leaves inside refill.seat and its last
    # inside chunk.deliver: the record's interval ends at those spans'
    # ends, a deliver loop (well under a chunk) after the callback's
    assert split["record_tpot_p95_ms"] == pytest.approx(
        line["metrics"]["tpot_p95_ms"]["value"], rel=0.25)
    readers = tables["readers"]
    assert set(readers) == set(record_report.READERS)
    assert all(v is not None and v >= 0 for v in readers.values())
    assert readers["step_prefill_wait_ms"] <= readers["step_prefill_ms"]
    assert readers["sched_tpot_refill_share"] == pytest.approx(
        split["others_refills_share"])
    assert len(tables["chunk_steps"]) > 0
    assert all(0 < row["owned"] <= 2 for row in tables["chunk_steps"])
    assert sum(b["n"] for b in tables["prefill_buckets"].values()) \
        == line["attempted"]
    assert readers["sched_stall_ms"] == pytest.approx(sum(
        row["ms"] - row["median_ms"] for row in tables["stalls"]), abs=0.01)
    # a wait that loaded a program names it (on the CPU a tiny window may)
    assert all(kind in ("trace", "lower", "load", "fetch")
               for row in tables["stalls"] for _, kind, _ in row["programs"])
    setup = tables["setup_programs"]
    assert setup["seconds"] == pytest.approx(readers["compile_setup_load_s"])
    # (the log is the PROCESS's: in a run of the benchmark that is the
    # set-up's programs, here also whatever earlier tests loaded)
    assert setup["seconds"] > 0 and setup["entries"] > 0
    assert "paged_decode_chunk" in setup["by_name"]
    json.dumps(tables)                      # every table is plain data


def test_the_four_new_metrics_print_in_a_traced_line(tiny_cell,
                                                     monkeypatch):
    """``run.measure`` with ``--trace 1`` at the tiny cell: the CPU has
    no device line, so the trace's reduction is replaced and the readers
    of the device trace left out; the line carries the four metrics
    beside the accepted phase metrics, and the wait inside its span."""
    import copy
    import json
    import time

    import benchmarks.run as bench_run
    from benchmarks import trace_reduce
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d: {
        "busy_s": 0.0, "window_s": 1.0, "breakdown": {}})
    cell = copy.copy(tiny_cell)
    cell.manifest = dict(cell.manifest, per_layer=[
        m for m in cell.manifest["per_layer"]
        if m["source"] != "device_trace"])
    line = json.loads(bench_run.measure(
        cell, 7, 0.5, True, time.perf_counter(),
        chip=lambda n: dict(harness.describe_device(), kind="TPU v5 lite")))
    got = line["metrics"]
    assert line["correct"] is True
    assert set(NEW) <= set(got) and "step_prefill_ms" in got
    assert [got[n]["unit"] for n in sorted(NEW)] == ["s", "ms", "%", "ms"]
    assert (0 < got["step_prefill_wait_ms"]["value"]
            <= got["step_prefill_ms"]["value"])
    assert 0 <= got["sched_tpot_refill_share"]["value"] < 100
    assert got["compile_setup_load_s"]["value"] > 0
