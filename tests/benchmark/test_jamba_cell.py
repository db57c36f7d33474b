"""CPU tests of what PR 33 added to the benchmark: the Jamba
configuration and its cell ``jamba2_reason_burst`` (files only), the
traffic mix ``reason_burst_192``, the two new per-layer readers, the
counts of ``flops_jamba.py``, and a whole run of ``benchmarks/run.py``'s
``measure`` through the new entry at a tiny size: sound, and with the
timed path broken underneath (the scan state carried in bfloat16; the
``D u`` term left out), which has to come out as not correct. No device
metric is read here.
"""

import copy
import json
import os
import shutil
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops_jamba, harness, traffic, weights_jamba  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(ROOT, "benchmarks", "configs",
                           "jamba2_3b_serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "jamba2_reason_burst"


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, harness.Cell(CELL).reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the configuration and the cell ------------------------------------------

def test_config_is_the_catalog_rows_with_nothing_reduced():
    c = CONFIG
    entry = {e["name"]: e for e in MANIFEST["configs"]}["jamba2_3b_serve"]
    assert entry["reduced"] == c["reduced"] == []
    assert (c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"],
            c["num_key_value_heads"], c["intermediate_size"]) == (
                2560, 28, 20, 1, 8192)
    assert (c["mamba_expand"], c["mamba_d_state"], c["mamba_d_conv"],
            c["mamba_dt_rank"], c["vocab_size"]) == (2, 16, 4, 160, 65536)
    if os.path.isfile(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"AI21-Jamba2-3B"' in l)
        assert entry["source"] == c["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert c[key] == value, key
    assert {"layer_order", "head_dim", "init", "precision"} <= set(
        c["assumed"])
    assert c["weights_dtype"] == "bfloat16" and "one v5e chip" in c["deployment"]
    assert set(c["limits"]) == {"kv_page_rms", "ssm_state_rms",
                                "conv_tail_rms", "widest_gap", "mean_gap"}
    assert set(c["limits"]) < set(c["limits_from"])
    s = c["serve"]
    assert (s["n_slots"], s["max_len"], s["page_tokens"], s["chunk"],
            s["n_pages"], s["n_snapshots"], s["snapshot_every"]) == (
                128, 1280, 128, 32, 1280, 64, 4)
    assert s["kv_int8"] is False and s["prefix_cache"] is True
    # 128 slots x 10 pages can always be covered
    assert s["n_pages"] >= s["n_slots"] * s["max_len"] // s["page_tokens"]


def test_counts_of_the_issue():
    """3,029 M parameters, the whole model; 9,318,400 B of state a slot;
    the benchmark's weights and the program's spec say the same."""
    assert flops_jamba.n_params(CONFIG) == 3_029_337_472
    assert weights_jamba.n_params(CONFIG) == flops_jamba.n_params(CONFIG)
    assert flops_jamba.n_mamba_layers(CONFIG) == 26
    assert flops_jamba.state_bytes_layer(CONFIG) == (327_680, 30_720)
    assert flops_jamba.state_bytes_slot(CONFIG) == 9_318_400
    kinds = weights_jamba.layer_kinds(CONFIG)
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    from benchmarks.entries import serve_paged_greedy_jamba as entry
    from mpi_acx_tpu.models import jamba, kvpage
    cfg = entry.program_config(CONFIG, "bfloat16")
    spec = kvpage.paged_spec(jamba, cfg)
    assert spec.state_bytes_slot == 9_318_400
    assert (spec.n_page_layers, spec.n_state_layers, spec.n_rep,
            spec.snapshot_every) == (2, 26, 20, 4)
    # the program finds the stretches the benchmark's weights are laid in
    assert [(len(p), r) for _, p, r in weights_jamba.stretches(CONFIG)] == [
        (len(s.period), s.repeats) for s in spec.segments]


def test_scan_work_against_hand_counts():
    c = {"hidden_size": 2, "mamba_expand": 2, "mamba_d_state": 3,
         "mamba_d_conv": 4, "attn_layer_period": 2, "attn_layer_offset": 1,
         "num_hidden_layers": 4}
    assert flops_jamba.channels(c) == 4 and flops_jamba.n_mamba_layers(c) == 2
    assert flops_jamba.state_bytes_layer(c) == (4 * 3 * 4, 3 * 4 * 2)
    assert flops_jamba.ssm_update_work(c, slots=5) == {
        "bytes": 2 * 5 * 48, "vector_ops": 7 * 60, "exps": 60}
    # 6 tokens x (4 channels x 10 B + 2 x 3 x 4 B) + (h0, end, 1 snapshot)
    assert flops_jamba.ssm_scan_work(c, tokens=6, snapshots=1) == {
        "bytes": 6 * (40 + 24) + 3 * 48, "vector_ops": 7 * 72, "exps": 72}
    # the issue's figures: 2.39 GB of state a step at 128 slots
    step = 26 * flops_jamba.ssm_update_work(CONFIG, 128)["bytes"]
    assert step == 26 * 128 * 2 * 327_680 and abs(step - 2.18e9) < 0.01e9


def test_cell_reports_the_metrics_the_issue_lists():
    cell = harness.Cell(CELL)
    assert cell.cell["chips"] == 1
    assert cell.cell["traffic"] in ("reason_burst_192", "reason_burst_256")
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "entry_first_token_ms", "sched_slot_occupancy",
        "sched_prefix_token_share", "compiles_in_window.serve",
        "kernel_ssm_update_roofline", "kernel_ssm_scan_roofline"}
    for m in cell.per_layer():
        assert m["moves"] in {e["name"] for e in cell.end_to_end()}, m
    new = {m["name"]: m for m in MANIFEST["per_layer"]
           if m["name"].startswith("kernel_ssm_")}
    assert all(m["workloads"] == [CELL] and m["layer"] == "Kernels"
               and m["source"] == "device_trace" for m in new.values())
    assert (new["kernel_ssm_update_roofline"]["moves"],
            new["kernel_ssm_scan_roofline"]["moves"]) == ("serve_tok_s",
                                                          "ttft_p95_ms")


def test_traffic_file_is_the_issues_and_its_warmup_covers_what_it_reaches():
    cell = harness.Cell(CELL)
    t, s = cell.traffic, CONFIG["serve"]
    chat = harness.load_json(ROOT, "benchmarks", "traffic", "chat_burst.json")
    assert t["kind"] == "serve_bursts" and t["prefixes"] is None
    assert t["body"] == chat["body"]
    assert t["output"] == {"dist": "lognormal", "median": 160, "sigma": 0.6,
                           "min": 48, "max": 512}
    assert (t["total_max"], t["pair_seed"]) == (1248, 33)
    shape = traffic.burst_shape(t)
    assert (len(shape), sum(b for _, b, _ in shape),
            sum(o for _, _, o in shape)) in ((192, 59317, 36077),
                                             (256, 79090, 48104))
    assert cell.cell["traffic"] == f"reason_burst_{len(shape)}"
    assert all(32 <= b <= 768 and 48 <= o <= 512 for _, b, o in shape)
    assert max(b + o for _, b, o in shape) + s["chunk"] <= s["max_len"]
    # (bucket, pages) classes of a cold prefill: power-of-two bucket,
    # 128-token pages; they fix the scatter's snapshot rows too
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    cls = lambda n: (bucket(n), -(-n // 128))
    reach = {cls(b) for _, b, _ in shape}
    warm = {cls(w["body"]) for w in t["warmup"]}
    assert reach == warm and len(t["warmup"]) == len(warm) == 7
    assert {b for b, _ in reach} == {64, 128, 256, 512, 1024}
    if len(shape) == 192:   # prompts that reach a 512-token snapshot
        assert sum(b >= 512 for _, b, _ in shape) == 31


# -- the readers --------------------------------------------------------------

def _traced_run(update_calls, scan_calls, chunks_inside=1, bucket=8,
                snaps=1, seconds_each=1e-3):
    """A hand-made traced run of a tiny geometry: 2 Mamba layers of 4
    channels x 3 numbers, 5 slots, chunk 2."""
    c = {"hidden_size": 2, "mamba_expand": 2, "mamba_d_state": 3,
         "mamba_d_conv": 4, "attn_layer_period": 3, "attn_layer_offset": 1,
         "num_hidden_layers": 3, "serve": {"n_slots": 5, "chunk": 2}}
    ops, t = [], 0.0

    def call(text):
        nonlocal t
        ops.append((text, t, seconds_each * 1e9))
        t += 2 * seconds_each * 1e9
    for _ in range(update_calls):
        call('%ssm_update.3 = (f32[5,4]{1,0}, f32[2,5,3,4]{3,2,1,0}) '
             'custom-call(), custom_call_target="tpu_custom_call"')
    result = f"f32[{bucket},4]{{1,0}}, " + (
        # (the kernel hands out one more snapshot row than it keeps)
        f"f32[{snaps + 1},3,4]{{2,1,0}}, " if snaps else "") + "f32[3,4]{1,0}"
    for n in range(scan_calls):
        # as the chip's trace names it: the Mosaic call, or the fusion
        # XLA wraps it and the slice of its snapshot rows in
        call(f'%ssm_scan.7 = ({result}) custom-call(), '
             'custom_call_target="tpu_custom_call"' if n % 2 else
             f'%ssm_scan.8 = ({result}) fusion(f32[3,4]{{1,0}} %p), '
             'kind=kCustom, calls=%fused_ssm_scan')
    call("%fusion.1 = f32[5,4]{1,0} fusion()")     # not a kernel's
    trace = {"devices": {"/device:TPU:0": ops}, "host": []}
    from benchmarks import trace_reduce
    reduced = dict(trace_reduce.reduce(trace), trace=trace)
    deliveries = ([(0.5, [(0, 1)])]
                  + [(1.1 + 0.1 * i, [(0, 2 + i)])
                     for i in range(chunks_inside)] + [(2.5, [(0, 9)])])
    log = types.SimpleNamespace(deliveries=lambda pauses=(): deliveries)
    return {"traced": (1.0, 2.0, []), "config": c, "reduced": reduced,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "bursts": [types.SimpleNamespace(log=log)]}


def test_ssm_update_reader_on_hand_made_runs():
    read = _reader("kernel_ssm_update_roofline").read
    # one chunk of 2 steps x 2 Mamba layers = 4 calls, each moving
    # 5 slots x 2 x 48 B = 480 B: 1,920 us of bytes over 4 ms of events
    assert read(_traced_run(4, 0)) == pytest.approx(100 * 1920e-6 / 4e-3)
    assert read(_traced_run(8, 2, chunks_inside=2)) == pytest.approx(48.0)
    # calls that are not chunks x chunk x Mamba layers: no attribution
    assert read(_traced_run(3, 0)) is None
    assert read(_traced_run(8, 0)) is None
    # no such call (the parent; a fallback to plain JAX); an untraced run
    assert read(_traced_run(0, 2)) is None
    assert read(dict(_traced_run(4, 0), traced=None)) is None


def test_ssm_scan_reader_on_hand_made_runs():
    read = _reader("kernel_ssm_scan_roofline").read
    # one refill = 2 calls over a bucket of 8 with one snapshot:
    # 8 x (4 x 10 + 24) + 3 x 48 = 656 B each
    assert read(_traced_run(4, 2)) == pytest.approx(100 * 2 * 656e-6 / 2e-3)
    # a bucket without snapshots: 8 x 64 + 2 x 48 = 608 B
    assert read(_traced_run(4, 2, snaps=0)) == pytest.approx(
        100 * 2 * 608e-6 / 2e-3)
    assert read(_traced_run(4, 4, bucket=16)) == pytest.approx(
        100 * 4 * (16 * 64 + 144) * 1e-6 / 4e-3)
    # not a whole multiple of the Mamba layers; no such call; untraced
    assert read(_traced_run(4, 3)) is None
    assert read(_traced_run(4, 0)) is None
    assert read(dict(_traced_run(4, 2), traced=None)) is None


# -- a whole run through the new entry, tiny, files only ---------------------

TINY = dict(vocab_size=96, hidden_size=64, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=1,
            intermediate_size=96, attn_layer_period=4, attn_layer_offset=1,
            mamba_d_state=8, mamba_dt_rank=8, max_position_embeddings=256,
            weights_dtype="float32", init_scale=0.125)
# The tiny configuration's own limits. In float32 the program and the
# reference differ by summation order alone: pages, state and window
# read 1e-7..1e-6, served tokens' gaps 0; a scan state rounded to
# bfloat16 at every step reads ~3e-3 in ``ssm_state_rms``, the ``D u``
# term left out moves logits by ~1.
TINY_LIMITS = {"widest_gap": 1e-3, "mean_gap": 1e-3, "kv_page_rms": 1e-4,
               "ssm_state_rms": 1e-4, "conv_tail_rms": 1e-4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's worth of benchmark DATA with a dummy Jamba serving
    cell added as files and manifest entries only; the code that runs it
    is the repo's, unchanged."""
    root = str(tmp_path_factory.mktemp("bench_root_jamba"))
    here = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(here, d))
    man = copy.deepcopy(MANIFEST)
    base = dict(CONFIG, **TINY)
    base["serve"] = dict(n_slots=2, max_len=128, chunk=4, kv_int8=False,
                         page_tokens=16, n_pages=24, prefix_cache=True,
                         n_snapshots=6, snapshot_every=2)
    base["check"] = {"served_requests": 4, "kv_prompts": 2, "kv_pages": 2,
                     "served_rows": 12}
    base["limits"] = TINY_LIMITS
    mix = {"kind": "serve_bursts", "burst_requests": 6, "prefixes": None,
           "body": {"dist": "uniform", "min": 33, "max": 60},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
           "total_max": 124, "pair_seed": 1,
           "warmup": [{"prefix": None, "body": 40, "out": 5},
                      {"prefix": None, "body": 60, "out": 5}]}
    for name, obj in (("configs/tiny_jamba", base),
                      ("traffic/tiny_jamba", mix)):
        with open(os.path.join(here, name + ".json"), "w") as f:
            json.dump(obj, f)
    man["configs"].append({
        "name": "tiny_jamba", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_jamba.json", "why": "test"})
    man["workloads"].append({
        "name": "tiny_jamba_cell", "config": "tiny_jamba",
        "traffic": "tiny_jamba", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_jamba_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _measure(root, seed):
    import benchmarks.run as bench_run
    cell = harness.Cell("tiny_jamba_cell", root=root,
                        here=os.path.join(root, "benchmarks"))
    line = bench_run.measure(cell, seed, 0.3, False, time.perf_counter(),
                             chip=lambda n: harness.describe_device())
    return json.loads(line)


def _checks(capsys):
    return {c["name"]: c for c in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if '"check"' in l))}


def test_the_jamba_cell_added_as_files_only_runs_and_is_correct(tiny_root,
                                                                capsys):
    line = _measure(tiny_root, seed=2 ** 31 + 77)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and set(line["metrics"]) == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"      # named, never hidden
    checks = _checks(capsys)
    assert {"kv_page_rms", "ssm_state_rms", "conv_tail_rms", "widest_gap",
            "mean_gap", "prefix_hits_and_restores_with_nothing_shared"} <= set(
                checks)
    assert all(c["ok"] for c in checks.values())


@pytest.mark.parametrize("control,fails,holds", [
    ("bf16_h", {"ssm_state_rms"}, {"conv_tail_rms"}),
    ("no_D_u", {"widest_gap", "mean_gap"}, {"ssm_state_rms",
                                            "conv_tail_rms"}),
])
def test_a_broken_scan_underneath_the_timed_path_is_not_correct(
        tiny_root, capsys, monkeypatch, control, fails, holds):
    """The configuration states a float32 scan state: carried in
    bfloat16 (rounded after every token, as the nearest precision below
    would) the first layer's snapshot is off the reference's by thirty
    times the limit; its conv window, which lies before the scan, is
    not. The ``D u`` term left out is part of the mathematics left out:
    the first layer's state is the reference's, the served tokens are
    not. (What lies behind the first Mamba layer, the pages among it,
    sees either.)"""
    import jax
    from benchmarks import control_jamba
    monkeypatch.setattr("mpi_acx_tpu.ops.ssm._token",
                        control_jamba.BROKEN[control])
    jax.clear_caches()
    try:
        line = _measure(tiny_root, seed=5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert line["correct"] is False and line["failed"] == 0
    checks = _checks(capsys)
    assert not any(checks[name]["ok"] for name in fails)
    assert all(checks[name]["ok"] for name in holds | {
        "failed_requests", "requeues_rejections_preemptions",
        "prefix_hits_and_restores_with_nothing_shared"})
