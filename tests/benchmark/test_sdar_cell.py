"""CPU tests of what PR 48 added to the benchmark: the SDAR-MoE
configuration and its cell ``sdar_chat_block_burst`` (files only), the
traffic mix ``chat_block_burst_192``, the four new per-layer readers,
the expert layer's and the block attend's operation and byte counts, the
tracer for a family whose first token arrives with a chunk, and a whole
run of ``benchmarks/run.py``'s ``measure`` through the new entry at a
tiny size: sound, and with the timed path broken underneath (the storing
forward's K/V replaced by the last denoising forward's; a causal mask
inside the block), which has to come out as not correct. No device
metric is read here. Nothing about the manifest's tail or length is
pinned with ``==``: membership only.
"""

import copy
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops_sdar, harness, traffic, weights_sdar  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
CONFIG = harness.load_json(ROOT, "benchmarks", "configs",
                           "sdar_30b_a3b_pp8_serve.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "sdar_chat_block_burst"


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, harness.Cell(CELL).reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the configuration and the cell ------------------------------------------

def test_config_keeps_every_published_width_and_lists_what_it_reduced():
    c = CONFIG
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (2048, 32, 4, 128)
    assert (c["moe_intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["norm_topk_prob"]) == (768, 128, 8,
                                                               True)
    assert (c["vocab_size"], c["rms_norm_eps"], c["rope_theta"]) == (
        151936, 1e-6, 1000000)
    entry = {e["name"]: e for e in MANIFEST["configs"]}[
        "sdar_30b_a3b_pp8_serve"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 6
    assert c["published"]["num_hidden_layers"] == 48
    for key in ("deployment", "generation", "assumed", "limits",
                "limits_from", "check", "serve"):
        assert key in c, key
    assert c["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_static", "mask_token_id": 151669,
        "greedy": True}
    for key in ("block_length", "denoising_steps", "remasking",
                "mask_token_id", "masked", "init", "precision"):
        assert key in c["assumed"], key
    if os.path.isfile(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"SDAR-30B-A3B-Chat"' in l)
        assert c["source"] == row["source_url"]
        assert entry["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert c[key] == value, key


def test_cut_arithmetic_of_the_issue():
    """4,361,055,744 parameters = 8.72 GB in bf16; a layer 623,120,640,
    of which the experts 603,979,776; an expert 4,718,592."""
    assert weights_sdar.n_params(CONFIG) == 4_361_055_744
    layer = sum(int(np.prod(s)) for s, _ in
                weights_sdar.layer_shapes(CONFIG).values())
    assert layer == 623_120_640
    assert flops_sdar.expert_bytes(CONFIG) == 2 * 4_718_592
    assert 128 * 4_718_592 == 603_979_776
    assert weights_sdar.n_params(dict(CONFIG, num_hidden_layers=48)) \
        == 48 * layer + 2 * 151936 * 2048 + 2048       # 30.5 B
    # pages: 2 KB a token a layer
    assert flops_sdar.page_bytes(CONFIG) == 128 * 2048
    s = CONFIG["serve"]
    assert s["n_pages"] == s["n_slots"] * s["max_len"] // s["page_tokens"]
    assert s["chunk"] % CONFIG["generation"]["block_length"] == 0
    assert s["page_tokens"] % CONFIG["generation"]["block_length"] == 0


def test_cell_reports_the_metrics_the_issue_lists():
    cell = harness.Cell(CELL)
    assert cell.cell["chips"] == 1
    assert cell.cell["config"] == "sdar_30b_a3b_pp8_serve"
    assert len(cell.cell["why"]) <= 200
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer()}
    assert per_layer == {
        "entry_first_token_ms", "sched_slot_occupancy",
        "sched_prefix_token_share", "compiles_in_window.serve",
        "kernel_moe_block_experts_roofline", "kernel_block_attend_roofline",
        "step_store_forward_share", "sched_block_token_share"}
    for m in cell.per_layer():
        assert m["moves"] in {e["name"] for e in cell.end_to_end()}, m
        cell.reader_path(m["name"])         # every one has its reader
    new = {m["name"]: m for m in MANIFEST["per_layer"]
           if m.get("workloads") == [CELL]}
    assert set(new) == {
        "kernel_moe_block_experts_roofline", "kernel_block_attend_roofline",
        "step_store_forward_share", "sched_block_token_share"}
    assert all(m["unit"] == "%" and m["moves"] == "serve_tok_s"
               for m in new.values())
    assert new["step_store_forward_share"]["better"] == "lower"


def test_traffic_file_is_the_issues_letter_for_letter():
    t = harness.load_json(ROOT, "benchmarks", "traffic",
                          harness.Cell(CELL).cell["traffic"] + ".json")
    chat = harness.load_json(ROOT, "benchmarks", "traffic", "chat_burst.json")
    assert t["kind"] == "serve_bursts"
    assert t["burst_requests"] in (160, 192, 224, 256)
    assert t["prefixes"] is None and t["body"] == chat["body"]
    assert t["body"] == {"dist": "lognormal", "median": 256, "sigma": 0.7,
                         "min": 32, "max": 768}
    assert t["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.6,
                           "min": 32, "max": 512}
    assert (t["total_max"], t["pair_seed"]) == (1280, 48)


def test_warmup_touches_every_prefill_shape_the_mix_can_reach():
    """(bucket, pages) classes of serve_paged_greedy's cold prefill of a
    block family: the bucket of the prompt's WHOLE BLOCKS (a power of
    two), the pages of the whole prompt; every class a body of 32-768
    tokens can fall in, whichever the quantile grid of a burst holds."""
    t = harness.load_json(ROOT, "benchmarks", "traffic",
                          harness.Cell(CELL).cell["traffic"] + ".json")
    W = CONFIG["generation"]["block_length"]
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    cls = lambda n: (bucket(n - n % W), -(-n // 128))
    reach = {cls(n) for n in range(t["body"]["min"], t["body"]["max"] + 1)}
    assert {cls(b) for _, b, _ in traffic.burst_shape(t)} <= reach
    warm = {cls(w["body"]) for w in t["warmup"]}
    assert reach == warm and len(t["warmup"]) == len(warm)
    s = CONFIG["serve"]
    assert all(w["body"] + w["out"] + s["chunk"] <= s["max_len"]
               for w in t["warmup"])
    # the admission rule seats every request: prompt + output + chunk
    assert max(b + o for _, b, o in traffic.burst_shape(t)) + s["chunk"] \
        <= s["max_len"]


# -- the counting functions and the readers ----------------------------------

def test_work_against_hand_counts():
    c = {"hidden_size": 4, "moe_intermediate_size": 3,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "generation": {"block_length": 2}, "serve": {"page_tokens": 16}}
    assert flops_sdar.expert_bytes(c) == 3 * 4 * 3 * 2 == 72
    assert flops_sdar.pair_flops(c) == 72
    assert flops_sdar.moe_work(c, experts_live=5, pairs=7) == (504, 360)
    assert flops_sdar.page_bytes(c) == 2 * 2 * 8 * 16 * 2 == 1024
    # 3 pages walked, 2 forwards of a live block: 3 x 16 + 2 x 2 keys met
    # by 2 positions x 4 heads of 8; 3 pages + 2 x 2 rows of K and V
    ops, nbytes = flops_sdar.block_attend_work(c, 3, 2)
    assert ops == 2 * 2 * 2 * 4 * 8 * (48 + 4)
    assert nbytes == 3 * 1024 + 2 * 2 * 2 * 2 * 8 * 2
    # the issue's figures: 9.4 MB an expert in bf16, 28.3 MFLOP a pair
    assert flops_sdar.expert_bytes(CONFIG) == 9_437_184
    assert flops_sdar.pair_flops(CONFIG) == 9_437_184


def _burst(metrics, events):
    log = types.SimpleNamespace(events=events)
    return types.SimpleNamespace(
        log=log, outs=types.SimpleNamespace(
            metrics=types.SimpleNamespace(**metrics)))


def test_chunk_deliveries_count_a_requests_first_token():
    from benchmarks.entries.serve_paged_greedy_sdar import chunk_deliveries
    events = [(0.50, 0, 0), (0.5001, 0, 1), (0.5002, 1, 0),
              (1.50, 0, 2), (1.5001, 1, 1),
              (2.80, 1, 2)]         # 0.3 s of it inside the profiler
    log = types.SimpleNamespace(events=events)
    assert chunk_deliveries(log) == [
        (0.50, [(0, 0), (0, 1), (1, 0)]), (1.50, [(0, 2), (1, 1)]),
        (2.80, [(1, 2)])]
    # a pause of the callback itself does not split a delivery
    events = [(1.0, 0, 0), (1.4, 0, 1), (2.5, 0, 2)]
    log = types.SimpleNamespace(events=events)
    assert len(chunk_deliveries(log)) == 3
    assert len(chunk_deliveries(log, pauses=[(1.0, 1.399)])) == 2


def test_the_two_counter_readers_on_hand_made_counters():
    store = _reader("step_store_forward_share").read
    share = _reader("sched_block_token_share").read
    a = _burst(dict(forwards_denoise=16, forwards_store=4,
                    block_by_chunk=[(8, 2, 24, 20, 4, 0, 30),
                                    (8, 2, 24, 4, 0, 20, 9)]), [])
    b = _burst(dict(forwards_denoise=8, forwards_store=2,
                    block_by_chunk=[(8, 2, 24, 12, 0, 12, 9)]), [])
    assert store({"bursts": [a, b]}) == pytest.approx(20.0)
    assert share({"bursts": [a]}) == pytest.approx(100.0 * 24 / 48)
    assert share({"bursts": [a, b]}) == pytest.approx(100.0 * 36 / 72)
    # a program without the counters (the parent; a token family):
    # nothing to read
    for read in (store, share):
        assert read({"bursts": [_burst({}, [])]}) is None
        assert read({"bursts": [_burst(dict(
            forwards_denoise=0, forwards_store=0, block_by_chunk=[]),
            [])]}) is None


TINY_C = {"hidden_size": 4, "moe_intermediate_size": 3, "num_experts": 8,
          "num_experts_per_tok": 2, "num_hidden_layers": 3,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
          "generation": {"block_length": 2, "denoising_steps": 2},
          "serve": {"n_slots": 5, "chunk": 4, "page_tokens": 16}}


def _traced_run(moe_calls, attend_calls, counters, seconds_each=1e-3):
    """A hand-made traced run of the tiny geometry above: the second of
    three chunks delivered inside the traced window, the kernels' events
    on one device plane. A chunk of 4 positions is 2 blocks of 2 + 1
    forwards: 6 forwards x 3 layers."""
    ops, t = [], 0.0

    def event(text):
        nonlocal t
        ops.append((text, t, seconds_each * 1e9))
        t += 2 * seconds_each * 1e9
    for width, n in zip((3, 4), moe_calls):
        for _ in range(n):
            event(f'%gmm.1 = f32[20,{width}]{{1,0}} custom-call(), '
                  'custom_call_target="tpu_custom_call"')
    for _ in range(attend_calls):
        event('%paged_flash_decode_attend.3 = bf16[5,2,4,8]{3,2,1,0} '
              'custom-call(), custom_call_target="tpu_custom_call"')
    # operations that are not the kernels': another shape, another name
    event('%paged_flash_decode_attend.9 = bf16[5,2,2,8]{3,2,1,0} '
          'custom-call(), custom_call_target="tpu_custom_call"')
    event("%fusion.1 = f32[20,4]{1,0} fusion()")
    trace = {"devices": {"/device:TPU:0": ops}, "host": []}
    from benchmarks import trace_reduce
    reduced = dict(trace_reduce.reduce(trace), trace=trace)
    events = [(0.5, 0, 0), (1.5, 0, 1), (2.5, 0, 2)]
    return {"traced": (1.0, 2.0, []), "config": TINY_C, "reduced": reduced,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "bursts": [_burst(counters, events)]}


def test_moe_block_roofline_reader_on_hand_made_runs():
    read = _reader("kernel_moe_block_experts_roofline").read
    # 6 forwards x 3 layers x 3 matmuls = 54 calls: 36 up, 18 down
    chunks = [(999, 99, 0, 18, 0), (12, 6, 4, 18, 0), (999, 99, 0, 18, 0)]
    run = _traced_run((36, 18), 0, {"moe_by_chunk": chunks})
    # the SECOND chunk is the traced one: 6 live experts x 72 B = 432 us
    # at 1e6 B/s, 12 pairs x 72 ops = 864 us: compute bounds; 54 ms
    assert read(run) == pytest.approx(100.0 * 864e-6 / 54e-3)
    assert read(_traced_run((36, 17), 0, {"moe_by_chunk": chunks})) is None
    assert read(_traced_run((0, 0), 0, {"moe_by_chunk": chunks})) is None
    # a program without the counters, a chunk without its counters, an
    # untraced run: nothing
    assert read(_traced_run((36, 18), 0, {})) is None
    assert read(_traced_run((36, 18), 0,
                            {"moe_by_chunk": chunks[:1]})) is None
    assert read(dict(run, traced=None)) is None


def test_block_attend_roofline_reader_on_hand_made_runs():
    read = _reader("kernel_block_attend_roofline").read
    # (denoise, store, positions, delivered, kept, dead, pages walked)
    chunks = [(4, 2, 20, 0, 0, 20, 999), (4, 2, 20, 10, 2, 8, 30),
              (4, 2, 20, 0, 0, 20, 999)]
    run = _traced_run((0, 0), 18, {"block_by_chunk": chunks})
    # 12 live positions = 6 live blocks x 3 forwards = 18 block-forwards;
    # one layer: 30 pages x 1024 B + 18 x 2 rows x 64 B = 33,024 B;
    # three layers at 1e6 B/s = 99.072 ms over the kernel's 18 ms?! no:
    # the hand-made peak makes memory the bound, the share is what it is
    ops, nbytes = flops_sdar.block_attend_work(TINY_C, 30, 18)
    want = 100.0 * max(3 * ops / 1e6, 3 * nbytes / 1e6) / 18e-3
    assert nbytes == 30 * 1024 + 18 * 2 * 2 * 2 * 8 * 2
    assert read(run) == pytest.approx(want)
    assert read(_traced_run((0, 0), 17, {"block_by_chunk": chunks})) is None
    assert read(_traced_run((0, 0), 18, {})) is None
    assert read(dict(run, traced=None)) is None


def test_block_tracer_takes_exactly_the_chunk_behind_the_last_first_token(
        monkeypatch):
    from benchmarks.entries import serve_paged_greedy_sdar as entry
    marks = []
    monkeypatch.setattr(harness, "start_trace",
                        lambda logdir: marks.append("start") or "span")
    monkeypatch.setattr(harness, "stop_trace",
                        lambda span: marks.append("stop"))
    now = [0.0]
    monkeypatch.setattr(entry.time, "perf_counter", lambda: now[0])
    tr = entry.BlockTracer("nowhere")
    log = harness.TokenLog(2, 0.0, clock=lambda: now[0])

    def token(t, rid):
        now[0] = t
        log.on_token(rid, 7)
        tr.tick(log, rid)
    token(1.0, 0), token(1.0001, 0)             # request 1 has none yet
    assert marks == []
    token(2.0, 0), token(2.0001, 1)             # now every request has one
    assert marks == ["start"]
    token(2.0002, 1), token(2.0003, 0)          # the same delivery goes on
    assert marks == ["start"] and tr.state == "on"
    token(3.0, 0)                               # the next chunk's first
    assert marks == ["start", "stop"] and tr.state == "done"
    token(4.0, 1)
    assert marks == ["start", "stop"]
    assert tr.t0 == 2.0001 and tr.t1 == 3.0


# -- a whole run through the new entry, tiny, files only ---------------------

TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
            num_experts=8, num_experts_per_tok=2, num_hidden_layers=3,
            max_position_embeddings=256, weights_dtype="float32",
            init_scale=0.125)   # 1 / sqrt(d): the layers decide the logits
# The tiny configuration's own limits. In float32 the program and the
# reference differ by summation order alone: pages read ~1e-6, served
# tokens' gaps and the commit order's 0 but for a near-tie; K/V stored
# from the last denoising forward read ~0.3, a causal mask inside the
# block moves logits by ~0.1.
TINY_LIMITS = {"kv_page_rms": 1e-4, "kv_deep_rms": 1e-4, "widest_gap": 1e-3,
               "mean_gap": 1e-4, "order_gap": 1e-4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's worth of benchmark DATA with a dummy SDAR serving
    cell added as files and manifest entries only; the code that runs it
    is the repo's, unchanged."""
    root = str(tmp_path_factory.mktemp("bench_root_sdar"))
    here = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(here, d))
    man = copy.deepcopy(MANIFEST)
    base = dict(CONFIG, **TINY)
    base["generation"] = dict(CONFIG["generation"], mask_token_id=95)
    base["serve"] = dict(n_slots=3, max_len=128, chunk=8, kv_int8=False,
                         page_tokens=16, n_pages=24, prefix_cache=True)
    base["check"] = {"served_requests": 3, "block_states": 6}
    base["limits"] = TINY_LIMITS
    mix = {"kind": "serve_bursts", "burst_requests": 6, "prefixes": None,
           "body": {"dist": "uniform", "min": 17, "max": 60},
           "output": {"dist": "lognormal", "median": 9, "sigma": 0.5,
                      "min": 3, "max": 20},
           "total_max": 120, "pair_seed": 1,
           "warmup": [{"prefix": None, "body": 30, "out": 5},
                      {"prefix": None, "body": 60, "out": 5}]}
    for name, obj in (("configs/tiny_sdar", base), ("traffic/tiny_sdar", mix)):
        with open(os.path.join(here, name + ".json"), "w") as f:
            json.dump(obj, f)
    man["configs"].append({
        "name": "tiny_sdar", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_sdar.json", "why": "test"})
    man["workloads"].append({
        "name": "tiny_sdar_cell", "config": "tiny_sdar",
        "traffic": "tiny_sdar", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_sdar_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _measure(root, seed):
    import benchmarks.run as bench_run
    cell = harness.Cell("tiny_sdar_cell", root=root,
                        here=os.path.join(root, "benchmarks"))
    line = bench_run.measure(cell, seed, 0.3, False, time.perf_counter(),
                             chip=lambda n: harness.describe_device())
    return json.loads(line)


def _checks(capsys):
    return {c["name"]: c for c in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if '"check"' in l))}


def test_the_sdar_cell_added_as_files_only_runs_and_is_correct(tiny_root,
                                                               capsys):
    line = _measure(tiny_root, seed=2 ** 31 + 77)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and set(line["metrics"]) == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"      # named, never hidden
    out = capsys.readouterr().out
    checks = {c["name"]: c for c in map(json.loads, (
        l for l in out.splitlines() if '"check"' in l))}
    assert {"kv_page_rms", "kv_deep_rms", "widest_gap", "mean_gap",
            "order_gap", "prefix_hits_with_nothing_shared"} <= set(checks)
    assert all(c["ok"] for c in checks.values())
    window = next(json.loads(l) for l in out.splitlines()
                  if '"window"' in l)
    assert window["store_forward_share"] == pytest.approx(0.2)
    assert window["positions"] == (window["delivered"] + window["kept"]
                                   + window["dead"])
    assert window["tokens"] == window["delivered"]
    compared = next(json.loads(l) for l in out.splitlines()
                    if '"compared"' in l)
    # prompt pages and the pages of generated blocks both compared
    assert compared["generated"] > 0
    assert compared["positions"] > compared["generated"]
    assert compared["tokens"] >= compared["block_states"] > 0


def _broken(monkeypatch, tiny_root, capsys, patch):
    import jax
    patch()
    jax.clear_caches()
    try:
        line = _measure(tiny_root, seed=5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert line["failed"] == 0
    return line, _checks(capsys)


def test_kv_of_the_last_denoising_forward_is_not_correct(
        tiny_root, capsys, monkeypatch):
    """The fifth forward left out: what the stage keeps of a block is
    what its LAST DENOISING forward wrote, when a position still held
    the mask token. The tokens are what they were; the pages of the
    generated blocks are not the finished sequence's."""
    from benchmarks import control_sdar
    line, checks = _broken(
        monkeypatch, tiny_root, capsys,
        lambda: control_sdar.break_program(monkeypatch.setattr, "no_store"))
    assert line["correct"] is False
    assert not checks["kv_deep_rms"]["ok"]


def test_a_causal_mask_inside_the_block_is_not_correct(
        tiny_root, capsys, monkeypatch):
    from benchmarks import control_sdar
    line, checks = _broken(
        monkeypatch, tiny_root, capsys,
        lambda: control_sdar.break_program(monkeypatch.setattr,
                                           "causal_block"))
    assert line["correct"] is False
    assert checks["kv_page_rms"]["ok"]      # the first layer sees no mask
    assert not (checks["kv_deep_rms"]["ok"] and checks["mean_gap"]["ok"])


@pytest.mark.parametrize("leg", ["causal_prefill", "drop_expert",
                                 "plain_softmax"])
def test_the_other_broken_legs_move_the_numbers_they_should(
        tiny_root, capsys, monkeypatch, leg):
    from benchmarks import control_sdar
    line, checks = _broken(
        monkeypatch, tiny_root, capsys,
        lambda: control_sdar.break_program(monkeypatch.setattr, leg))
    assert line["correct"] is False
    assert not all(checks[n]["ok"] for n in (
        "kv_page_rms", "kv_deep_rms", "widest_gap", "mean_gap", "order_gap"))
