"""CPU tests of what PR 40 added to the benchmark: the GigaChat3
configuration and its cell ``gigachat_doc_qa_burst`` (files only), the
traffic mix ``doc_qa_burst_128``, the four new per-layer readers, the
counts of ``flops_gigachat.py``, and a whole run of
``benchmarks/run.py``'s ``measure`` through the new entry at a tiny
size: sound, and with the timed path broken underneath (a held expert
left out, the shared expert left out, plain top-k in place of the
group-limited selection), which has to come out as not correct. No
device metric is read here.
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

from benchmarks import flops_gigachat, harness, traffic  # noqa: E402
from benchmarks import weights_gigachat  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
NAME = "gigachat31_702b_ep16_serve"
CONFIG = harness.load_json(ROOT, "benchmarks", "configs", NAME + ".json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "gigachat_doc_qa_burst"
NEW = ("kernel_mla_decode_attend_roofline", "kernel_mla_prefill_attn_roofline",
       "kernel_moe_held_experts_roofline", "step_moe_held_pair_share")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, harness.Cell(CELL).reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the configuration and the cell ------------------------------------------

def test_config_is_the_catalog_row_with_the_five_keys_reduced():
    c = CONFIG
    entry = {e["name"]: e for e in MANIFEST["configs"]}[NAME]
    assert entry["reduced"] == c["reduced"] == REDUCED
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    # every width as published
    assert (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["n_group"], c["topk_group"]) == (
                7168, 64, 1536, 512, 128, 64, 192, 18432, 2048, 8, 8, 4)
    # the cut, within the guide's floors
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"]) == (6, 1, 16, 16032, 0)
    assert c["experts_held"] == {"first": 0, "count": 16, "of": 256}
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    if os.path.isfile(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"GigaChat3.1-702B-A36B"' in l)
        assert entry["source"] == c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in REDUCED:
                assert c["published"][key] == value, key
            else:
                assert c[key] == value, key
    assert {"init", "e_score_correction_bias", "precision", "rope_pairing",
            "dropped_groups", "n_pages", "max_len"} <= set(c["assumed"])
    assert "16 v5e chips share each layer" in c["deployment"]
    assert set(c["limits"]) == {"latent_page_rms", "widest_gap", "mean_gap"}
    assert set(c["limits"]) < set(c["limits_from"])
    # ISSUE 40's arguments but ``max_len``: one more page a slot (67),
    # so that the admission rule (prompt + output + chunk <= max_len)
    # seats every request of the issue's traffic (``total_max`` 8,448)
    assert c["serve"] == dict(n_slots=64, max_len=8576, chunk=32,
                              kv_int8=False, page_tokens=128, n_pages=2560,
                              prefix_cache=True, moe_block=2048)


def test_counts_by_hand():
    c = CONFIG
    # the issue's count: 5.17 B parameters, 10.35 GB in bf16; the
    # weights file draws exactly what the count says
    n = weights_gigachat.n_params(c)
    attn = (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 20480
            + 12288 * 7168 + 1536 + 512 + 2 * 7168)
    assert abs(attn - 132.6e6) < 0.1e6
    moe = 7168 * 256 + 256 + 17 * 3 * 7168 * 2048
    assert n == (attn + 3 * 7168 * 18432) + 5 * (attn + moe) \
        + 2 * 16032 * 7168 + 7168
    assert abs(n - 5.17e9) < 0.01e9
    assert flops_gigachat.latent_row_bytes(c) == 1152
    assert flops_gigachat.kv_bytes_token(c) == 6912
    assert flops_gigachat.expert_bytes(c) == 88_080_384
    assert flops_gigachat.pair_flops(c) == 6 * 7168 * 2048
    assert flops_gigachat.n_moe_layers(c) == 5
    # one row: 64 heads x (576 + 512) x 2 ops over 1,152 B = 120.9 FLOP/B
    ops, nbytes = flops_gigachat.latent_attend_work(c, 1000)
    assert (ops, nbytes) == (1000 * 64 * 1088 * 2, 1000 * 1152)
    assert abs(ops / nbytes - 120.9) < 0.1
    # 2 rows behind 3 cached: (3 + 1) + (3 + 2) = 9 (query, key) pairs
    assert flops_gigachat.prefill_attention_flops(c, 2, 3) == \
        64 * 2 * 9 * (192 + 192)
    # a cold prefill of 8,192: ~9.9 TFLOP over the six layers
    assert abs(6 * flops_gigachat.prefill_attention_flops(c, 8192, 0)
               - 9.9e12) < 0.05e12
    assert flops_gigachat.held_experts_work(c, 3, 7) == (
        7 * 6 * 7168 * 2048, 3 * 88_080_384)


@pytest.mark.parametrize("leaf, same", [("bias", True), ("gate", False)])
def test_the_selection_bias_is_the_files_and_every_other_leaf_the_seeds(
        leaf, same):
    """No seed gets other work than another: which experts the selection
    bias makes popular (so how many routed pairs and live experts this
    chip's 16 see) is drawn from the file's ``selection_bias_seed``, as
    a burst's multiset is from ``pair_seed``; the router's gate, like
    every other leaf, is the seed's. The file's widths are cut down,
    the router's 256 and the file's seed kept."""
    import jax.numpy as jnp
    import numpy as np
    c = dict(CONFIG, hidden_size=32, intermediate_size=32,
             moe_intermediate_size=16, vocab_size=64, q_lora_rank=16,
             kv_lora_rank=16, num_attention_heads=2)
    a, b = (np.asarray(weights_gigachat.make_gigachat(
        c, seed, jnp.float32)["seg1"][leaf]) for seed in (1, 2 ** 31 + 5))
    assert a.shape[0] == 5 and a.shape[-1] == 256
    assert (a == b).all() == same
    if leaf == "bias":
        assert a.dtype == np.float32 and np.abs(a).max() <= 0.05
        assert 0.02 < a.std() < 0.035      # uniform +-0.05: 0.0289
        other = np.asarray(weights_gigachat.make_gigachat(
            dict(c, selection_bias_seed=41), 1, jnp.float32)["seg1"][leaf])
        assert not (a == other).all()


def test_cell_reports_the_metrics_the_issue_lists():
    cell = harness.Cell(CELL)
    assert cell.cell == {
        "name": CELL, "config": NAME, "traffic": "doc_qa_burst_128",
        "chips": 1, "why": cell.cell["why"]}
    assert len(cell.cell["why"]) <= 200
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["configs"][-1]["name"] == NAME
    assert len(MANIFEST["workloads"]) == 6
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {
        "entry_first_token_ms", "sched_slot_occupancy",
        "sched_prefix_token_share", "compiles_in_window.serve", *NEW}
    for m in cell.per_layer():
        assert m["moves"] in {e["name"] for e in cell.end_to_end()}, m
        assert os.path.isfile(cell.reader_path(m["name"]))
    new = [m for m in MANIFEST["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == list(NEW)
    assert all(m["workloads"] == [CELL] and m["unit"] == "%" for m in new)
    assert [(m["layer"], m["source"], m["moves"]) for m in new] == [
        ("Kernels", "device_trace", "serve_tok_s"),
        ("Kernels", "device_trace", "ttft_p95_ms"),
        ("Kernels", "device_trace", "serve_tok_s"),
        ("Step programs", "program_counter", "serve_tok_s")]
    # every list the cell was appended to ends with it
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]


def test_traffic_file_is_the_issues_and_its_warmup_covers_what_it_reaches():
    cell = harness.Cell(CELL)
    t, s = cell.traffic, CONFIG["serve"]
    assert t["kind"] == "serve_bursts" and t["burst_requests"] == 128
    assert t["prefixes"] == {"count": 16, "tokens": 7936, "zipf_s": 0.0}
    assert t["body"] == {"dist": "uniform", "min": 32, "max": 256}
    assert t["output"] == {"dist": "lognormal", "median": 128, "sigma": 0.5,
                           "min": 32, "max": 256}
    assert (t["total_max"], t["pair_seed"]) == (8448, 40)
    assert t["total_max"] + s["chunk"] <= s["max_len"] == 67 * 128
    shape = traffic.burst_shape(t)
    # 16 documents x 8 askers; prompts of 7,969-8,191 tokens
    assert sorted(p for p, _, _ in shape) == sorted(list(range(16)) * 8)
    assert (min(b for _, b, _ in shape), max(b for _, b, _ in shape)) == (
        33, 255)
    assert all(32 <= o <= 256 for _, _, o in shape)
    assert max(7936 + b + o for _, b, o in shape) + s["chunk"] <= s["max_len"]
    assert 7936 == 62 * s["page_tokens"]
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    pages = lambda n: -(-n // s["page_tokens"])
    # ONE cold bucket; three suffix buckets behind 62 hit pages; what a
    # program's shape also depends on: the fresh pages it scatters
    cold = {(bucket(7936 + b), pages(7936 + b)) for _, b, _ in shape}
    hit = {(min(bucket(b), s["max_len"] - 7936), pages(7936 + b) - 62)
           for _, b, _ in shape}
    assert cold == {(8192, 63), (8192, 64)}
    assert hit == {(64, 1), (128, 1), (256, 2)}
    seen, warm_cold, warm_hit = set(), set(), set()
    for w in t["warmup"]:
        if w["prefix"] in seen:
            warm_hit.add((bucket(w["body"]), pages(7936 + w["body"]) - 62))
        else:
            warm_cold.add((bucket(7936 + w["body"]),
                           pages(7936 + w["body"])))
        seen.add(w["prefix"])
    assert (warm_cold, warm_hit) == (cold, hit)
    assert len(t["warmup"]) == 5 and all(w["out"] == 2 for w in t["warmup"])
    # the last request of a burst is never its document's first: the
    # traced refill is a suffix prefill
    assert sum(o for _, _, o in shape) == 17790


# -- the readers --------------------------------------------------------------

TINY_C = {"num_attention_heads": 2, "kv_lora_rank": 4, "qk_rope_head_dim": 2,
          "qk_nope_head_dim": 3, "v_head_dim": 5, "num_hidden_layers": 3,
          "first_k_dense_replace": 1, "hidden_size": 8,
          "moe_intermediate_size": 6, "num_experts_per_tok": 2,
          "serve": {"n_slots": 5, "chunk": 2, "page_tokens": 4}}


def _traced_run(attend_calls=0, rows_calls=0, gmm_calls=0, chunks_inside=1,
                by_chunk="sound", spans=True, seconds_each=1e-3):
    """A hand-made traced run of a tiny geometry: 3 layers (2 expert
    layers), 2 heads on a 4 + 2 wide row, 5 slots, chunk 2; one request
    of 9 prompt tokens (2 hit pages of 4) whose first token and whose
    chunks fall inside the traced window."""
    ops, t = [], 0.0

    def call(text):
        nonlocal t
        ops.append((text, t, seconds_each * 1e9))
        t += 2 * seconds_each * 1e9
    for _ in range(attend_calls):
        call('%paged_flash_decode_attend.3 = bf16[5,1,2,4]{3,2,1,0} '
             'custom-call(), custom_call_target="tpu_custom_call"')
    for _ in range(rows_calls):
        call('%flash_rows_attention.7 = bf16[2,8,5]{2,1,0} custom-call(), '
             'custom_call_target="tpu_custom_call"')
    for n in range(gmm_calls):
        call(f'%gmm.{n} = f32[10,{(6, 6, 8)[n % 3]}]{{1,0}} custom-call(), '
             'custom_call_target="tpu_custom_call"')
    call("%fusion.1 = f32[5,4]{1,0} fusion()")     # not a kernel's
    trace = {"devices": {"/device:TPU:0": ops}, "host": []}
    from benchmarks import trace_reduce
    reduced = dict(trace_reduce.reduce(trace), trace=trace)
    # request 0's tokens 2.. delivered by the chunks inside the window
    deliveries = ([(0.5, [(0, 1)])]
                  + [(1.2 + 0.1 * i, [(0, 2 + 2 * i), (0, 3 + 2 * i)])
                     for i in range(chunks_inside)] + [(2.5, [(0, 9)])])
    log = types.SimpleNamespace(deliveries=lambda pauses=(): deliveries,
                                first=[1.1, 0.2])
    # per chunk: (pairs routed, held experts live, fullest, layer-steps,
    # pairs held, group hits)
    chunks = {"sound": [(40, 1, 1, 4, 2, 3)] * 4, "parent": [(40, 9, 2, 4)] * 4,
              None: None}[by_chunk]
    metrics = types.SimpleNamespace(
        moe_by_chunk=chunks, moe_experts_held=2, moe_assignments=160,
        moe_pairs_held=8,
        spans=[types.SimpleNamespace(name="refill.prefill",
                                     ids={"rid": 0, "hit_pages": 2})]
        if spans else [])
    outs = types.SimpleNamespace(metrics=metrics)
    import numpy as np
    return {"traced": (1.0, 2.0, []), "config": TINY_C, "reduced": reduced,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "bursts": [types.SimpleNamespace(
                log=log, outs=outs, prompts=[np.zeros(9), np.zeros(5)])]}


def test_latent_attend_reader_on_hand_made_runs():
    read = _reader(NEW[0]).read
    # one chunk of 2 steps x 3 layers = 6 calls; the two delivered
    # tokens attended 9 + 2 and 9 + 3 rows a layer: 23 x 3 rows of
    # 2 heads x (6 + 4) x 2 = 40 ops and 12 B: ops bound, 2,760 us of
    # operations over 6 ms of events
    assert read(_traced_run(attend_calls=6)) == pytest.approx(
        100 * 23 * 3 * 40e-6 / 6e-3)
    assert read(_traced_run(attend_calls=12, chunks_inside=2)) == \
        pytest.approx(100 * (23 + 27) * 3 * 40e-6 / 12e-3)
    # bytes bound it where the bytes' time is the larger
    run = _traced_run(attend_calls=6)
    run["peaks"] = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    assert read(run) == pytest.approx(100 * 23 * 3 * 12e-6 / 6e-3)
    # calls that are not chunks x chunk x layers: no attribution; no
    # such call (the parent); an untraced run
    assert read(_traced_run(attend_calls=5)) is None
    assert read(_traced_run(attend_calls=12)) is None
    assert read(_traced_run()) is None
    assert read(dict(_traced_run(attend_calls=6), traced=None)) is None
    # never over 100: a kernel at the peak reads 100
    fast = _traced_run(attend_calls=6, seconds_each=23 * 3 * 40e-6 / 6)
    assert read(fast) == pytest.approx(100.0)


def test_prefill_attention_reader_on_hand_made_runs():
    read = _reader(NEW[1]).read
    # request 0's first token fell inside the window: 9 - 8 = 1 row
    # behind 8 cached: 9 pairs x 2 heads x 2 x (5 + 5) a layer, 3 layers
    flop = 3 * 2 * 2 * 9 * 10
    assert read(_traced_run(rows_calls=3)) == pytest.approx(
        100 * flop * 1e-6 / 3e-3)
    # not refills x layers; no call; no span record (the parent); untraced
    assert read(_traced_run(rows_calls=4)) is None
    assert read(_traced_run(rows_calls=6)) is None
    assert read(_traced_run()) is None
    assert read(_traced_run(rows_calls=3, spans=False)) is None
    assert read(dict(_traced_run(rows_calls=3), traced=None)) is None
    assert read(_traced_run(rows_calls=3, seconds_each=flop * 1e-6 / 3)) \
        == pytest.approx(100.0)


def test_held_experts_reader_on_hand_made_runs():
    read = _reader(NEW[2]).read
    # one chunk of 2 steps x 2 expert layers x 3 = 12 calls; its counters
    # (the SECOND delivery's: index 1): 1 live held expert of 3 x 8 x 6
    # x 2 = 288 B, 2 held pairs of 6 x 8 x 6 = 288 ops: 576 us over 12 ms
    assert read(_traced_run(gmm_calls=12)) == pytest.approx(
        100 * 576e-6 / 12e-3)
    assert read(_traced_run(gmm_calls=24, chunks_inside=2)) == \
        pytest.approx(100 * 2 * 576e-6 / 24e-3)
    # miscounted calls; none; the parent's 4-wide counters; none at all
    assert read(_traced_run(gmm_calls=11)) is None
    assert read(_traced_run()) is None
    assert read(_traced_run(gmm_calls=12, by_chunk="parent")) is None
    assert read(_traced_run(gmm_calls=12, by_chunk=None)) is None
    assert read(dict(_traced_run(gmm_calls=12), traced=None)) is None
    assert read(_traced_run(gmm_calls=12, seconds_each=576e-6 / 12)) == \
        pytest.approx(100.0)


def test_held_pair_share_reader_on_hand_made_runs():
    read = _reader(NEW[3]).read
    run = _traced_run()
    assert read(run) == pytest.approx(100 * 8 / 160)
    m = run["bursts"][0].outs.metrics
    m.moe_experts_held = 0                  # holds every expert (LFM2)
    assert read(run) is None
    del m.moe_experts_held                  # the parent's metrics
    assert read(run) is None
    m.moe_experts_held, m.moe_assignments = 2, 0
    assert read(run) is None
    m.moe_assignments = m.moe_pairs_held = 8    # a layer that holds all
    assert read(run) == pytest.approx(100.0)


# -- a whole run through the new entry, tiny, files only ---------------------

TINY = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=16, v_head_dim=24, intermediate_size=96,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, n_routed_experts=4,
            num_experts_per_tok=2, n_group=2, topk_group=1,
            max_position_embeddings=512, weights_dtype="float32",
            init_scale=0.125, experts_held={"first": 0, "count": 4, "of": 8},
            rope_scaling=dict(beta_fast=32, beta_slow=1, factor=4.0,
                              mscale=1, mscale_all_dim=1,
                              original_max_position_embeddings=64))
# The tiny configuration's own limits. In float32 the program and the
# reference differ by summation order alone: the pages read 1e-7..1e-6,
# served tokens' gaps 0 or a near-tie's 1e-5; anything left out moves
# logits by 1e-2..1.
TINY_LIMITS = {"widest_gap": 1e-3, "mean_gap": 1e-4, "latent_page_rms": 1e-4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's worth of benchmark DATA with a dummy GigaChat3
    serving cell added as files and manifest entries only; the code that
    runs it is the repo's, unchanged."""
    root = str(tmp_path_factory.mktemp("bench_root_gigachat"))
    here = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(here, d))
    man = copy.deepcopy(MANIFEST)
    base = dict(CONFIG, **TINY)
    base["serve"] = dict(n_slots=2, max_len=128, chunk=4, kv_int8=False,
                         page_tokens=16, n_pages=24, prefix_cache=True,
                         moe_block=16)
    base["check"] = {"served_requests": 4, "served_cold": 1, "kv_prompts": 2,
                     "kv_pages": 2, "served_rows": 12,
                     "reference_heads_at_once": 2}
    base["limits"] = TINY_LIMITS
    mix = {"kind": "serve_bursts", "burst_requests": 6,
           "prefixes": {"count": 2, "tokens": 32, "zipf_s": 0.0},
           # (bodies of ONE cold bucket, 64, and ONE suffix bucket, 32,
           # both in the warm-up: ``correct`` holds the window's compiles
           # to 0)
           "body": {"dist": "uniform", "min": 17, "max": 30},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
           "total_max": 124, "pair_seed": 1,
           "warmup": [{"prefix": 0, "body": 20, "out": 5},
                      {"prefix": 0, "body": 25, "out": 5}]}
    for name, obj in (("configs/tiny_gigachat", base),
                      ("traffic/tiny_gigachat", mix)):
        with open(os.path.join(here, name + ".json"), "w") as f:
            json.dump(obj, f)
    man["configs"].append({
        "name": "tiny_gigachat", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_gigachat.json", "why": "test"})
    man["workloads"].append({
        "name": "tiny_gigachat_cell", "config": "tiny_gigachat",
        "traffic": "tiny_gigachat", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_gigachat_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _measure(root, seed):
    import benchmarks.run as bench_run
    cell = harness.Cell("tiny_gigachat_cell", root=root,
                        here=os.path.join(root, "benchmarks"))
    line = bench_run.measure(cell, seed, 0.3, False, time.perf_counter(),
                             chip=lambda n: harness.describe_device())
    return json.loads(line)


def _checks(capsys):
    return {c["name"]: c for c in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if '"check"' in l))}


def test_the_gigachat_cell_added_as_files_only_runs_and_is_correct(tiny_root,
                                                                   capsys):
    line = _measure(tiny_root, seed=2 ** 31 + 77)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and set(line["metrics"]) == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu"      # named, never hidden
    checks = _checks(capsys)
    assert {"latent_page_rms", "widest_gap", "mean_gap",
            "served_cold_and_hit_compared", "failed_requests",
            "requeues_rejections_preemptions", "compiles"} <= set(checks)
    assert all(c["ok"] for c in checks.values())
    # requests of both kinds were held to the reference
    assert checks["served_cold_and_hit_compared"]["value"] == [1, 3]


@pytest.mark.parametrize("control", ["one_expert_left_out", "no_shared_expert",
                                     "plain_topk", "pages_in_8_bits"])
def test_a_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch,
                                            control):
    """Part of the mathematics left out underneath the serve programs
    (the first held expert, the shared expert, the group limit) fails the served tokens' gaps and leaves the first layer's
    pages, which lie in front of any FFN, the reference's; pages read
    back in 8 bits fail ``latent_page_rms`` alone."""
    import jax
    from benchmarks import control_gigachat
    control_gigachat.BROKEN[control](monkeypatch)
    jax.clear_caches()
    try:
        line = _measure(tiny_root, seed=5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert line["correct"] is False and line["failed"] == 0
    checks = _checks(capsys)
    fails = ({"latent_page_rms"} if control == "pages_in_8_bits"
             else {"mean_gap"})
    assert not any(checks[name]["ok"] for name in fails)
    holds = {"failed_requests", "requeues_rejections_preemptions",
             "served_cold_and_hit_compared"} | (
                 {"mean_gap", "widest_gap"} if control == "pages_in_8_bits"
                 else {"latent_page_rms"})
    assert all(checks[name]["ok"] for name in holds)
