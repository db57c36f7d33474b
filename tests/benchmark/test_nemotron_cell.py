"""CPU tests of what PR 44 added to the benchmark: the Nemotron-H
configuration and its cell ``nemotron3_agent_burst`` (files only), the
traffic mix ``agent_burst_192``, the four new per-layer readers, the
counts of ``flops_nemotron.py``, and a whole run of
``benchmarks/run.py``'s ``measure`` through the new entry at a tiny
size: sound, and with the timed path broken underneath (the shared
expert left out, every held expert left out, the snapshot restored from
the wrong row), which has to come out as not correct. No device metric
is read here. Nothing here pins the manifest's length or tail: the next
cell is appended behind this one.
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

from benchmarks import flops_nemotron, harness, traffic  # noqa: E402
from benchmarks import weights_nemotron  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
NAME = "nemotron3_super_120b_ep4_serve"
CONFIG = harness.load_json(ROOT, "benchmarks", "configs", NAME + ".json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron3_agent_burst"
NEW = ("kernel_ssd_update_roofline", "kernel_ssd_scan_roofline",
       "kernel_moe_latent_experts_roofline", "sched_snapshot_restore_share")
JOINED = ("entry_first_token_ms", "sched_slot_occupancy",
          "sched_prefix_token_share", "compiles_in_window.serve")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]


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
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["ssm_state_size"], c["n_groups"], c["conv_kernel"],
            c["chunk_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["moe_latent_size"],
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"]) == (
                4096, 128, 64, 128, 8, 4, 128, 32, 2, 128, 1024, 2688, 5376,
                22, 5)
    # the cut, within the guide's floors: a whole period, >= 8 experts,
    # >= 1/8 of the vocabulary
    assert (c["num_hidden_layers"], c["hybrid_override_pattern"],
            c["n_routed_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"]) == (11, "MEMEMEM*EME", 128,
                                               32768, 0)
    assert c["experts_held"] == {"first": 0, "count": 128, "of": 512}
    assert c["vocab_size"] * 4 == c["published"]["vocab_size"]
    pub = c["published"]["hybrid_override_pattern"]
    assert pub.startswith(c["hybrid_override_pattern"]) and len(pub) == 88
    # the stage keeps the published ratio 40 : 40 : 8
    assert [c["hybrid_override_pattern"].count(k) * 8 for k in "ME*"] == [
        pub.count(k) for k in "ME*"]
    if os.path.isfile(CATALOG):
        row = next(json.loads(l) for l in open(CATALOG)
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in l)
        assert entry["source"] == c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in REDUCED:
                assert c["published"][key] == value, key
            else:
                assert c[key] == value, key
    assert {"positions", "init", "e_score_correction_bias", "precision",
            "n_pages", "max_len", "n_snapshots"} <= set(c["assumed"])
    assert "32 v5e chips, 8 pipeline stages of 11 layers" in c["deployment"]
    assert set(c["limits"]) == {"kv_page_rms", "ssm_state_rms",
                                "conv_tail_rms", "widest_gap", "mean_gap"}
    assert set(c["limits"]) < set(c["limits_from"])
    assert all(v > 0 for v in c["limits"].values())
    assert c["serve"] == dict(n_slots=96, max_len=5120, chunk=32,
                              kv_int8=False, page_tokens=128, n_pages=1536,
                              prefix_cache=True, n_snapshots=64,
                              snapshot_every=4, moe_block=1024)
    assert c["weights_dtype"] == "bfloat16" and c["selection_bias_seed"] == 44


def test_counts_by_hand():
    c = CONFIG
    # the issue's count: 4.65 B parameters, 9.30 GB in bf16; the weights
    # file draws exactly what the count says
    n = weights_nemotron.n_params(c)
    mamba = (4096 * 18560 + 4 * 10240 + 10240 + 3 * 128 + 8192
             + 8192 * 4096 + 4096)
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256 + 4096
    moe = (4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
           + 128 * 2 * 1024 * 2688)
    assert abs(mamba - 109.6e6) < 0.1e6 and abs(attn - 35.7e6) < 0.1e6
    assert abs(moe - 128 * 5_505_024 - 54.5e6) < 0.1e6
    assert n == 5 * mamba + attn + 5 * moe + 2 * 32768 * 4096 + 4096
    assert abs(n - 4.65e9) < 0.01e9
    assert [(k, "".join(p), r) for k, p, r in weights_nemotron.stretches(c)
            ] == [("seg0", "ME", 3), ("seg1", "M", 1), ("seg2", "*", 1),
                  ("seg3", "E", 1), ("seg4", "M", 1), ("seg5", "E", 1)]
    assert [e[0] for e in weights_nemotron.plan(c)] == list("MEMEMEM*EME")
    assert flops_nemotron.mamba_dims(c) == (128, 64, 128, 8)
    assert [flops_nemotron.n_layers(c, k) for k in "ME*"] == [5, 5, 1]
    # 4.19 MB of state + 61 KB of conv window a slot a layer: 21.3 MB
    assert flops_nemotron.state_bytes_layer(c) == (4_194_304, 61_440)
    assert flops_nemotron.state_bytes_slot(c) == 5 * 4_255_744
    assert abs(96 * flops_nemotron.state_bytes_slot(c) - 2.04e9) < 0.01e9
    assert flops_nemotron.ssd_update_work(c, 96)["bytes"] == \
        2 * 96 * 4_194_304
    # one chunk of 128 tokens: C B^T a group, and three products a head
    work = flops_nemotron.ssd_scan_work(c, 512, 1)
    assert work["ops"] == 4 * (8 * 2 * 128 ** 3
                               + 128 * (2 * 128 * 128 * 64
                                        + 4 * 128 * 128 * 64))
    assert work["bytes"] == 512 * (8192 * 6 + 2048 * 2 + 128 * 4) \
        + 3 * 4_194_304
    assert flops_nemotron.ssd_scan_work(c, 40, 0)["ops"] == work["ops"] // 4
    assert flops_nemotron.expert_bytes(c) == 11_010_048
    assert flops_nemotron.pair_flops(c) == 4 * 1024 * 2688
    assert flops_nemotron.latent_experts_work(c, 3, 7) == (
        7 * 4 * 1024 * 2688, 3 * 11_010_048)


@pytest.mark.parametrize("leaf, same", [("bias", True), ("gate", False)])
def test_the_selection_bias_is_the_files_and_every_other_leaf_the_seeds(
        leaf, same):
    """No seed gets other work than another: which experts the selection
    bias makes popular is drawn from the file's ``selection_bias_seed``;
    the router's gate, like every other leaf, is the seed's. The file's
    widths are cut down, the router's 512 and the file's seed kept."""
    import jax.numpy as jnp
    import numpy as np
    c = dict(CONFIG, hidden_size=32, vocab_size=64, mamba_num_heads=4,
             mamba_head_dim=8, ssm_state_size=16, n_groups=2, head_dim=8,
             num_attention_heads=4, moe_latent_size=16,
             moe_intermediate_size=8, moe_shared_expert_intermediate_size=8,
             experts_held={"first": 0, "count": 4, "of": 512})
    a, b = (np.asarray(weights_nemotron.make_nemotron(
        c, seed, jnp.float32)["seg0"][1][leaf]) for seed in (1, 2 ** 31 + 5))
    assert a.shape[0] == 3 and a.shape[-1] == 512
    assert (a == b).all() == same
    if leaf == "bias":
        assert a.dtype == np.float32 and np.abs(a).max() <= 0.05
        assert 0.02 < a.std() < 0.035      # uniform +-0.05: 0.0289
        other = np.asarray(weights_nemotron.make_nemotron(
            dict(c, selection_bias_seed=45), 1,
            jnp.float32)["seg0"][1][leaf])
        assert not (a == other).all()


def test_cell_reports_the_metrics_the_issue_lists():
    cell = harness.Cell(CELL)
    assert cell.cell == {
        "name": CELL, "config": NAME, "traffic": "agent_burst_192",
        "chips": 1, "why": cell.cell["why"]}
    assert len(cell.cell["why"]) <= 200
    assert CELL in [w["name"] for w in MANIFEST["workloads"]]
    assert NAME in [e["name"] for e in MANIFEST["configs"]]
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tok_s", "ttft_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer()} == {*JOINED, *NEW}
    for m in cell.per_layer():
        assert m["moves"] in {e["name"] for e in cell.end_to_end()}, m
        assert os.path.isfile(cell.reader_path(m["name"]))
    new = {m["name"]: m for m in MANIFEST["per_layer"] if m["name"] in NEW}
    assert set(new) == set(NEW)
    assert all(m["workloads"] == [CELL] and m["unit"] == "%"
               and m["better"] == "higher" for m in new.values())
    assert [(new[n]["layer"], new[n]["source"], new[n]["moves"])
            for n in NEW] == [
        ("Kernels", "device_trace", "serve_tok_s"),
        ("Kernels", "device_trace", "ttft_p95_ms"),
        ("Kernels", "device_trace", "serve_tok_s"),
        ("Scheduler", "program_counter", "ttft_p95_ms")]
    # the four come in the issue's order, behind every metric PR 40 left
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = [names.index(n) for n in NEW]
    assert at == sorted(at) and at[0] > names.index("step_moe_held_pair_share")
    # the lists of the two xl_* cells stay theirs
    for m in MANIFEST["per_layer"]:
        if m["name"] not in (*JOINED, *NEW):
            assert CELL not in m.get("workloads", []), m["name"]


def test_traffic_file_is_the_issues_and_its_warmup_covers_what_it_reaches():
    cell = harness.Cell(CELL)
    t, s = cell.traffic, CONFIG["serve"]
    assert t["kind"] == "serve_bursts"
    assert t["burst_requests"] in (160, 192, 224)
    assert t["prefixes"] == {"count": 8, "tokens": 4096, "zipf_s": 1.0}
    assert t["body"] == {"dist": "uniform", "min": 32, "max": 512}
    assert t["output"] == {"dist": "lognormal", "median": 128, "sigma": 0.6,
                           "min": 32, "max": 384}
    assert (t["total_max"], t["pair_seed"]) == (4992, 44)
    assert t["total_max"] + s["chunk"] <= s["max_len"] == 40 * 128
    shape = traffic.burst_shape(t)
    assert {p for p, _, _ in shape} == set(range(8))
    assert all(32 <= b <= 512 and 32 <= o <= 384 for _, b, o in shape)
    assert max(4096 + b + o for _, b, o in shape) <= t["total_max"]
    assert 4096 == 32 * s["page_tokens"] and 32 % s["snapshot_every"] == 0
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    pages = lambda n: -(-n // s["page_tokens"])
    # ONE cold bucket (8,192 capped at max_len); five suffix buckets
    # behind 32 hit pages; what a program's shape also depends on: the
    # fresh pages it scatters
    cold = {(min(bucket(4096 + b), s["max_len"]), pages(4096 + b))
            for _, b, _ in shape}
    hit = {(bucket(b), pages(4096 + b) - 32) for _, b, _ in shape}
    assert cold == {(5120, n) for n in (33, 34, 35, 36)}
    assert hit == {(64, 1), (128, 1), (256, 2), (512, 3), (512, 4)}
    seen, warm_cold, warm_hit = set(), set(), set()
    for w in t["warmup"]:
        if w["prefix"] in seen:
            warm_hit.add((bucket(w["body"]), pages(4096 + w["body"]) - 32))
        else:
            warm_cold.add((min(bucket(4096 + w["body"]), s["max_len"]),
                           pages(4096 + w["body"])))
        seen.add(w["prefix"])
    assert warm_cold == cold and warm_hit >= hit
    assert all(w["out"] == 2 for w in t["warmup"])
    # a cold prefill takes a snapshot row at every 4th page: 8 prompts
    # fill the store's 64 rows, and a 9th row goes to a 36-page prompt
    assert s["n_snapshots"] == 8 * (32 // s["snapshot_every"])


# -- the readers --------------------------------------------------------------

TINY_C = {"mamba_num_heads": 2, "mamba_head_dim": 4, "ssm_state_size": 8,
          "n_groups": 1, "conv_kernel": 4, "chunk_size": 4,
          "hybrid_override_pattern": "MEM*E", "moe_latent_size": 4,
          "moe_intermediate_size": 6, "num_experts_per_tok": 2,
          "serve": {"n_slots": 64, "chunk": 2, "page_tokens": 4}}


def _traced_run(update_calls=0, scan=(), gmm_calls=0, chunks_inside=1,
                record=True, seconds_each=1e-3):
    """A hand-made traced run of a tiny geometry: 5 layers (2 Mamba-2, 2
    expert layers), 64 slots (128 pairs: one row tile), chunk 2; the
    chunks of the ``on_token`` record that fall inside the traced
    window; ``scan``: the buckets of the ``%ssd_scan`` calls."""
    ops, t = [], 0.0

    def call(text):
        nonlocal t
        ops.append((text, t, seconds_each * 1e9))
        t += 2 * seconds_each * 1e9
    for n in range(update_calls):
        call(f'%ssd_update.{n} = (f32[64,4,2]{{2,1,0}}, f32[2,64,2,4,8]) '
             'custom-call(), custom_call_target="tpu_custom_call"')
    for n, bucket in enumerate(scan):
        snaps = "f32[2,2,4,8]{3,2,1,0}, " if bucket >= 8 else ""
        call(f'%ssd_scan.{n} = (f32[{bucket},8]{{1,0}}, {snaps}'
             'f32[2,4,8]{2,1,0}) custom-call(), '
             'custom_call_target="tpu_custom_call"')
    for n in range(gmm_calls):
        call(f'%gmm.{n} = f32[128,{(6, 4)[n % 2]}]{{1,0}} custom-call(), '
             'custom_call_target="tpu_custom_call"')
    call("%fusion.1 = f32[5,4]{1,0} fusion()")     # not a kernel's
    trace = {"devices": {"/device:TPU:0": ops}, "host": []}
    from benchmarks import trace_reduce
    reduced = dict(trace_reduce.reduce(trace), trace=trace)
    deliveries = ([(0.5, [(0, 1)])]
                  + [(1.2 + 0.1 * i, [(0, 2 + 2 * i), (0, 3 + 2 * i)])
                     for i in range(chunks_inside)] + [(2.5, [(0, 9)])])
    log = types.SimpleNamespace(deliveries=lambda pauses=(): deliveries)
    # per chunk: (pairs routed, held experts live, fullest, layer-steps,
    # pairs held, group hits, pairs dead); delivering slot-steps
    metrics = types.SimpleNamespace(
        moe_by_chunk=[(40, 3, 1, 4, 9, 3, 2)] * 4 if record else None,
        state_steps_by_chunk=[7, 5, 3, 1] if record else None,
        state_snapshot_seats=3, prefills=4)
    if not record:
        del metrics.state_steps_by_chunk, metrics.state_snapshot_seats
    outs = types.SimpleNamespace(metrics=metrics)
    return {"traced": (1.0, 2.0, []), "config": TINY_C, "reduced": reduced,
            "peaks": {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e6},
            "bursts": [types.SimpleNamespace(log=log, outs=outs)]}


def test_ssd_update_reader_on_hand_made_runs():
    read = _reader(NEW[0]).read
    # one chunk of 2 steps x 2 Mamba-2 layers = 4 calls; the chunk
    # inside the window is the record's second (5 delivering
    # slot-steps): 5 x 2 layers x 2 x 256 B over 4 ms of events
    assert read(_traced_run(update_calls=4)) == pytest.approx(
        100 * 5 * 2 * 512e-6 / 4e-3)
    assert read(_traced_run(update_calls=8, chunks_inside=2)) == \
        pytest.approx(100 * (5 + 3) * 2 * 512e-6 / 8e-3)
    # every slot delivering in every step would read the kernel's own
    # bytes: 64 slots x 2 steps; the share never counts more than that
    assert 5 <= 64 * 2
    # calls that are not chunks x chunk x layers: no attribution; no
    # such call or no record (the parent); an untraced run
    assert read(_traced_run(update_calls=3)) is None
    assert read(_traced_run(update_calls=8)) is None
    assert read(_traced_run()) is None
    assert read(_traced_run(update_calls=4, record=False)) is None
    assert read(dict(_traced_run(update_calls=4), traced=None)) is None


def test_ssd_scan_reader_on_hand_made_runs():
    read = _reader(NEW[1]).read
    # a bucket of 8 with one snapshot, both Mamba-2 layers: 2 chunks of
    # (1 x 2 x 4 x 4 x 8 + 2 x (2 x 4 x 4 x 4 + 4 x 4 x 8 x 4)) = 1,536
    # ops and 8 x (8 x 6 + 16 x 2 + 2 x 4) + 3 x 256 = 1,472 B a call
    work = flops_nemotron.ssd_scan_work(TINY_C, 8, 1)
    assert (work["ops"], work["bytes"]) == (2 * 1536, 1472)
    assert read(_traced_run(scan=(8, 8))) == pytest.approx(
        100 * 2 * 3072e-6 / 2e-3)
    # bytes bound it where the bytes' time is the larger
    run = _traced_run(scan=(4, 4))
    assert read(run) == pytest.approx(
        100 * 2 * flops_nemotron.ssd_scan_work(TINY_C, 4, 0)["ops"] * 1e-6
        / 2e-3)
    run["peaks"] = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    assert read(run) == pytest.approx(
        100 * 2 * flops_nemotron.ssd_scan_work(TINY_C, 4, 0)["bytes"] * 1e-6
        / 2e-3)
    # not a whole multiple of the layers; no such call; untraced
    assert read(_traced_run(scan=(8,))) is None
    assert read(_traced_run()) is None
    assert read(dict(_traced_run(scan=(8, 8)), traced=None)) is None


def test_latent_experts_reader_on_hand_made_runs():
    read = _reader(NEW[2]).read
    # one chunk x 2 steps x 2 expert layers x 2 matmuls = 8 calls; 3
    # live held experts of 2 x 4 x 6 x 2 = 96 B, 9 held pairs of 4 x 4
    # x 6 = 96 ops: ops bound, 864 us over 8 ms of events
    assert read(_traced_run(gmm_calls=8)) == pytest.approx(
        100 * 9 * 96e-6 / 8e-3)
    run = _traced_run(gmm_calls=8)
    run["peaks"] = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e6}
    assert read(run) == pytest.approx(100 * 3 * 96e-6 / 8e-3)
    assert read(_traced_run(gmm_calls=6)) is None
    assert read(_traced_run()) is None
    assert read(_traced_run(gmm_calls=8, record=False)) is None
    assert read(dict(_traced_run(gmm_calls=8), traced=None)) is None


def test_snapshot_restore_share_reader_on_hand_made_runs():
    read = _reader(NEW[3]).read
    run = _traced_run()
    assert read(run) == pytest.approx(75.0)
    m = run["bursts"][0].outs.metrics
    m.state_snapshot_seats = 0              # nothing shared, or no row
    assert read(run) == 0.0
    m.prefills = 0
    assert read(run) is None
    assert read(_traced_run(record=False)) is None      # the parent's


# -- a whole run through the new entry, tiny, files only ---------------------

TINY = dict(vocab_size=96, hidden_size=32, num_hidden_layers=11,
            hybrid_override_pattern="MEMEMEM*EME", num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
            mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=8,
            n_routed_experts=4, num_experts_per_tok=3, moe_latent_size=16,
            moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
            max_position_embeddings=512, weights_dtype="float32",
            init_scale=0.18, experts_held={"first": 2, "count": 4, "of": 8})
# The tiny configuration's own limits. In float32 the program and the
# reference differ by summation order alone: pages, state and window
# read 1e-7..1e-5, served tokens' gaps 0 or a near-tie's 1e-5; anything
# left out moves logits by 1e-2..1, a wrong snapshot row the state by 1.
TINY_LIMITS = {"widest_gap": 1e-3, "mean_gap": 1e-4, "kv_page_rms": 1e-4,
               "ssm_state_rms": 1e-4, "conv_tail_rms": 1e-4}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's worth of benchmark DATA with a dummy Nemotron-H
    serving cell added as files and manifest entries only; the code that
    runs it is the repo's, unchanged."""
    root = str(tmp_path_factory.mktemp("bench_root_nemotron"))
    here = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(here, d))
    man = copy.deepcopy(MANIFEST)
    base = dict(CONFIG, **TINY)
    base["serve"] = dict(n_slots=2, max_len=128, chunk=4, kv_int8=False,
                         page_tokens=16, n_pages=24, prefix_cache=True,
                         n_snapshots=6, snapshot_every=2, moe_block=16)
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
    for name, obj in (("configs/tiny_nemotron", base),
                      ("traffic/tiny_nemotron", mix)):
        with open(os.path.join(here, name + ".json"), "w") as f:
            json.dump(obj, f)
    man["configs"].append({
        "name": "tiny_nemotron", "source": "test", "reduced": [],
        "file": "benchmarks/configs/tiny_nemotron.json", "why": "test"})
    man["workloads"].append({
        "name": "tiny_nemotron_cell", "config": "tiny_nemotron",
        "traffic": "tiny_nemotron", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny_nemotron_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _measure(root, seed):
    import benchmarks.run as bench_run
    cell = harness.Cell("tiny_nemotron_cell", root=root,
                        here=os.path.join(root, "benchmarks"))
    line = bench_run.measure(cell, seed, 0.3, False, time.perf_counter(),
                             chip=lambda n: harness.describe_device())
    return json.loads(line)


def _checks(capsys):
    return {c["name"]: c for c in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if '"check"' in l))}


def test_the_nemotron_cell_added_as_files_only_runs_and_is_correct(tiny_root,
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
            "mean_gap", "served_cold_and_hit_compared", "failed_requests",
            "requeues_rejections_preemptions", "compiles",
            "state_snapshot_seats"} <= set(checks)
    assert all(c["ok"] for c in checks.values())
    # requests of both kinds were held to the reference, and snapshots
    # were restored
    assert checks["served_cold_and_hit_compared"]["value"] == [1, 3]
    assert checks["state_snapshot_seats"]["value"] > 0


@pytest.mark.parametrize("control", ["no_shared_expert",
                                     "held_experts_left_out",
                                     "wrong_snapshot_row", "bf16_state"])
def test_a_broken_timed_path_is_not_correct(tiny_root, capsys, monkeypatch,
                                            control):
    """Part of the mathematics left out underneath the serve programs
    (the shared expert, this share's experts) fails the served tokens'
    mean gap and leaves the first Mamba-2 layer's snapshot, which lies
    in front of any expert layer, the reference's; a hit restored from
    another page's row fails ``ssm_state_rms``, and so does a state
    rounded to bfloat16 after every token (in this float32 cell by four
    decades; the window is the conv's and stays the reference's)."""
    import jax
    from benchmarks import control_nemotron
    control_nemotron.BROKEN[control](monkeypatch)
    jax.clear_caches()
    try:
        line = _measure(tiny_root, seed=5)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert line["correct"] is False and line["failed"] == 0
    checks = _checks(capsys)
    of_state = control in ("wrong_snapshot_row", "bf16_state")
    fails = {"ssm_state_rms"} if of_state else {"mean_gap"}
    assert not any(checks[name]["ok"] for name in fails)
    holds = {"failed_requests", "requeues_rejections_preemptions",
             "served_cold_and_hit_compared", "state_snapshot_seats"} | (
                 {"bf16_state": {"conv_tail_rms"}}.get(control, set())
                 if of_state else {"ssm_state_rms", "conv_tail_rms"})
    assert all(checks[name]["ok"] for name in holds)
