"""CPU tests of the benchmark's harness (benchmarks/, BENCHMARK.json).

They check the yardstick, not the system: the manifest, that every file
a cell names is found, the traffic generator, the metric arithmetic,
the operation and byte counts, the trace reduction on a recorded v5e
trace, and a whole run of ``benchmarks/run.py``'s ``measure`` at a tiny
size with the look for a chip replaced — sound, and with the timed path
broken underneath, which has to come out as not correct. No device
metric is read here: a CPU run proves control flow and counts only.
"""

import copy
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, trace_reduce, traffic  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
SERVE_TRAFFIC = sorted({w["traffic"] for w in MANIFEST["workloads"]
                        if harness.Cell(w["name"]).traffic["kind"]
                        == "serve_bursts"})


# -- the manifest ----------------------------------------------------------

def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_is_well_formed(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if "moves" in metric:                       # a per-layer metric
        target = {m["name"]: m for m in MANIFEST["end_to_end"]}[
            metric["moves"]]
        # every cell that reads it reports the metric it should move
        assert set(metric.get("workloads", CELLS)) <= set(
            target.get("workloads", CELLS))
        assert "bound" not in metric and metric["layer"]
    else:
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_every_file_it_names(name):
    cell = harness.Cell(name)
    assert NAME.match(name) and len(cell.cell["why"]) <= 200
    assert cell.config["entry"] and cell.traffic["kind"]
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "entries", cell.config["entry"] + ".py"))
    assert os.path.isfile(os.path.join(
        ROOT, "benchmarks", "reference", cell.config["reference"] + ".py"))
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer()
    for m in cell.per_layer():
        assert os.path.isfile(cell.reader_path(m["name"]))
    assert set(cell.config["limits"]) <= set(cell.config["limits_from"])
    with pytest.raises(FileNotFoundError):
        cell.reader_path("no_such_metric.serve")


def test_unknown_workload_and_device_are_errors():
    with pytest.raises(KeyError):
        harness.Cell("no_such_cell")
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# -- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("name", SERVE_TRAFFIC)
def test_serve_traffic_is_deterministic_and_within_its_clips(name):
    t = harness.load_json(ROOT, "benchmarks", "traffic", name + ".json")
    seed = 2 ** 31 + 12345
    a, b = (traffic.ServeBursts(t, seed, 50257) for _ in range(2))
    other = traffic.ServeBursts(t, seed + 1, 50257)
    plen = t["prefixes"]["tokens"] if t["prefixes"] else 0
    for gen in (a, b):
        gen.warmup()
    orders = []
    for _ in range(2):
        (pa, na), (pb, nb), (po, no) = a.burst(), b.burst(), other.burst()
        orders.append([len(p) for p in pa])
        assert na == nb and all((x == y).all() for x, y in zip(pa, pb))
        assert len(pa) == t["burst_requests"]
        # another seed: the same sizes in the same order, other tokens
        assert [(len(p), n) for p, n in zip(pa, na)] == [
            (len(p), n) for p, n in zip(po, no)]
        assert any((x != y).any() for x, y in zip(pa, po))
        for p, n in zip(pa, na):
            assert t["body"]["min"] <= len(p) - plen <= t["body"]["max"]
            assert 1 <= n <= t["output"]["max"]
            assert len(p) + n <= t["total_max"]
            assert p.dtype == np.int32 and 0 <= p.min() and p.max() < 50257
    # every burst the same multiset, burst after burst in another order
    assert sorted(orders[0]) == sorted(orders[1]) and orders[0] != orders[1]


def test_shared_prefix_mix_shares_whole_pages_by_zipf():
    t = harness.load_json(ROOT, "benchmarks", "traffic",
                          "shared_prefix_burst.json")
    counts = traffic._zipf_counts(8, 1.0, 96)
    assert counts.sum() == 96 and counts[0] == 35 and counts[-1] == 4
    assert list(counts) == sorted(counts, reverse=True)
    gen = traffic.ServeBursts(t, 7, 50257)
    prompts, _ = gen.burst()
    heads = {p[:512].tobytes() for p in prompts}
    assert len(heads) == 8 and t["prefixes"]["tokens"] % 128 == 0
    # no sharing beyond the system prompt
    assert len({p[:544].tobytes() for p in prompts}) == t["burst_requests"]


def test_warmup_touches_every_prefill_shape_the_mix_can_reach():
    """(bucket, pages) classes of serve_paged_greedy's cold prefill —
    power-of-two bucket, 128-token pages — over the mix's lengths."""
    t = harness.load_json(ROOT, "benchmarks", "traffic", "chat_burst.json")
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    cls = lambda n: (bucket(n), -(-n // 128))
    reach = {cls(b) for _, b, _ in traffic.burst_shape(t)}
    assert reach <= {cls(w["body"]) for w in t["warmup"]}


# -- metric arithmetic -----------------------------------------------------

def test_percentile_is_nearest_rank():
    s = list(range(1, 101))
    assert harness.percentile(s, 0.95) == 95
    assert harness.percentile(s, 0.50) == 50
    assert harness.percentile([3.0], 0.95) == 3.0
    assert harness.percentile([1, 2, 3, 4], 0.95) == 4
    with pytest.raises(ValueError):
        harness.percentile([], 0.5)


def test_token_log_ttft_and_tpot_on_hand_made_records():
    now = [100.0]
    log = harness.TokenLog(3, t_handed=100.0, clock=lambda: now[0])
    for t, rid in ((100.5, 0), (101.0, 1), (101.5, 0), (102.5, 0)):
        now[0] = t
        log.on_token(rid, 7)
    assert log.ttft_s() == [0.5, 1.0, None]        # request 2 never served
    assert log.count == [3, 1, 0]
    assert log.tpot_s() == [(102.5 - 100.5) / 2]   # only >= 2 tokens
    # request 0's two decode tokens came 1 s apart: two deliveries
    assert log.deliveries() == [(101.5, [(0, 1)]), (102.5, [(0, 2)])]
    # ... unless the callback itself was away for that second
    assert log.deliveries(pauses=[(101.5, 102.49)]) == [
        (101.5, [(0, 1), (0, 2)])]


# -- operations and bytes --------------------------------------------------

def test_flop_and_byte_counts_against_hand_counts():
    c = harness.load_json(ROOT, "benchmarks", "configs",
                          "gpt2_medium_train.json")
    per_layer = 3 * 1024 * 1024 + 1024 * 1024 + 2 * 1024 * 4096
    assert flops.gpt2_matmul_params(c) == 24 * per_layer + 50257 * 1024
    # attention, one sequence of 1024, causal, forward: 24 layers x
    # (QK^T + PV) x 2 ops x 1024^2 x 1024 / 2
    fwd = 24 * 2 * 2 * 1024 * 1024 * 1024 / 2
    assert flops.attention_flops(1024, 1024, 24) == fwd
    assert flops.train_flops_per_token(c, 1024) == (
        6 * flops.gpt2_matmul_params(c) + 3 * fwd / 1024)
    # one slot at 130 live tokens -> 2 blocks of 128, K and V, bf16
    assert flops.decode_attend_bytes([130], 128, 25, 64, 48) == (
        2 * 128 * 25 * 64 * 2 * 2 * 48)
    assert flops.decode_attend_bytes([128, 1], 128, 1, 1, 1) == 2 * 128 * 4
    assert flops.flash_attention_flops(2, 16, 1024, 64) == (
        2 * 16 * 2 * 2 * 1024 * 1024 * 64 / 2)
    peak = flops.peaks("TPU v5 lite")
    share, bound = flops.roofline_share(0, 819e9, 2.0, peak)
    assert (share, bound) == (50.0, "memory")
    share, bound = flops.roofline_share(197e12, 1, 1.0, peak)
    assert (round(share, 6), bound) == (100.0, "compute")


# -- the trace reduction ---------------------------------------------------

def test_reduce_on_hand_made_events():
    ms = 1e6
    trace = {"devices": {"/device:TPU:0": [
        ("fusion.1", 10 * ms, 2 * ms), ("fusion.1", 14 * ms, 2 * ms),
        ("copy.2", 15 * ms, 3 * ms), ("late", 40 * ms, 5 * ms)]},
        "host": [(trace_reduce.WINDOW_SPAN, 8 * ms, 12 * ms),
                 ("bench:pause", 11.5 * ms, 2.4 * ms)]}
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.012)
    assert r["busy_s"] == pytest.approx(0.006)     # [10,12] + [14,18]
    assert r["op_seconds"]["fusion.1"] == pytest.approx(0.004)
    assert "late" not in r["op_seconds"]
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["bench:pause"] == pytest.approx(0.002)
    assert gaps["(no host span)"] == pytest.approx(0.004)
    assert trace_reduce.op_seconds(r, "fusion") == pytest.approx(0.004)
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {"/device:TPU:0": []}, "host": []})


def test_reduce_on_the_recorded_v5e_trace():
    """benchmarks/fixtures/tiny.xplane.pb: three runs of one jitted
    tanh(a @ a).sum() on a v5e, a 2 ms host pause (span ``bench:pause``)
    before each, all inside the window span (recorded in PR 23)."""
    trace = trace_reduce.load(os.path.join(
        ROOT, "benchmarks", "fixtures", "tiny.xplane.pb"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert len(trace["devices"]["/device:TPU:0"]) == 9
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.010253899)
    assert r["busy_s"] == pytest.approx(5.463e-06)
    assert 0 < r["busy_s"] < r["window_s"]
    ops = r["breakdown"]["device_ops"]
    assert ops[0][0] == "%fusion fusion bf16[]" and len(ops) == 3
    assert trace_reduce.op_calls(trace, "%fusion", "kOutput") == 3
    assert trace_reduce.op_seconds(r, "%fusion") == pytest.approx(5.417e-06)
    assert "bench:pause" in dict(r["breakdown"]["idle_gaps"])


def test_self_time_and_short_names():
    assert dict(trace_reduce.self_times(
        [("w", 0, 100), ("a", 10, 20), ("b", 40, 30), ("c", 45, 5)])) == {
            "w": 5e-8, "a": 2e-8, "b": 2.5e-8, "c": 5e-9}
    assert trace_reduce.short_name(
        '%flash_attention_lse.16 = f32[8,16,1024,65]{3,2,1,0:T(8,128)S(1)} '
        'custom-call(bf16[8,16,1024,64]{3,2,1,0} %bitcast.612), '
        'custom_call_target="tpu_custom_call"') == (
            "%flash_attention_lse.16 pallas f32[8,16,1024,65]")
    assert trace_reduce.short_name(
        "%while.4 = (s32[]{:T(128)}, bf16[4,8]{1,0}) while((s32[]{:T(128)}, "
        "bf16[4,8]{1,0}) %tuple.9), condition=%c, body=%b") == (
            "%while.4 while s32[]")


# -- a whole run at a tiny size --------------------------------------------

TINY = dict(vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_inner=128)
# The tiny serving configuration's own limits (a configuration brings
# its own): bf16 pages read 0.0029-0.0030 here, int8 pages 0.0057-0.0058.
TINY_SERVE_LIMITS = {"widest_gap": 0.16, "kv_page_rms": 0.0041}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's worth of benchmark DATA with a dummy serving cell and
    a dummy training cell added as files and manifest entries only; the
    code that runs them is the repo's, unchanged."""
    root = str(tmp_path_factory.mktemp("bench_root"))
    here = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", d),
                        os.path.join(here, d))
    man = copy.deepcopy(MANIFEST)

    def add(kind, config, traffic_mix, tag=""):
        base = harness.load_json(ROOT, "benchmarks", "configs",
                                 f"gpt2_{kind}.json")
        base.update(TINY, n_positions=128 if "serve" in base else 32)
        base.update(config)
        kind += tag
        for name, obj in ((f"configs/tiny_{kind}", base),
                          (f"traffic/tiny_{kind}", traffic_mix)):
            with open(os.path.join(here, name + ".json"), "w") as f:
                json.dump(obj, f)
        man["configs"].append({
            "name": f"tiny_{kind}", "source": "test", "reduced": [],
            "file": f"benchmarks/configs/tiny_{kind}.json", "why": "test"})
        man["workloads"].append({
            "name": f"tiny_{kind}_cell", "config": f"tiny_{kind}",
            "traffic": f"tiny_{kind}", "chips": 1, "why": "test"})
        like = "xl_shared_prefix" if "serve" in base else "medium_train_1k"
        for m in man["end_to_end"] + man["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(f"tiny_{kind}_cell")

    serve = dict(n_slots=2, max_len=128, chunk=4, kv_int8=False,
                 page_tokens=16, n_pages=16, prefix_cache=True)
    check = {"check": {"served_requests": 4, "kv_prompts": 2, "kv_pages": 2},
             "limits": TINY_SERVE_LIMITS}
    mix = (
        {"kind": "serve_bursts", "burst_requests": 6,
         "prefixes": {"count": 2, "tokens": 32, "zipf_s": 1.0},
         "body": {"dist": "uniform", "min": 3, "max": 20},
         "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                    "min": 2, "max": 12},
         "total_max": 124, "pair_seed": 1,
         "warmup": [{"prefix": 0, "body": 5, "out": 5},
                    {"prefix": 0, "body": 9, "out": 5}]})
    add("xl_serve", dict(check, serve=serve), mix)
    # the program's own lower precision, as a cell of its own: the control
    add("xl_serve", dict(check, serve=dict(serve, kv_int8=True)), mix,
        tag="_kvint8")
    medium = harness.load_json(ROOT, "benchmarks", "configs",
                               "gpt2_medium_train.json")
    add("medium_train",
        {"train": dict(medium["train"], n_micro=2, xent_chunk=64)},
        {"kind": "train_batches", "seq": 32, "rows_per_step": 4,
         "dataset_tokens": 4096, "group_steps": 2})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root


def _measure(root, workload, seed, trace=False):
    import benchmarks.run as bench_run
    cell = harness.Cell(workload, root=root,
                        here=os.path.join(root, "benchmarks"))
    line = bench_run.measure(cell, seed, 0.5, trace, time.perf_counter(),
                             chip=lambda n: harness.describe_device())
    return json.loads(line)


@pytest.mark.parametrize("workload, metrics", [
    ("tiny_xl_serve_cell",
     {"serve_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}),
    ("tiny_medium_train_cell", {"train_tok_s", "setup_s"})])
def test_a_cell_added_as_files_only_runs_and_is_correct(tiny_root, workload,
                                                        metrics, capsys):
    line = _measure(tiny_root, workload, seed=2 ** 31 + 77)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"      # named, never hidden
    out = capsys.readouterr().out
    checks = [json.loads(l) for l in out.splitlines() if '"check"' in l]
    assert checks and all(c["ok"] for c in checks)  # number beside limit
    assert all("limit" in c and "value" in c for c in checks)


def test_an_altered_served_token_comes_out_not_correct(tiny_root,
                                                       monkeypatch):
    """The timed path broken underneath: the paged decode step hands
    back every token + 1."""
    from mpi_acx_tpu.models import kvpage
    make = kvpage.make_paged_step_fn

    def broken(params, cfg, *a, **kw):
        step = make(params, cfg, *a, **kw)

        def altered(state, tok, keys):
            state, toks, keys = step(state, tok, keys)
            return state, (toks + 1) % cfg.vocab, keys
        return altered
    monkeypatch.setattr(kvpage, "make_paged_step_fn", broken)
    line = _measure(tiny_root, "tiny_xl_serve_cell", seed=5)
    assert line["correct"] is False and line["failed"] == 0


def test_int8_pages_where_the_configuration_says_bf16_are_not_correct(
        tiny_root, capsys):
    """The control, at a size a test run holds: the program with its own
    lower precision switched on (``kv_int8=True``), held to the limits of
    the bf16 configuration, fails the page check and nothing else."""
    line = _measure(tiny_root, "tiny_xl_serve_kvint8_cell", seed=2 ** 31 + 77)
    assert line["correct"] is False and line["failed"] == 0
    checks = {c["name"]: c for c in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if '"check"' in l))}
    assert not checks.pop("kv_page_rms")["ok"]
    assert checks and all(c["ok"] for c in checks.values())


def test_kv_error_measures_against_the_references_own_keys_and_values():
    import jax.numpy as jnp
    from benchmarks import weights
    from benchmarks.reference import gpt2
    c = dict(TINY, n_positions=32)
    tree = weights.make_gpt2(c, 5, jnp.float32)
    tok = jnp.arange(24, dtype=jnp.int32)
    zero = jnp.zeros((2, 4, 16, 24), jnp.float32)
    sums = np.asarray(gpt2.kv_error(tree, tok, zero, zero, n_head=4))
    assert sums.shape == (2, 4) and (sums > 0).all()
    # nothing cached: the difference IS the reference, K and V alike
    assert np.allclose(sums[:, 0], sums[:, 1]) and np.allclose(
        sums[:, 2], sums[:, 3])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_root, monkeypatch):
    from mpi_acx_tpu import train
    make = train.make_train_step_optax

    def broken(*a, **kw):
        step, n_stages = make(*a, **kw)

        def idle(params, opt_state, tokens, targets):
            loss, _, _ = step(params, opt_state, tokens, targets)
            return loss, params, opt_state
        return idle, n_stages
    monkeypatch.setattr(train, "make_train_step_optax", broken)
    line = _measure(tiny_root, "tiny_medium_train_cell", seed=5)
    assert line["correct"] is False


def test_part_of_the_batch_left_out_moves_the_loss_past_its_limit(
        tiny_root):
    """What the loss limit is there to catch: the reference on the whole
    batch against the reference on half of it."""
    import jax.numpy as jnp
    from benchmarks import weights
    from benchmarks.entries import train_step_optax as entry
    from benchmarks.reference import gpt2
    c = harness.load_json(tiny_root, "benchmarks", "configs",
                          "tiny_medium_train.json")
    tree = weights.make_gpt2(c, 3, jnp.float32)
    b = jnp.asarray(np.random.default_rng(3).integers(0, 128, (4, 33)),
                    jnp.int32)
    whole, _ = gpt2.loss_and_grads(tree, b[:, :-1], b[:, 1:], n_head=4)
    half, _ = gpt2.loss_and_grads(tree, b[:2, :-1], b[:2, 1:], n_head=4)
    assert abs(float(whole) - float(half)) > c["limits"]["loss_gap"]
    del entry


def test_served_gaps_are_zero_for_the_references_own_tokens_only(tiny_root):
    import jax.numpy as jnp
    from benchmarks import weights
    from benchmarks.entries import serve_paged_greedy as entry
    from benchmarks.reference import gpt2
    c = harness.load_json(tiny_root, "benchmarks", "configs",
                          "tiny_xl_serve.json")
    tree = weights.make_gpt2(c, 11, jnp.bfloat16)
    seq = np.random.default_rng(11).integers(0, 128, 40).astype(np.int32)
    for i in range(20, 40):                  # greedy by the reference
        rows = gpt2.logits_from(tree, jnp.asarray(np.pad(seq, (0, 88))),
                                i - 1, jnp.zeros((1,), jnp.int8), n_head=4)
        seq[i] = int(np.asarray(rows)[0].argmax())
    assert entry.served_gaps(tree, c, seq, 20).max() == 0.0
    seq[30] = (seq[30] + 1) % 128
    gaps = entry.served_gaps(tree, c, seq, 20)
    assert gaps[10] > 0 and gaps[:10].max() == 0.0


def test_run_py_refuses_to_measure_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode not in (0, None)
    assert '"correct"' not in p.stdout and "TPU chip" in p.stderr
