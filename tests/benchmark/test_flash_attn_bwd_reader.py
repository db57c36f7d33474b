"""CPU tests of what PR 32 added to the benchmark: the per-layer reader
``kernel_flash_attn_bwd_roofline`` on made-up reduced traces, and its
manifest entry. No device metric is read here."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import flops, harness, trace_reduce  # noqa: E402

MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
NAME = "kernel_flash_attn_bwd_roofline"
OLDER = [
    "entry_first_token_ms", "sched_slot_occupancy",
    "sched_prefix_token_share", "step_decode_ms", "train_step_ms",
    "train_mfu", "compiles_in_window.serve", "compiles_in_window.train",
    "kernel_decode_attend_roofline", "kernel_flash_attn_roofline",
    "entry_setup_ms", "sched_queue_wait_p95_ms", "sched_step_utilization",
    "sched_host_share", "sched_refill_host_ms", "step_prefill_ms",
    "kernel_moe_experts_roofline", "step_moe_live_expert_share"]

# 3 layers x 2 micro-batches: 6 backwards a step
CONFIG = {"n_layer": 3, "n_head": 2, "n_embd": 8, "train": {"n_micro": 2}}
TRAFFIC = {"rows_per_step": 4, "seq": 16}
PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def _reader(name):
    cell = harness.Cell("medium_train_1k")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), cell.reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(n_bwd, n_fwd, seconds_each=1e-3):
    """A hand-made traced run: ``n_bwd`` backward and ``n_fwd`` forward
    kernel events and one fusion on one device plane."""
    ops, t = [], 0.0

    def add(text, n):
        nonlocal t
        for _ in range(n):
            ops.append((text, t, seconds_each * 1e9))
            t += 2 * seconds_each * 1e9

    add("%attn_bwd.7 = (bf16[2,2,16,4]{3,2,1,0}, bf16[2,2,16,4]{3,2,1,0}, "
        "bf16[2,2,16,4]{3,2,1,0}) custom-call(%a, %b), "
        'custom_call_target="tpu_custom_call"', n_bwd)
    add("%flash_attention_lse.16 = f32[2,2,16,5]{3,2,1,0} custom-call(%a), "
        'custom_call_target="tpu_custom_call"', n_fwd)
    add("%fusion.1 = f32[2,2,16,4]{3,2,1,0} fusion(%attn_bwd.7)", 1)
    trace = {"devices": {"/device:TPU:0": ops}, "host": []}
    return {"reduced": dict(trace_reduce.reduce(trace), trace=trace),
            "config": CONFIG, "traffic": TRAFFIC, "peaks": PEAKS}


@pytest.mark.parametrize("n_bwd,n_fwd,steps", [
    (6, 12, 1), (24, 48, 4), (12, 0, 2),           # whole steps: a share
    (0, 12, None), (5, 12, None), (7, 12, None),   # a fallback, a miss
], ids=["one_step", "four_steps", "no_forward", "no_backward_calls",
        "a_layer_missed", "one_too_many"])
def test_bwd_roofline_reader_on_hand_made_traces(n_bwd, n_fwd, steps):
    read = _reader(NAME).read
    got = read(_run(n_bwd, n_fwd))
    if steps is None:
        assert got is None
        return
    # a backward on [2 rows, 2 heads, 16, 4]: twice the causal forward's
    # 2 x 2 x 2 x 2 x 16 x 16 x 4 / 2 = 8192 operations, 8.2 us at the
    # made-up peak, against 1 ms of kernel time each; 8 tensors of
    # 2 x 16 x 8 bf16 values = 4096 B = 4.1 us: compute bounds it
    assert flops.flash_attention_flops(2, 2, 16, 4, backward=True) == 16384
    assert got == pytest.approx(100.0 * 16384e-9 / 1e-3)
    assert 0 < got < 100


def test_forward_and_backward_readers_count_their_own_calls_only():
    run = _run(6, 12)
    fwd = _reader("kernel_flash_attn_roofline").read
    # 12 forward calls of 8192 operations over 12 ms, whatever the
    # backward's calls number
    assert fwd(run) == pytest.approx(100.0 * 8192e-9 / 1e-3)
    assert fwd(_run(0, 12)) == pytest.approx(fwd(run))
    assert fwd(_run(6, 0)) is None
    # and a program with no such kernel (the parent) reads nothing
    assert _reader(NAME).read(_run(0, 12)) is None


def test_manifest_entry_is_appended_and_the_older_ones_keep_their_order():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[:len(OLDER)] == OLDER
    assert names.index(NAME) == names.index("step_moe_live_expert_share") + 1
    entry = MANIFEST["per_layer"][names.index(NAME)]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels",
        "moves": "train_tok_s", "workloads": ["medium_train_1k"]}
    cell = harness.Cell("medium_train_1k")
    assert {m["name"] for m in cell.per_layer()} == {
        "train_step_ms", "train_mfu", "compiles_in_window.train",
        "kernel_flash_attn_roofline", NAME}
    assert os.path.isfile(cell.reader_path(NAME))
