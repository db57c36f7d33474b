"""Tests for bench.py's incremental TPU-evidence capture (round-4
verdict item #1: rounds 2-4 lost entire windows of chip time to
all-or-nothing 600 s children; the harness itself must be tested).

The TPU children are mocked — these tests verify the ORCHESTRATION:
probe-first fast-fail, per-child banking to BENCH_BANK.json, the
rewrite of BENCH_FULL.json after every child (so a mid-run kill keeps
everything measured so far), and the unmeasured-vs-regression gate
split. Reference: the reference repo has no benchmark harness at all
(SURVEY.md §6) — this is our own obligation.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "REPO", str(tmp_path))
    # Native rows are not under test here: pin them.
    monkeypatch.setattr(mod, "native_bench",
                        lambda msg_bytes=None: (25.0, 40.0, 1.5))
    monkeypatch.setattr(
        mod, "_run_cpu_child",
        lambda mode, timeout=300: (
            {"quant_allreduce_traffic_reduction": 3.88}, None))
    return mod


def _run_main(bench, full=True):
    code = 0
    try:
        bench.main(full=full)
    except SystemExit as e:
        code = e.code or 0
    return code


def test_probe_down_fast_fails_and_skips(bench, capsys):
    """Chip unavailable: ONE probe failure gates every TPU child; all TPU rows
    are unmeasured (skipped loudly), not regressions; exit 0."""
    calls = []

    def fake_child(mode, attempts=3, timeout=420, **kw):
        calls.append(mode)
        return None, f"timeout after {timeout}s (attempt {attempts})"

    bench._run_tpu_child = fake_child
    assert _run_main(bench) == 0
    assert calls == ["probe"], "expensive children must not run"
    doc = json.load(open(os.path.join(bench.REPO, "BENCH_FULL.json")))
    assert "partial" not in doc
    skipped = {c["metric"] for c in doc["checks"] if c.get("skipped")}
    assert "gpt2_fwd_tokens_per_s" in skipped
    assert "train_step_tokens_per_s" in skipped
    assert not doc["result"]["regressions"]
    assert "probe failed" in doc["result"]["tpu_error"]
    # Native + chip-independent rows still gated green.
    ok = {c["metric"] for c in doc["checks"] if c.get("ok")}
    assert {"pingpong_p50_us", "partitioned_bw_gbps",
            "quant_allreduce_traffic_reduction"} <= ok


def test_partial_failure_keeps_earlier_rows(bench):
    """Chip unavailable mid-run (after flash): fwd+flash rows are banked and
    in BENCH_FULL.json; later rows are outage-skips, exit 0."""
    rows = {
        "probe": {"tpu_probe_ok": True, "device": "tpu"},
        "fwd": {"gpt2_fwd_tokens_per_s": 250000.0,
                "gpt2_fwd_b16s512_tokens_per_s": 380000.0,
                "device": "tpu"},
        "flash": {"flash_speedup_s4096": 30.0, "device": "tpu"},
    }

    def fake_child(mode, attempts=3, timeout=420, **kw):
        if mode in rows:
            return rows[mode], None
        return None, f"timeout after {timeout}s (attempt 1)"

    bench._run_tpu_child = fake_child
    assert _run_main(bench) == 0
    doc = json.load(open(os.path.join(bench.REPO, "BENCH_FULL.json")))
    by = {c["metric"]: c for c in doc["checks"]}
    assert by["gpt2_fwd_tokens_per_s"]["ok"] is True
    assert by["flash_speedup_s4096"]["ok"] is True
    assert by["decode_tokens_per_s"]["skipped"]
    assert "TPU outage" in by["decode_tokens_per_s"]["reason"]
    assert not doc["result"]["regressions"]
    # The measured rows were banked the moment they landed.
    bank = json.load(open(os.path.join(bench.REPO, "BENCH_BANK.json")))
    assert bank["gpt2_fwd_tokens_per_s"]["value"] == 250000.0
    assert bank["flash_speedup_s4096"]["value"] == 30.0
    assert "decode_tokens_per_s" not in bank


def test_chip_unavailable_mid_run_skips_remaining_groups(bench):
    """Once a group exhausts retries AND the re-probe fails, later
    groups must fail fast (no attempts x timeout burn) with a loud
    mid-run error."""
    calls = []
    alive = {"probe": True}

    def fake_child(mode, attempts=3, timeout=420, **kw):
        calls.append(mode)
        if mode == "probe":
            if alive["probe"]:
                alive["probe"] = False     # first probe green, re-probe dead
                return {"tpu_probe_ok": True, "device": "tpu"}, None
            return None, "timeout after 150s (attempt 1)"
        if mode == "fwd":
            return {"gpt2_fwd_tokens_per_s": 250000.0,
                    "gpt2_fwd_b16s512_tokens_per_s": 380000.0,
                    "device": "tpu"}, None
        return None, f"timeout after {timeout}s"

    bench._run_tpu_child = fake_child
    assert _run_main(bench) == 0
    # flash fails -> re-probe fails -> decode/train/spec never spawn.
    assert calls.count("flash") == 1
    assert "decode" not in calls and "train" not in calls \
        and "spec" not in calls
    doc = json.load(open(os.path.join(bench.REPO, "BENCH_FULL.json")))
    by = {c["metric"]: c for c in doc["checks"]}
    assert by["gpt2_fwd_tokens_per_s"]["ok"] is True
    assert by["decode_tokens_per_s"]["skipped"]
    assert "mid-run" in by["decode_tokens_per_s"]["reason"]


def test_true_regression_still_fails_gate(bench):
    """A measured row below 0.9x baseline exits nonzero — the
    unmeasured split must not soften real regressions."""
    def fake_child(mode, attempts=3, timeout=420, **kw):
        if mode == "probe":
            return {"tpu_probe_ok": True, "device": "tpu"}, None
        if mode == "fwd":
            return {"gpt2_fwd_tokens_per_s": 1000.0,   # way below baseline
                    "gpt2_fwd_b16s512_tokens_per_s": 380000.0,
                    "device": "tpu"}, None
        return None, "timeout"

    bench._run_tpu_child = fake_child
    assert _run_main(bench) == 1
    doc = json.load(open(os.path.join(bench.REPO, "BENCH_FULL.json")))
    assert "gpt2_fwd_tokens_per_s" in doc["result"]["regressions"]


def test_bank_merges_not_overwrites(bench):
    """_bank appends/updates rows without dropping earlier evidence."""
    bench._bank({"a": 1, "device": "tpu"})
    bench._bank({"b": 2.5, "device": "tpu"})
    bench._bank({"a": 3, "device": "tpu"})
    bank = json.load(open(os.path.join(bench.REPO, "BENCH_BANK.json")))
    assert bank["a"]["value"] == 3 and bank["b"]["value"] == 2.5
    assert "device" not in bank
    assert bank["a"]["device"] == "tpu"


def test_key_drift_is_a_failure_not_a_skip(bench):
    """A successful child whose expected metric key vanished must FAIL
    the gate (key drift), never silently skip."""
    def fake_child(mode, attempts=3, timeout=420, **kw):
        if mode == "probe":
            return {"tpu_probe_ok": True, "device": "tpu"}, None
        if mode == "fwd":
            return {"renamed_key": 1.0, "device": "tpu"}, None
        return None, "timeout"

    bench._run_tpu_child = fake_child
    assert _run_main(bench) == 1
    doc = json.load(open(os.path.join(bench.REPO, "BENCH_FULL.json")))
    by = {c["metric"]: c for c in doc["checks"]}
    assert by["gpt2_fwd_tokens_per_s"]["ok"] is False
    assert "key drift" in by["gpt2_fwd_tokens_per_s"]["reason"]


def test_bank_reuse_requires_same_code_rev(bench, monkeypatch):
    """Reuse may stand in for a fresh measurement ONLY when the banked
    rows carry the CURRENT code fingerprint — rows from older code
    (or rows with none, e.g. pre-r05 banks) must re-measure."""
    monkeypatch.setattr(bench, "_code_rev", lambda: "rev-live")
    bench._bank({"decode_tokens_per_s": 5000.0, "device": "tpu"},
                group="decode")
    monkeypatch.setenv("ACX_BANK_REUSE_H", "18")
    assert bench._bank_reuse("decode") == {"decode_tokens_per_s": 5000.0}

    # Code changed since the row was banked -> refuse.
    monkeypatch.setattr(bench, "_code_rev", lambda: "rev-changed")
    assert bench._bank_reuse("decode") is None

    # No fingerprint at all (legacy row) -> refuse.
    bank_path = os.path.join(bench.REPO, "BENCH_BANK.json")
    bank = json.load(open(bank_path))
    del bank["decode_tokens_per_s"]["rev"]
    json.dump(bank, open(bank_path, "w"))
    monkeypatch.setattr(bench, "_code_rev", lambda: "rev-live")
    assert bench._bank_reuse("decode") is None

    # Reuse is opt-in: without the env the fresh row is never reused.
    monkeypatch.delenv("ACX_BANK_REUSE_H")
    bench._bank({"decode_tokens_per_s": 5000.0, "device": "tpu"},
                group="decode")
    assert bench._bank_reuse("decode") is None


def test_outage_attaches_banked_rows(bench, capsys):
    """A chip-unavailable run must still surface committed chip evidence:
    the final JSON line carries every banked TPU row with provenance
    instead of a tpu_error-only artifact (rounds 2-4 failure mode)."""
    bench._bank({"gpt2_fwd_tokens_per_s": 250000.0, "device": "tpu"},
                group="fwd")
    bench._run_tpu_child = lambda mode, **kw: (None, "timeout (probe)")
    assert _run_main(bench, full=False) == 0
    last = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")][-1]
    out = json.loads(last)
    assert "tpu_error" in out
    row = out["banked_tpu_rows"]["gpt2_fwd_tokens_per_s"]
    assert row["value"] == 250000.0
    assert row["ts"] and row["rev"]


def test_outage_refuses_cross_rev_speedups(bench, capsys, monkeypatch):
    """A `*_speedup` ratio only attaches when it AND both component rows
    carry the same recorded rev; mixed (or missing) revs land under
    banked_speedups_dropped instead — the stale pre-factoring 0.73x
    int8-KV row survived exactly because both sides defaulted to
    "unrecorded" and compared equal."""
    monkeypatch.setattr(bench, "_code_rev", lambda: "rev-a")
    bench._bank({"decode_tokens_per_s": 5000.0,
                 "decode_flash_tokens_per_s": 9000.0,
                 "decode_flash_speedup": 1.8, "device": "tpu"},
                group="decode")
    bench._run_tpu_child = lambda mode, **kw: (None, "timeout (probe)")

    def last_out():
        return json.loads(
            [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")][-1])

    assert _run_main(bench, full=False) == 0
    out = last_out()
    assert out["banked_tpu_rows"]["decode_flash_speedup"]["value"] == 1.8
    assert "decode_flash_speedup" not in out.get(
        "banked_speedups_dropped", {})

    # The variant row re-measured on different code: refuse the ratio
    # (the plain component rows still attach).
    monkeypatch.setattr(bench, "_code_rev", lambda: "rev-b")
    bench._bank({"decode_flash_tokens_per_s": 9500.0, "device": "tpu"},
                group="decode")
    assert _run_main(bench, full=False) == 0
    out = last_out()
    assert "decode_flash_speedup" not in out["banked_tpu_rows"]
    assert "decode_flash_tokens_per_s" in out["banked_tpu_rows"]
    assert "different revs" in \
        out["banked_speedups_dropped"]["decode_flash_speedup"]

    # Rows predating rev stamping never count as matching.
    bank_path = os.path.join(bench.REPO, "BENCH_BANK.json")
    bank = json.load(open(bank_path))
    for k in ("decode_tokens_per_s", "decode_flash_tokens_per_s",
              "decode_flash_speedup"):
        del bank[k]["rev"]
    json.dump(bank, open(bank_path, "w"))
    assert _run_main(bench, full=False) == 0
    out = last_out()
    assert "decode_flash_speedup" not in out.get("banked_tpu_rows", {})
    assert "unrecorded" in \
        out["banked_speedups_dropped"]["decode_flash_speedup"]


def test_midrun_outage_artifact_carries_banked_rows(bench):
    """Chip unavailable mid --full run: BENCH_FULL.json itself (not just the
    stdout line) must carry the banked evidence."""
    bench._bank({"decode_tokens_per_s": 6000.0, "device": "tpu"},
                group="decode")
    rows = {
        "probe": {"tpu_probe_ok": True, "device": "tpu"},
        "fwd": {"gpt2_fwd_tokens_per_s": 250000.0,
                "gpt2_fwd_b16s512_tokens_per_s": 380000.0,
                "device": "tpu"},
    }

    def fake_child(mode, attempts=3, timeout=420, **kw):
        if mode in rows:
            return rows[mode], None
        return None, f"timeout after {timeout}s (attempt 1)"

    bench._run_tpu_child = fake_child
    assert _run_main(bench) == 0
    doc = json.load(open(os.path.join(bench.REPO, "BENCH_FULL.json")))
    banked = doc["result"]["banked_tpu_rows"]
    assert banked["decode_tokens_per_s"]["value"] == 6000.0
