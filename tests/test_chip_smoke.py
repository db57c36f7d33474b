"""The scaffolding around the chip run: where compiled programs are kept
(mpi_acx_tpu.backend.enable_compile_cache) and chip_smoke.py itself.

None of this is a chip run. On the CPU chip_smoke.py must FAIL; its phase
functions are rehearsed here at a tiny size — on one device and on four
of the suite's virtual devices — so that a wrong argument or mesh costs
no chip time.
"""

import os
import re
import subprocess
import sys

import jax
import pytest

from mpi_acx_tpu import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

_PROBE = """
import sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from mpi_acx_tpu import backend
path = backend.enable_compile_cache()
seen = []
jax.monitoring.register_event_listener(lambda e, **kw: seen.append(e))
def acx_cache_probe_{tag}(x):
    return jnp.tanh(x) * 3 + 1
jax.jit(acx_cache_probe_{tag})(jnp.ones((8, 128))).block_until_ready()
print("CACHE", path, jax.config.jax_compilation_cache_dir,
      sum(e.endswith("/cache_hits") for e in seen),
      sum(e.endswith("/cache_misses") for e in seen))
"""


def _probe(tag, env_dir=None):
    """One CPU process that compiles a jitted function through the
    helper; returns (cache dir it reported, dir JAX holds, hits,
    misses)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(backend.CACHE_ENV, None)
    if env_dir is not None:
        env[backend.CACHE_ENV] = env_dir
    r = subprocess.run([sys.executable, "-c",
                        _PROBE.format(repo=REPO, tag=tag)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    _, path, held, hits, misses = r.stdout.strip().splitlines()[-1].split()
    return path, held, int(hits), int(misses)


def _entries(path, tag):
    return [n for n in os.listdir(path)
            if n.startswith(f"jit_acx_cache_probe_{tag}-")
            and n.endswith("-cache")]


@pytest.mark.parametrize("placed", ["from_outside", "default"])
def test_two_processes_share_one_cache_entry(placed, tmp_path):
    """Two successive processes compile the same function through the
    helper: one entry, and the second process hits it — under
    JAX_COMPILATION_CACHE_DIR when it is set, under the fixed
    in-checkout directory when it is not."""
    tag = f"{placed}_{os.getpid()}"       # the FUNCTION is unique, not the dir
    env_dir = str(tmp_path / "cc") if placed == "from_outside" else None
    want = env_dir or backend.DEFAULT_CACHE_DIR
    try:
        first = _probe(tag, env_dir)
        second = _probe(tag, env_dir)
        assert first[:2] == second[:2] == (want, want)
        # The first process compiles the function (its name is new);
        # the second finds everything it compiles in the cache.
        assert first[3] >= 1 and second[2:] == (first[2] + first[3], 0), \
            (first, second)
        assert len(_entries(want, tag)) == 1
    finally:
        if os.path.isdir(want):
            for n in os.listdir(want):
                if f"acx_cache_probe_{tag}-" in n:
                    os.remove(os.path.join(want, n))


def test_no_directory_is_set_in_code_when_placed_from_outside(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv(backend.CACHE_ENV, "/somewhere/else")
    assert backend.enable_compile_cache() == "/somewhere/else"
    assert not [c for c in calls if "cache_dir" in c[0]], calls


def test_default_directory_is_fixed_and_inside_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.delenv(backend.CACHE_ENV, raising=False)
    a, b = backend.enable_compile_cache(), backend.enable_compile_cache()
    assert a == b == backend.DEFAULT_CACHE_DIR
    assert ("jax_compilation_cache_dir", a) in calls
    assert os.path.dirname(a) == REPO
    # Nothing of a temporary name, a pid or the time in it.
    assert not re.search(r"tmp|\d", os.path.relpath(a, REPO)), a
    ignored = subprocess.run(["git", "-C", REPO, "check-ignore", "-q", a])
    assert ignored.returncode == 0, f"{a} is not ignored by git"


def test_chip_smoke_fails_without_a_tpu():
    """On the CPU the script exits non-zero and never prints ok:true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode != 0, r.stdout
    assert r.stdout.strip(), r.stderr[-2000:]
    assert '"ok": true' not in r.stdout.strip().splitlines()[-1]
    assert '"platform": "tpu"' not in r.stdout


@pytest.fixture
def loopback_runtime_closed():
    """serve_fixed's hand-off opens the process's loopback Runtime, which
    lives until exit; close it so that the tests which open their own
    (test_disagg, test_runtime) find the native runtime free."""
    yield
    from mpi_acx_tpu.models import disagg
    if disagg._loopback_runtime is not None:
        disagg._loopback_runtime.finalize()
        disagg._loopback_runtime = None


@pytest.mark.parametrize("phase", ["serve_paged", "serve_fixed", "train",
                                   "tp_serve", "train_mesh"])
def test_phase_runs_at_tiny_size(phase, capsys, loopback_runtime_closed):
    """Every phase function, end to end at TINY: the one-chip phases on
    one CPU device, the --chips 4 phases on four virtual devices. The
    chip-only demands (a TPU, the Pallas kernel in the compiled step)
    are off; everything else the phase asserts holds here too."""
    assert chip_smoke.PHASES[phase](chip_smoke.TINY, seed=0,
                                    require_kernel=False)
    rows = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert rows and all('"ok": true' in ln for ln in rows), rows
    if phase == "serve_paged":
        # the engagement facts of both families through the one loop
        import json
        by = {r["phase"]: r for r in map(json.loads, rows)}
        assert by["serve_paged/bf16"]["paged_operator"] == "attention"
        assert by["serve_paged/bf16"]["paged_ffn"] == "dense:_mlp"
        row = by["serve_paged/lfm2"]
        assert row["paged_operator"] == "attention+conv"
        assert row["paged_ffn"].startswith(
            "dense:_dense_ffn+moe:sorted_expert_ffn/")
        assert 0 < row["moe_live_expert_share"] <= 1
        assert row["conv_tail_restores"] >= 2 <= row["prefix_hits"]
        assert row["programs_traced"][1] == 0
        row = by["serve_paged/jamba"]
        assert row["paged_operator"] == "attention+mamba"
        assert row["conv_tail_restores"] >= 2 <= row["prefix_hits"]
        assert 0 < row["state_snapshot_rows_hwm"] <= 4
        assert row["state_snapshots_taken"] >= 2
        assert row["state_bytes_slot"] > 0 and row["programs_traced"][1] == 0
        row = by["serve_paged/gigachat"]
        assert row["paged_operator"] == "latent_attention"
        assert row["paged_ffn"].startswith(
            "dense:_dense_ffn+moe:_shared_ffn+sorted_expert_ffn/")
        assert 0 < row["moe_pairs_held"] < row["moe_pairs_routed"]
        assert row["moe_pairs_held"] == 2 * row["moe_group_hits"]
        assert row["kv_bytes_token"] == 3 * 2 * 48
        assert row["prefix_hits"] >= 2 and row["programs_traced"][1] == 0
        row = by["serve_paged/nemotron"]
        assert row["paged_operator"] == "attention+mamba2"
        assert row["paged_ffn"].startswith(
            "moe:_shared_ffn+latent:sorted_expert_ffn/")
        assert row["state_snapshot_seats"] >= 2 <= row["prefix_hits"]
        assert 0 < row["moe_latent_rows"] == row["moe_pairs_held"] \
            < row["moe_pairs_routed"]
        assert row["state_bytes_moved"] == 2 * row["state_slot_steps"] \
            * row["state_bytes_slot"] > 0
        assert 0 < row["state_steps_dead"] and 0 < row["state_dead_share"] \
            == round(row["state_steps_dead"] / (row["state_steps_dead"]
                                                + row["state_slot_steps"]), 4)
        assert row["programs_traced"][1] == 0
        # the span record of every leg: what a call kept, no wait far
        # above its like in the warm call's few spans unless the machine
        # stopped, and the first call's programs by name
        for name in ("bf16", "int8", "lfm2", "jamba", "gigachat",
                     "nemotron"):
            row = by["serve_paged/" + name]
            assert row["spans"] >= 5 * row["prefills"] + 5 * row["steps"]
            assert len(row["stalls"]) == len(row["stall_ms"]) == 2
            assert (row["stalls"][1] == 0) == (row["stall_ms"][1] == 0)
            loads = row["programs_trace_lower_load_s"]
            assert row["programs_loaded"][0] >= row["programs_traced"][0]
            assert row["programs_loaded"][1] == 0
            assert {"paged_decode_chunk", "paged_prefill"} <= set(loads)
            assert all(len(v) == 3 for v in loads.values())
            assert 0 < row["programs_loaded_s"] <= row["wall_s"]
