"""Elastic-fleet membership through the Python stack (docs/DESIGN.md §12):
epoch/view/stats surface on Runtime, the multihost join budget and fleet
snapshot helpers, join-warm checkpoint restore, the serving loop's
slot-revive telemetry, and the rolling-restart itest end-to-end — including
a deliberately wedged join whose hang the doctor must attribute to the
victim even when the victim's flight dump is missing.

Fleet state seeds at first native-library use and stays armed for the
life of the process, so every test that instantiates ``Runtime`` runs in
a SUBPROCESS (worker modes of this file, the test_recovery.py pattern).
The pure-Python helpers run in-process.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _acxrun():
    from mpi_acx_tpu import runtime
    return runtime.acxrun_path()


def _rolling_restart():
    p = os.path.join(REPO, "build", "itests", "rolling-restart")
    if not os.path.exists(p):
        subprocess.run(["make", "-C", REPO, "itest"], check=True,
                       capture_output=True)
    return p


def _run(cmd, env_extra=None, timeout=120):
    env = dict(os.environ)
    env.pop("ACX_FAULT", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO, env=env)


# -- pure-Python surface ----------------------------------------------------


def test_fleet_state_names_cover_lifecycle():
    """The state-name table matches the lifecycle the native enum walks:
    JOIN -> ACTIVE -> DRAINING -> LEFT/DEAD, with index 0 reserved for
    unknown so a garbage value never renders as a real state."""
    from mpi_acx_tpu.runtime import FLEET_STATE_NAMES
    assert FLEET_STATE_NAMES[0] == "unknown"
    for name in ("joining", "active", "draining", "left", "dead"):
        assert name in FLEET_STATE_NAMES


def test_fleet_join_budget_defaults_and_env(monkeypatch):
    """Join budget = ACX_FLEET_JOIN_TIMEOUT_MS (default 10 s) plus the
    handshake margin; an explicit timeout wins over the env."""
    from mpi_acx_tpu.parallel import multihost
    monkeypatch.delenv("ACX_FLEET_JOIN_TIMEOUT_MS", raising=False)
    assert multihost.fleet_join_budget_s() == pytest.approx(11.0)
    assert multihost.fleet_join_budget_s(timeout_ms=4000.0) == \
        pytest.approx(5.0)
    monkeypatch.setenv("ACX_FLEET_JOIN_TIMEOUT_MS", "2500")
    assert multihost.fleet_join_budget_s() == pytest.approx(3.5)
    assert multihost.fleet_join_budget_s(margin_s=0.0,
                                         timeout_ms=1000.0) == \
        pytest.approx(1.0)


def test_serving_metrics_revive_field_defaults_zero():
    """slots_revived rides next to slots_shed so a serving run with no
    membership churn reports 0/0, not missing keys."""
    from mpi_acx_tpu.models.serving import ServingMetrics
    m = ServingMetrics()
    assert m.slots_shed == 0
    assert m.slots_revived == 0


def test_warm_start_empty_dir_returns_none(tmp_path):
    """A fleet that never checkpointed gives the joiner nothing to warm
    from: (None, None), keep the freshly built state."""
    from mpi_acx_tpu import checkpoint
    state, step = checkpoint.warm_start(str(tmp_path / "empty"),
                                        like={"w": np.zeros(4)})
    assert state is None and step is None


def test_warm_start_restores_latest_step(tmp_path):
    """Join-warm restore hands back the latest saved step bit-identical:
    the joiner serves the same weights the fleet is serving."""
    from mpi_acx_tpu import checkpoint
    d = str(tmp_path / "ckpt")
    with checkpoint.Checkpointer(d) as ckpt:
        ckpt.save(3, {"w": np.arange(4, dtype=np.float32)})
        ckpt.save(7, {"w": np.arange(4, dtype=np.float32) * 2})
    state, step = checkpoint.warm_start(
        d, like={"w": np.zeros(4, dtype=np.float32)})
    assert step == 7
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.arange(4, dtype=np.float32) * 2)


# -- Runtime fleet surface (subprocess: armed native state) -----------------


def test_fleet_view_loopback():
    """A 1-rank fleet boots at epoch >= 1 with its own slot ACTIVE and
    zeroed churn counters; fleet_snapshot agrees with the parts."""
    r = _run([sys.executable, __file__, "--fleet-loopback-worker"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FLEET LOOPBACK OK" in r.stdout


def test_fleet_leave_loopback_is_clean():
    """A graceful leave with nothing in flight cancels 0 ops and moves
    this rank's own slot out of ACTIVE."""
    r = _run([sys.executable, __file__, "--fleet-leave-worker"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FLEET LEAVE OK" in r.stdout


def _fleet_loopback_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    from mpi_acx_tpu.parallel import multihost
    rt = runtime.Runtime()
    assert rt.fleet_epoch() >= 1
    assert rt.fleet_view() == ["active"]
    stats = rt.fleet_stats()
    assert set(stats) == {"epoch", "joins", "leaves", "deaths", "active"}
    assert stats["active"] == 1
    assert stats["joins"] == stats["leaves"] == stats["deaths"] == 0
    snap = multihost.fleet_snapshot(rt)
    assert snap["epoch"] == rt.fleet_epoch()
    assert snap["view"] == ["active"]
    assert snap["stats"]["active"] == 1
    print("FLEET LOOPBACK OK", flush=True)
    return 0


def _fleet_leave_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    assert rt.fleet_leave(500.0) == 0  # nothing in flight: clean departure
    assert rt.fleet_stats()["active"] == 0
    assert rt.fleet_view() != ["active"]
    print("FLEET LEAVE OK", flush=True)
    return 0


# -- rolling restart end-to-end ---------------------------------------------


def test_rolling_restart_replaces_every_rank():
    """The capstone itest under acxrun: every rank of a 2-rank socket
    fleet is replaced one at a time under load, the fleet epoch climbs
    monotonically, and the run exits 0."""
    r = _run([_acxrun(), "-np", "2", "-timeout", "100",
              "-transport", "socket", _rolling_restart()],
             timeout=150)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rolling-restart: OK" in r.stdout


def test_wedged_join_doctor_attribution(tmp_path):
    """A deliberately wedged join (the replacement never dials in) must
    not hang the survivors: they time the join out on the
    ACX_FLEET_JOIN_TIMEOUT_MS budget, dump flight state, and exit 7.
    acx_doctor.py then attributes the stall to the victim rank even with
    the victim's own dump deleted — the gap corroborates the verdict
    (satellite: tolerate a missing per-rank dump)."""
    flight = str(tmp_path / "rr")
    r = _run([_acxrun(), "-np", "3", "-timeout", "100",
              "-transport", "socket", _rolling_restart()],
             env_extra={"ACX_RR_WEDGE": "1",
                        "ACX_FLEET_JOIN_TIMEOUT_MS": "6000",
                        "ACX_FLIGHT": flight},
             timeout=150)
    assert r.returncode == 7, r.stdout + r.stderr
    dumps = sorted(str(p) for p in tmp_path.glob("rr.rank*.flight.json"))
    assert len(dumps) >= 2, r.stdout + r.stderr
    victim = str(tmp_path / "rr.rank1.flight.json")
    if victim in dumps:
        os.unlink(victim)
        dumps.remove(victim)
    d = _run([sys.executable, os.path.join(REPO, "tools", "acx_doctor.py"),
              "--json"] + dumps)
    assert d.returncode == 0, d.stdout + d.stderr
    verdict = json.loads(d.stdout)
    assert verdict["culprit"] == 1, verdict
    assert verdict["anomaly"] in ("dead_link", "missing_dump"), verdict
    assert 1 in verdict.get("missing_ranks", []), verdict


if __name__ == "__main__":
    if "--fleet-loopback-worker" in sys.argv:
        raise SystemExit(_fleet_loopback_worker())
    if "--fleet-leave-worker" in sys.argv:
        raise SystemExit(_fleet_leave_worker())
    raise SystemExit(2)
