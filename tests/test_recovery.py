"""Survivable links through the Python stack (docs/DESIGN.md §9):
graceful drain (Runtime.drain / MPIX_Drain), recovery counters in
resilience/metrics snapshots, the serving loop's uncharged
requeue-on-peer-loss, and the chaos-ring itest's CRC/NAK/replay
counters landing in the metrics plane.

Native recovery state (ACX_RECONNECT_*, ACX_METRICS) seeds at first
library use and stays armed for the life of the process, so every armed
path runs in a SUBPROCESS (worker modes of this file, the test_fault.py
pattern). The serving-loop tests are pure JAX/CPU and run in-process.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _acxrun():
    from mpi_acx_tpu import runtime
    return runtime.acxrun_path()


def _chaos_ring():
    p = os.path.join(REPO, "build", "itests", "chaos-ring")
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


# -- drain: bounded cancellation of in-flight ops ---------------------------


def test_drain_cancels_unmatched_loopback_recv():
    """An irecv nobody will ever match is cancelled by drain() within its
    timeout: drain returns 1, the waiter raises the typed error the
    cancel stamped, and a second drain of the now-empty table returns
    0."""
    r = _run([sys.executable, __file__, "--drain-loopback-worker"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DRAIN LOOPBACK OK" in r.stdout


def test_drain_unblocks_survivor_of_dead_peer():
    """acceptance: a rank dies mid-flight on the socket plane with the
    reconnect ladder pinned long (the op parks in RECOVERING, no failure
    detector will save the waiter) — the survivor's drain() cancels the
    op with a typed error and the process exits 0."""
    r = _run([_acxrun(), "-np", "2", "-transport", "socket",
              sys.executable, __file__, "--drain-socket-worker"],
             env_extra={"ACX_RECONNECT_MAX": "8",
                        "ACX_RECONNECT_BACKOFF_MS": "500"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DRAIN SOCKET OK" in r.stdout


# -- recovery counters reach every stats surface ----------------------------


def test_drain_while_recovering_no_double_count():
    """Drain during the RECOVERING window (peer lost, reconnect ladder
    pinned long so the link sits mid-recovery for seconds): the parked op
    cancels in bounded time with a typed error, a second drain returns 0,
    and drained_slots moves by exactly the first drain's count — no
    double-charge across repeated drains."""
    r = _run([_acxrun(), "-np", "2", "-transport", "socket",
              sys.executable, __file__, "--drain-recovering-worker"],
             env_extra={"ACX_RECONNECT_MAX": "8",
                        "ACX_RECONNECT_BACKOFF_MS": "500"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DRAIN RECOVERING OK" in r.stdout


def test_recovery_counters_in_metrics_registry():
    """Runtime.metrics() (the ACX_METRICS registry) and
    Runtime.recovery_stats() both expose the survivable-link counters by
    name, and a drained op ticks drained_slots in both."""
    r = _run([sys.executable, __file__, "--metrics-keys-worker"],
             env_extra={"ACX_METRICS": "1"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RECOVERY METRICS OK" in r.stdout


def test_chaos_ring_counters_reach_metrics_json(tmp_path):
    """chaos-ring under corrupt_frame heals (exit 0, byte-exact payloads)
    AND the healing is visible: the per-rank metrics dumps carry
    crc_rejects / naks_sent on the receiver and frames_replayed on the
    sender."""
    m = str(tmp_path / "m")
    r = _run([_acxrun(), "-np", "2", "-transport", "socket",
              "-fault", "corrupt_frame:rank=0:nth=2",
              _chaos_ring()],
             env_extra={"ACX_METRICS": m, "ACX_CHAOS_ROUNDS": "10"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "chaos-ring: OK" in r.stdout
    totals = {}
    for rank in (0, 1):
        d = json.loads((tmp_path / f"m.rank{rank}.metrics.json").read_text())
        for k, v in d["counters"].items():
            totals[k] = totals.get(k, 0) + v
    assert totals["crc_rejects"] >= 1, totals
    assert totals["naks_sent"] >= 1, totals
    assert totals["frames_replayed"] >= 1, totals


# -- replay_broken: budget overrun latches, next loss is terminal -----------


def test_replay_broken_latch_end_to_end():
    """Overrunning ACX_REPLAY_BUF_BYTES latches the link replay_broken:
    the gauge is live in Runtime.recovery_stats(), and when the peer then
    dies the parked op resolves to a typed error in bounded time (the
    broken link cannot heal, so it dead-latches instead of recovering)
    and the gauge settles back to 0."""
    r = _run([_acxrun(), "-np", "2", "-transport", "socket",
              sys.executable, __file__, "--replay-broken-worker"],
             env_extra={"ACX_REPLAY_BUF_BYTES": "64",
                        "ACX_RECONNECT_MAX": "2",
                        "ACX_RECONNECT_BACKOFF_MS": "50"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "REPLAY BROKEN OK" in r.stdout
    # The runtime said so out loud, once, at latch time.
    assert "overran ACX_REPLAY_BUF_BYTES" in r.stderr, r.stderr


# -- serving: peer loss requeues without charging the retry budget ----------


def _tiny():
    import jax
    from mpi_acx_tpu.models import transformer as tfm
    cfg = tfm.tiny_config(vocab=61, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_seq=96)
    return cfg, tfm.init_params(jax.random.key(0), cfg), tfm


def _tiny_prompts(cfg, n=5):
    import jax
    ks = jax.random.split(jax.random.key(3), n)
    lens = [5, 9, 3, 7, 4]
    return [np.asarray(jax.random.randint(ks[i], (lens[i % len(lens)],),
                                          0, cfg.vocab), np.int32)
            for i in range(n)]


def test_serving_requeues_on_peer_loss_without_charge():
    """A step failure shaped like a lost rank (AcxPeerDeadError) requeues
    the in-flight requests WITHOUT spending their retry budget — proven
    by serving with max_request_retries=0, where a charged requeue would
    raise — sheds one slot to match the lost capacity, keeps serving,
    and still produces outputs bit-equal to the failure-free run."""
    from mpi_acx_tpu import runtime
    from mpi_acx_tpu.models import serving
    cfg, params, tfm = _tiny()
    prompts = _tiny_prompts(cfg)
    want = serving.serve_greedy(params, cfg, prompts, n_new=6, n_slots=3,
                                max_len=32, family=tfm)

    fns = serving.make_server_fns(params, cfg, tfm)
    prefill_fn, step_fn, scatter_fn, chunk, kv8, smp = fns
    calls = {"n": 0}

    def lossy_step(cache, tok, keys):
        calls["n"] += 1
        if calls["n"] == 2:
            raise runtime.AcxPeerDeadError(
                "tpu-acx: peer dead (error=20, source=1, tag=0)",
                runtime.ERR_PEER_DEAD, 1, 0)
        return step_fn(cache, tok, keys)

    got = serving.serve_greedy(
        params, cfg, prompts, n_new=6, n_slots=3, max_len=32, family=tfm,
        max_request_retries=0,
        server_fns=(prefill_fn, lossy_step, scatter_fn, chunk, kv8, smp))
    assert calls["n"] > 2, "peer loss fired before the loop finished"
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert got.metrics.peer_requeues >= 1, got.metrics
    assert got.metrics.slots_shed == 1, got.metrics
    # Uncharged: no victim's retry counter moved.
    assert all(r.retries == 0 for r in got.metrics.per_request), \
        got.metrics.per_request


def test_serving_charged_failure_still_bounded():
    """A non-peer-loss failure keeps the old contract: it charges the
    budget and a persistent one propagates past max_request_retries —
    the uncharged path must not have unbounded every failure."""
    from mpi_acx_tpu.models import serving
    cfg, params, tfm = _tiny()
    fns = serving.make_server_fns(params, cfg, tfm)

    def dead_step(cache, tok, keys):
        raise RuntimeError("wedged device")

    with pytest.raises(RuntimeError, match="max_request_retries"):
        serving.serve_greedy(
            params, cfg, _tiny_prompts(cfg, n=2), n_new=4, n_slots=2,
            max_len=32, family=tfm, max_request_retries=1,
            server_fns=(fns[0], dead_step, fns[2], fns[3], fns[4],
                        fns[5]))


# -- multihost: recovery-aware patience -------------------------------------


def test_recovery_budget_tracks_reconnect_ladder(monkeypatch):
    """recovery_budget_s mirrors the native dial ladder: explicit args
    are summed exponentially with the cap, and the env-seeded form reads
    the same knobs the transport does."""
    from mpi_acx_tpu.parallel import multihost
    # 5 attempts, 50ms base: waits 50+100+200+400 = 750ms + 1s margin.
    assert abs(multihost.recovery_budget_s(5, 50.0) - 1.75) < 1e-9
    # The per-wait cap bounds the tail: 4 waits of 100,200,400,500.
    assert abs(multihost.recovery_budget_s(5, 100.0, cap_ms=500.0)
               - 2.2) < 1e-9
    monkeypatch.setenv("ACX_RECONNECT_MAX", "3")
    monkeypatch.setenv("ACX_RECONNECT_BACKOFF_MS", "100")
    assert abs(multihost.recovery_budget_s() - 1.3) < 1e-9


# -- subprocess workers ----------------------------------------------------


def _drain_loopback_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    dst = np.zeros(8, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=0, tag=11)  # never matched
    t0 = time.monotonic()
    n = rt.drain(200.0)
    assert time.monotonic() - t0 < 30
    assert n == 1, n
    try:
        rt.wait(rv)
        return 1  # a cancelled op must not look completed-clean
    except runtime.AcxTimeoutError:
        pass  # loopback peer is healthy, so the cancel stamps TIMEOUT
    assert rt.recovery_stats()["drained_slots"] >= 1
    assert rt.proxy_stats()["drained_slots"] >= 1  # merged view, same data
    assert rt.drain(50.0) == 0  # nothing left in flight
    print("DRAIN LOOPBACK OK")
    rt.finalize()
    return 0


def _drain_socket_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    if rt.rank == 1:
        time.sleep(0.1)  # let rank 0 post against us first
        sys.stdout.flush()
        os._exit(0)      # die mid-flight: no finalize, no goodbye
    dst = np.zeros(8, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=1, tag=12)
    time.sleep(0.2)
    n = rt.drain(400.0)
    assert n >= 1, n
    try:
        rt.wait(rv)
        return 1
    except (runtime.AcxPeerDeadError, runtime.AcxTimeoutError):
        pass  # PEER_DEAD while the link recovers; TIMEOUT otherwise
    assert rt.recovery_stats()["drained_slots"] >= 1
    print("DRAIN SOCKET OK", flush=True)
    os._exit(0)  # peer is gone; skip the finalize barrier entirely


def _drain_recovering_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    if rt.rank == 1:
        # Exit only after rank 0's recv is provably posted (its token
        # send follows the irecv): an EOF with nothing in flight would
        # dead-latch immediately instead of opening a RECOVERING window.
        tok = np.zeros(1, dtype=np.int32)
        rt.wait(rt.irecv_enqueue(tok, source=0, tag=22))
        os._exit(0)      # die mid-flight: no finalize, no goodbye
    dst = np.zeros(8, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=1, tag=21)
    tok = np.ones(1, dtype=np.int32)
    rt.wait(rt.isend_enqueue(tok, dest=1, tag=22))
    # Wait for the cut wire to be noticed and the link to enter RECOVERING
    # (the pinned 8 x 500ms ladder keeps the window open for ~10s).
    deadline = time.monotonic() + 10
    while rt.recovery_stats()["links_recovering"] < 1:
        assert time.monotonic() < deadline, rt.recovery_stats()
        time.sleep(0.01)
    base = rt.recovery_stats()["drained_slots"]
    t0 = time.monotonic()
    n1 = rt.drain(300.0)
    assert time.monotonic() - t0 < 30  # bounded, not a hang
    assert n1 == 1, n1
    try:
        rt.wait(rv)
        return 1  # a drained op must not look completed-clean
    except (runtime.AcxPeerDeadError, runtime.AcxTimeoutError):
        pass  # PEER_DEAD while the link recovers; TIMEOUT otherwise
    assert rt.drain(100.0) == 0  # nothing left: the cancel latched
    stats = rt.recovery_stats()
    assert stats["drained_slots"] == base + 1, stats
    print("DRAIN RECOVERING OK", flush=True)
    os._exit(0)  # peer is gone; skip the finalize barrier entirely


def _replay_broken_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    if rt.rank == 1:
        # Receive the sends that overrun rank 0's replay budget, tell
        # rank 0 we're done, then die without finalize — the broken
        # link's next loss must be terminal, not a heal.
        buf = np.zeros(256, dtype=np.int32)
        for i in range(3):
            rt.wait(rt.irecv_enqueue(buf, source=0, tag=31))
            assert buf[0] == i, (i, buf[0])
        tok = np.ones(1, dtype=np.int32)
        rt.wait(rt.isend_enqueue(tok, dest=0, tag=32))
        time.sleep(0.1)  # let the token frame drain off the socket
        os._exit(0)
    # Each 1 KiB eager frame dwarfs the 64-byte budget, so recording it
    # evicts unacked bytes and latches replay_broken on first full write.
    src = np.zeros(256, dtype=np.int32)
    for i in range(3):
        src[0] = i
        rt.wait(rt.isend_enqueue(src, dest=1, tag=31))
    deadline = time.monotonic() + 10
    while rt.recovery_stats()["replay_broken_links"] < 1:
        assert time.monotonic() < deadline, rt.recovery_stats()
        time.sleep(0.01)
    tok = np.zeros(1, dtype=np.int32)
    rt.wait(rt.irecv_enqueue(tok, source=1, tag=32))
    assert tok[0] == 1
    # Park an op against the (about to be dead) peer. The short pinned
    # ladder means the EOF dead-latches within ~1s; the posted recv must
    # resolve to a typed error, never hang.
    dst = np.zeros(8, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=1, tag=33)
    t0 = time.monotonic()
    try:
        rt.wait(rv)
        return 1  # completing clean against a dead peer is the bug
    except (runtime.AcxPeerDeadError, runtime.AcxTimeoutError):
        pass
    assert time.monotonic() - t0 < 30
    # Dead-latch settles the gauge: a gone link is no longer "moving but
    # fragile".
    assert rt.recovery_stats()["replay_broken_links"] == 0, \
        rt.recovery_stats()
    print("REPLAY BROKEN OK", flush=True)
    os._exit(0)  # peer is gone; skip the finalize barrier entirely


def _metrics_keys_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    keys = ("reconnects", "replayed_frames", "crc_rejects", "naks_sent",
            "drained_slots", "links_recovering")
    rs = rt.recovery_stats()
    assert all(k in rs for k in keys), rs
    # Drain an unmatched recv so drained_slots is provably live, then
    # check the metrics registry mirrors the recovery counters by name.
    dst = np.zeros(4, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=0, tag=13)
    assert rt.drain(100.0) == 1
    try:
        rt.wait(rv)
        return 1
    except runtime.AcxTimeoutError:
        pass
    c = rt.metrics()["counters"]
    for k in ("reconnects", "frames_replayed", "crc_rejects", "naks_sent",
              "drained_slots"):
        assert k in c, sorted(c)
    assert c["drained_slots"] >= 1, c
    print("RECOVERY METRICS OK")
    rt.finalize()
    return 0


if __name__ == "__main__":
    if "--drain-loopback-worker" in sys.argv:
        raise SystemExit(_drain_loopback_worker())
    if "--drain-socket-worker" in sys.argv:
        raise SystemExit(_drain_socket_worker())
    if "--drain-recovering-worker" in sys.argv:
        raise SystemExit(_drain_recovering_worker())
    if "--replay-broken-worker" in sys.argv:
        raise SystemExit(_replay_broken_worker())
    if "--metrics-keys-worker" in sys.argv:
        raise SystemExit(_metrics_keys_worker())
    raise SystemExit("unknown worker mode")
