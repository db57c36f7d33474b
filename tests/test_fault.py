"""Resilience plane through the Python stack: env-driven fault
injection, retry/backoff, op deadlines, heartbeat dead-peer detection
(src/core/fault.cc, src/core/proxy.cc, src/net/socket_transport.cc),
plus the serving loop's request re-queue (models/serving.py).

ACX_FAULT / ACX_HEARTBEAT_MS seed process-global native state at first
use and stay armed for the life of the process, so every fault-armed
path runs in a SUBPROCESS (worker modes of this file, the
test_runtime.py pattern) — the shared pytest process never arms one.
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


def _run(cmd, env_extra=None, timeout=120):
    env = dict(os.environ)
    env.pop("ACX_FAULT", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO, env=env)


# -- launcher-level spec validation ----------------------------------------


def test_acxrun_rejects_bad_fault_spec():
    """A typo'd -fault spec must die at launch (exit 2), not silently
    run the job fault-free."""
    r = _run([_acxrun(), "-np", "1", "-fault", "bogus:nth=1",
              "/bin/true"])
    assert r.returncode == 2, r.stdout + r.stderr
    assert "bad -fault schedule" in r.stderr


def test_acxrun_rejects_truncated_schedule():
    """A trailing ';' means a spec went missing (shell quoting): refuse
    the half-schedule rather than run a different experiment."""
    r = _run([_acxrun(), "-np", "1", "-fault", "drop:nth=1;",
              "/bin/true"])
    assert r.returncode == 2, r.stdout + r.stderr
    assert "bad -fault schedule" in r.stderr


# -- transient drop -> retry -> success ------------------------------------


def test_transient_drop_retried_to_completion(tmp_path):
    """acceptance (a): rank 0's first send is swallowed at issue; the
    proxy's backoff retry re-posts it and the ring completes. Counters
    land in resilience_stats AND the ACX_TRACE event stream."""
    trace = str(tmp_path / "t")
    r = _run([_acxrun(), "-np", "2", "-fault",
              "drop:rank=0:kind=send:nth=1",
              sys.executable, __file__, "--drop-worker"],
             env_extra={"ACX_TRACE": trace})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DROP RETRY OK" in r.stdout
    events = [e["name"] for e in
              json.load(open(f"{trace}.rank0.trace.json"))["traceEvents"]]
    assert "fault_drop" in events, events
    assert "op_retry" in events, events


def test_injected_fail_raises_typed_error():
    """fail:... completes the op with MPIX_ERR_INJECTED and wait()
    surfaces it as AcxError (not a hang, not a bare status)."""
    r = _run([sys.executable, __file__, "--fail-worker"],
             env_extra={"ACX_FAULT": "fail:rank=0:kind=send:nth=1"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FAIL RAISED OK" in r.stdout


def test_deadline_bounds_unmatched_recv():
    """A recv nobody ever sends to completes with AcxTimeoutError
    within the configured deadline instead of blocking forever."""
    t0 = time.monotonic()
    r = _run([sys.executable, __file__, "--deadline-worker"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DEADLINE OK" in r.stdout
    assert time.monotonic() - t0 < 60


def test_dead_peer_raises_within_deadline():
    """acceptance (b): a peer that exits mid-job is declared dead by
    the heartbeat sweep and the blocked Python wait() raises a typed
    exception within the configured bound."""
    r = _run([_acxrun(), "-np", "2",
              sys.executable, __file__, "--deadpeer-worker"],
             env_extra={"ACX_HEARTBEAT_MS": "25",
                        "ACX_PEER_TIMEOUT_MS": "200",
                        "ACX_PEER_GRACE_MS": "500"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DEADPEER OK" in r.stdout


# -- multihost bootstrap degrades cleanly ----------------------------------


def test_multihost_initialize_bounded():
    """A worker pointed at a coordinator that isn't there raises a
    RuntimeError naming the rendezvous triple within ACX_INIT_TIMEOUT_S
    (where the JAX build supports a bounded init; SKIP otherwise)."""
    code = (
        "import inspect, os, jax\n"
        "import sys\n"
        "sys.path.insert(0, " + repr(REPO) + ")\n"
        "if 'initialization_timeout' not in inspect.signature("
        "jax.distributed.initialize).parameters:\n"
        "    print('SKIP: no initialization_timeout'); raise SystemExit(0)\n"
        "from mpi_acx_tpu.parallel import multihost\n"
        "try:\n"
        "    multihost.initialize()\n"
        "except RuntimeError as e:\n"
        "    assert 'multihost initialize failed' in str(e), e\n"
        "    print('INIT BOUNDED OK'); raise SystemExit(0)\n"
        "raise SystemExit('initialize() against a dead coordinator "
        "returned')\n")
    r = _run([sys.executable, "-c", code],
             env_extra={"JAX_PLATFORMS": "cpu",
                        "ACX_COORDINATOR": "127.0.0.1:1",
                        "ACX_NPROCS": "2", "ACX_PROC_ID": "1",
                        "ACX_INIT_TIMEOUT_S": "5"},
             timeout=180)
    if "SKIP" in r.stdout:
        pytest.skip("jax.distributed.initialize has no bounded init")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "INIT BOUNDED OK" in r.stdout


# -- serving: failed step costs a replay, not the server -------------------


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


def test_serving_persistent_failure_raises_with_rid():
    """Past max_request_retries the failure propagates, naming the
    request — a permanently broken step can't spin the server."""
    from mpi_acx_tpu.models import serving
    cfg, params, tfm = _tiny()
    prompts = _tiny_prompts(cfg, n=2)
    fns = serving.make_server_fns(params, cfg, tfm)

    def dead_step(cache, tok, keys):
        raise RuntimeError("wedged device")

    with pytest.raises(RuntimeError, match="max_request_retries"):
        serving.serve_greedy(
            params, cfg, prompts, n_new=4, n_slots=2, max_len=32,
            family=tfm, max_request_retries=1,
            server_fns=(fns[0], dead_step, fns[2], fns[3], fns[4],
                        fns[5]))


def test_serving_rejects_zero_length_prompt():
    from mpi_acx_tpu.models import serving
    cfg, params, tfm = _tiny()
    with pytest.raises(AssertionError, match="zero-length"):
        serving.serve_greedy(params, cfg,
                             [np.asarray([1, 2], np.int32),
                              np.asarray([], np.int32)],
                             n_new=2, n_slots=2, max_len=32, family=tfm)


def test_serving_rejects_chunk_mismatched_fns():
    """The tuple carries its baked-in chunk; reusing it under another
    chunk must fail at the door, not mis-slice token blocks."""
    from mpi_acx_tpu.models import serving
    cfg, params, tfm = _tiny()
    fns = serving.make_server_fns(params, cfg, tfm, chunk=2)
    with pytest.raises(AssertionError, match="chunk"):
        serving.serve_greedy(params, cfg, _tiny_prompts(cfg, n=2),
                             n_new=4, n_slots=2, max_len=32, family=tfm,
                             chunk=1, server_fns=fns)


# -- subprocess workers ----------------------------------------------------


def _drop_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    right = (rt.rank + 1) % rt.size
    left = (rt.rank - 1) % rt.size
    src = np.full(16, rt.rank * 10, dtype=np.int32)
    dst = np.full(16, -1, dtype=np.int32)
    s = rt.isend_enqueue(src, dest=right, tag=1)
    rv = rt.irecv_enqueue(dst, source=left, tag=1)
    rt.wait(rv)
    rt.wait(s)
    errs = int(not (dst == left * 10).all())
    if rt.rank == 0:
        st = rt.resilience_stats()
        errs |= int(st["fault_drops"] < 1 or st["retries"] < 1)
        # Merged view reaches the same counters (proxy_stats satellite).
        errs |= int(rt.proxy_stats()["retries"] != st["retries"])
    errs = rt.allreduce_max(errs)
    if rt.rank == 0 and errs == 0:
        print("DROP RETRY OK")
    rt.finalize()
    return errs


def _fail_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    src = np.arange(8, dtype=np.int32)
    s = rt.isend_enqueue(src, dest=0, tag=2)
    try:
        rt.wait(s)
    except runtime.AcxError as e:
        assert e.error == runtime.ERR_INJECTED, e
        assert rt.resilience_stats()["fault_fails"] >= 1
        print("FAIL RAISED OK")
        rt.finalize()
        return 0
    return 1


def _deadline_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    rt.set_deadline(200.0)
    assert abs(rt.get_deadline() - 200.0) < 1e-6
    dst = np.zeros(8, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=0, tag=3)  # never matched
    t0 = time.monotonic()
    try:
        rt.wait(rv)
    except runtime.AcxTimeoutError:
        elapsed = time.monotonic() - t0
        assert elapsed < 30, elapsed
        assert rt.resilience_stats()["timeouts"] >= 1
        rt.set_deadline(0.0)
        print("DEADLINE OK")
        rt.finalize()
        return 0
    return 1


def _deadpeer_worker() -> int:
    sys.path.insert(0, REPO)
    from mpi_acx_tpu import runtime
    rt = runtime.Runtime()
    if rt.rank != 0:
        # Crash without farewell: the heartbeat sweep must notice.
        sys.stdout.flush()
        os._exit(0)
    rt.set_deadline(10000.0)  # failsafe so a missed detection still ends
    dst = np.zeros(8, dtype=np.int32)
    rv = rt.irecv_enqueue(dst, source=1, tag=4)
    try:
        rt.wait(rv)
    except runtime.AcxPeerDeadError:
        assert rt.resilience_stats()["peers_dead"] >= 1
    except runtime.AcxTimeoutError:
        pass  # deadline failsafe: still bounded, still typed
    else:
        return 1
    print("DEADPEER OK", flush=True)
    os._exit(0)  # peer is gone; skip the finalize barrier entirely


if __name__ == "__main__":
    if "--drop-worker" in sys.argv:
        raise SystemExit(_drop_worker())
    if "--fail-worker" in sys.argv:
        raise SystemExit(_fail_worker())
    if "--deadline-worker" in sys.argv:
        raise SystemExit(_deadline_worker())
    if "--deadpeer-worker" in sys.argv:
        raise SystemExit(_deadpeer_worker())
    raise SystemExit("unknown worker mode")
