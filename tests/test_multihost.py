"""Multi-host distributed runtime: 2 real processes on localhost, gloo
cross-process collectives, hybrid ICI x DCN mesh. The multi-process
equivalent of the virtual-mesh tests — this is the topology a v5e pod
slice job runs (one process per host), shrunk to one machine.
"""

import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(port):
    import tempfile
    ckdir = tempfile.mkdtemp(prefix="acx_mh_ck_")
    procs = []
    try:
        for pid in (0, 1):
            env = dict(os.environ)
            # The workers run on plain CPU JAX, one device each.
            env.pop("XLA_FLAGS", None)
            env["JAX_PLATFORMS"] = "cpu"
            env["ACX_COORDINATOR"] = f"127.0.0.1:{port}"
            env["ACX_NPROCS"] = "2"
            env["ACX_PROC_ID"] = str(pid)
            env["ACX_CKPT_DIR"] = ckdir  # shared fresh checkpoint dir
            procs.append(subprocess.Popen(
                [sys.executable, WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        # Drain both pipes concurrently: sequential communicate() deadlocks
        # if the not-yet-drained worker fills its pipe buffer mid-collective.
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(len(procs)) as ex:
            futs = [ex.submit(p.communicate, timeout=280) for p in procs]
            outs = []
            for p, f in zip(procs, futs):
                out, err = f.result(timeout=290)
                outs.append((p.returncode, out, err))
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def test_two_process_distributed():
    # One retry with a fresh port: _free_port closes the probe socket
    # before the coordinator binds, so a busy host can steal the port.
    for attempt in (0, 1):
        outs = _run_workers(_free_port())
        if attempt == 0 and any(rc != 0 for rc, _, _ in outs):
            continue
        for rc, out, err in outs:
            assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err}"
            assert "MH_OK 52.0" in out, out
        return


def test_initialize_noop_single_process():
    """Without ACX_COORDINATOR, initialize() is a no-op (standalone runs)."""
    env = dict(os.environ)
    env.pop("ACX_COORDINATOR", None)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); "
         "from mpi_acx_tpu.parallel import multihost as mh; "
         "mh.initialize(); assert mh.process_count() == 1; print('OK')"
         % REPO],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout, r.stderr)
