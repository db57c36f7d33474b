"""Device->proxy flag bridge (SURVEY.md §2 C6, the reference's defining
coupling): a Pallas kernel's flag write must drive a real wire transfer.

Two acxrun ranks; the sender's partition payloads are computed by Pallas
kernels that mark readiness in the same kernel, the readiness crosses the
Python/native boundary into the proxy-polled table, the proxy pushes the
partitions onto the wire, and the receiver's arrival decision is made by
the Pallas parrived kernel over a mirror of the native table. See
tests/device_bridge_worker.py for the per-rank script.
"""

import os
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "device_bridge_worker.py")


def _run_worker(worker, extra_env=None):
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True, timeout=600)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    import sys
    return subprocess.run(
        [os.path.join(REPO, "build", "acxrun"), "-np", "2", "-timeout",
         "480", sys.executable, worker],
        env=env, capture_output=True, text=True, timeout=540)


def test_kernel_pready_drives_wire_transfer():
    r = _run_worker(WORKER)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("BRIDGE_OK 4") == 2, r.stdout + r.stderr


def test_in_program_partitioned_publish(tmp_path):
    """VERDICT r03 item 3: ONE jitted program per rank — the sender's
    ordered io_callback publish nodes fire between Pallas produce
    kernels inside the running program, the receiver's while_loop polls
    the table in-program, and the receiver PROVES overlap by witnessing
    a partially-completed flag table. The ACX_TRACE timeline must show
    the per-partition wire pushes staggered across the program (not a
    tail batch after it)."""
    import json
    tr = str(tmp_path / "ip")
    stagger_s = 0.04
    r = _run_worker(
        os.path.join(REPO, "tests", "device_bridge_inprogram_worker.py"),
        extra_env={"ACX_TRACE": tr, "ACX_IP_STAGGER_S": str(stagger_s)})
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("INPROGRAM_OK 4") == 2, r.stdout + r.stderr

    # Sender-side trace: one pready_wire per partition, spread over at
    # least two stagger intervals — the proxy pushed partitions while
    # the program was still running, not after it returned.
    d = json.loads((tmp_path / "ip.rank0.trace.json").read_text())
    wires = sorted(float(e["ts"]) for e in d["traceEvents"]
                   if e["name"] == "pready_wire")
    assert len(wires) == 4, d["traceEvents"]
    assert wires[-1] - wires[0] > 2 * stagger_s * 1e6, wires
