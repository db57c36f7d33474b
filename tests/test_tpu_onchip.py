"""The trigger + bridge plane's two-rank worker, on the CPU.

tests/tpu_onchip_worker.py is the proof that a compiled jitted program
fires io_callback triggers and that a Pallas flag kernel publishes
through the device->proxy bridge, driving a real 2-rank wire transfer.
Its chip leg (rank 0 on the TPU, rank 1 on the CPU) is chip_smoke.py's
trigger phase; here the same worker runs in cpu/cpu mode, so the launch
plumbing and the program shapes stay continuously tested.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "tpu_onchip_worker.py")


def test_onchip_worker_cpu_mode():
    """The worker's program shapes and plumbing, chip-free."""
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True, timeout=600)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["ACX_RANK0_PLATFORM"] = "cpu"
    r = subprocess.run(
        [os.path.join(REPO, "build", "acxrun"), "-np", "2", "-timeout",
         "420", sys.executable, WORKER],
        env=env, capture_output=True, text=True, timeout=480)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("ONCHIP_OK") == 2, r.stdout + r.stderr
