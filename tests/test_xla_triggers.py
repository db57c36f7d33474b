"""In-program MPIX triggers (SURVEY.md §7.1 row 3): a single jitted XLA
computation fires a native transfer at an interior program point and
consumes the reply — the PJRT-host-callback analogue of the reference's
stream memOps triggers (sendrecv.cu:152-208). Two acxrun ranks run
tests/xla_triggers_worker.py."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "xla_triggers_worker.py")


def test_jitted_program_triggers_native_transfer():
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True, timeout=600)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [os.path.join(REPO, "build", "acxrun"), "-np", "2", "-timeout",
         "240", sys.executable, WORKER],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("TRIG_OK") == 2, r.stdout + r.stderr
