"""Kernels: the Mamba layers' prefill scan's share of its roofline in
the traced refill. Time: summed device time, inside the traced window,
of the events named ``%ssm_scan*`` (one a Mamba layer a prefill; on the
chip XLA wraps the Mosaic call and the slice of its snapshot rows in a
``fusion`` that keeps the call's name, so the text need not hold
``tpu_custom_call``). Work (``flops_jamba.ssm_scan_work``): what the scan
must read and write for the bucket each call ran over (``u``, ``z``,
``dt``, ``B``, ``C`` and the state before in; ``y``, the snapshots and
the end state out), the bucket and the snapshots read from the call's
own result shapes, at the chip's HBM peak: ``peaks.json`` holds no
vector peak and the scan has no matrix product, so the share reads LOW
where the vector unit bounds the kernel (PERF.md sets its operation
count against the chip's vector rate). Returns nothing when the program
has no such call, or when the calls are not a whole multiple of the
Mamba layers."""
import re

from benchmarks import flops, flops_jamba, trace_reduce

KERNEL = ("%ssm_scan",)


def read(run):
    if not run["traced"]:
        return None
    c = run["config"]
    ch = flops_jamba.channels(c)
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = nbytes = 0
    for name in run["reduced"]["op_seconds"]:
        if not all(k in name for k in KERNEL):
            continue
        # its results, in whatever order: f32[bucket,C], f32[N,C] and,
        # with snapshots, f32[snapshots + 1,N,C] (the kernel's last
        # snapshot row is its own scratch)
        results = name.split(") ", 1)[0]
        bucket = re.search(rf"f32\[(\d+),{ch}\]", results)
        snaps = re.search(rf"f32\[(\d+),{c['mamba_d_state']},{ch}\]",
                          results)
        if not bucket:
            return None
        n = trace_reduce.op_calls(run["reduced"]["trace"], name)
        calls += n
        nbytes += n * flops_jamba.ssm_scan_work(
            c, int(bucket.group(1)),
            int(snaps.group(1)) - 1 if snaps else 0)["bytes"]
    if not calls or seconds <= 0 or calls % flops_jamba.n_mamba_layers(c):
        return None
    return flops.roofline_share(0.0, nbytes, seconds, run["peaks"])[0]
