"""Kernels: the Mamba-2 layers' chunked prefill scan's share of its
roofline in the traced refill. Time: summed device time, inside the
traced window, of the events named ``%ssd_scan*`` (one a Mamba-2 layer a
prefill; XLA may wrap the Mosaic call and the slice of its snapshot rows
in a ``fusion`` that keeps the call's name). Work
(``flops_nemotron.ssd_scan_work``): the LARGER of the chunks' matrix
products at the MXU peak and what the scan must read and write at the
HBM peak, for the bucket each call ran over, the bucket (whole chunks)
and the snapshots read from the call's own result shapes. The kernel's
products are float32 (several passes of the MXU each), so the share
reads low against the bfloat16 peak. Returns nothing when the program
has no such call, or when the calls are not a whole multiple of the
Mamba-2 layers."""
import re

from benchmarks import flops, flops_nemotron, trace_reduce

KERNEL = ("%ssd_scan",)


def read(run):
    if not run["traced"]:
        return None
    c = run["config"]
    H, P, N, _ = flops_nemotron.mamba_dims(c)
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = ops = nbytes = 0
    for name in run["reduced"]["op_seconds"]:
        if not all(k in name for k in KERNEL):
            continue
        # its results, in whatever order: f32[bucket,H*P], f32[H,P,N]
        # and, with snapshots, f32[snapshots + 1,H,P,N] (the kernel's
        # last snapshot row is its own scratch)
        results = name.split(") ", 1)[0]
        bucket = re.search(rf"f32\[(\d+),{H * P}\]", results)
        snaps = re.search(rf"f32\[(\d+),{H},{P},{N}\]", results)
        if not bucket:
            return None
        n = trace_reduce.op_calls(run["reduced"]["trace"], name)
        work = flops_nemotron.ssd_scan_work(
            c, int(bucket.group(1)), int(snaps.group(1)) - 1 if snaps else 0)
        calls += n
        ops += n * work["ops"]
        nbytes += n * work["bytes"]
    if not calls or seconds <= 0 or calls % flops_nemotron.n_layers(c, "M"):
        return None
    return flops.roofline_share(ops, nbytes, seconds, run["peaks"])[0]
