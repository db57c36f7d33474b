"""Kernels: the flash-attention Pallas kernel's share of its roofline in
a training step. Time: summed device time of the ``tpu_custom_call``
events named ``%flash_attention*`` inside the traced window. Work: one
causal forward on [micro-batch, heads, seq, head] per call (the
backward is not a kernel in this program, and a remat step calls the
forward twice; every call is counted with its own work). The kernel
reads and writes a few MB a call, so compute bounds it."""
from benchmarks import flops, trace_reduce

KERNEL = ("%flash_attention", "tpu_custom_call")


def read(run):
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *KERNEL)
    if not calls or seconds <= 0:
        return None
    c, t = run["config"], run["traffic"]
    rows = t["rows_per_step"] // c["train"]["n_micro"]
    head = c["n_embd"] // c["n_head"]
    work = calls * flops.flash_attention_flops(rows, c["n_head"], t["seq"],
                                               head)
    nbytes = calls * 4 * rows * t["seq"] * c["n_embd"] * 2
    return flops.roofline_share(work, nbytes, seconds, run["peaks"])[0]
