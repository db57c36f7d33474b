"""Kernels: the attention backward Pallas kernel's share of its roofline
in a training step. Time: summed device time, inside the traced window,
of the ``tpu_custom_call`` events named ``%attn_bwd*``
(``ops/attention.py`` names its backward call so; the forward's calls
are ``%flash_attention*`` and do not count). One backward a layer a
micro-batch a step, one call each: nothing is read unless the calls
number a whole multiple of that, so a step that fell back to plain JAX,
or a layer that missed the kernel, reads nothing and not a number.
Work: ``flops.flash_attention_flops(backward=True)`` a backward, twice a
causal forward. The backward needs two and a half forwards' block
products (five for two), so the share reads low, never high. Bytes: q,
k, v, o, dO in and dQ, dK, dV out; compute bounds it."""
from benchmarks import flops, trace_reduce

KERNEL = ("%attn_bwd", "tpu_custom_call")


def read(run):
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *KERNEL)
    c, t = run["config"], run["traffic"]
    if (not calls or seconds <= 0
            or calls % (c["n_layer"] * c["train"]["n_micro"])):
        return None
    rows = t["rows_per_step"] // c["train"]["n_micro"]
    head = c["n_embd"] // c["n_head"]
    work = calls * flops.flash_attention_flops(
        rows, c["n_head"], t["seq"], head, backward=True)
    nbytes = calls * 8 * rows * t["seq"] * c["n_embd"] * 2
    return flops.roofline_share(work, nbytes, seconds, run["peaks"])[0]
