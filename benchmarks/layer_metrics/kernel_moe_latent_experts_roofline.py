"""Kernels: the held LATENT experts' grouped matmuls' share of their
roofline in the traced decode chunk (a chip of the deployment holds 128
of the router's 512 experts; an expert is two matrices in a 1,024-wide
latent). Time: summed device time, inside the traced window, of the
``tpu_custom_call`` events whose result is ``f32[rows, expert width]``
(the up projection) or ``f32[rows, latent]`` (the down projection),
``rows`` the slots' ``top_k`` pairs padded to the kernel's row tile: two
an expert layer a step. Work (``flops_nemotron.latent_experts_work``):
the LARGER of each live HELD expert's two matrices read once (11 MB) at
the HBM peak and each held pair computed once at the MXU peak, from the
program's own device counters of the chunks that ran inside the traced
window (``ServingMetrics.moe_by_chunk``: the held experts hit and the
pairs held, of the slots that own a request and can still deliver, over
steps and expert layers). Returns nothing when the program has no such
counters (the parent), when the chunks of the ``on_token`` record do
not find their counters, or when the kernel's calls in the trace are
not ``chunks x chunk x expert layers x 2`` (then the attribution would
be a guess)."""
from benchmarks import flops, flops_nemotron, trace_reduce
from benchmarks.layer_metrics.kernel_moe_held_experts_roofline import \
    traced_chunks


def read(run):
    if not run["traced"]:
        return None
    c, s = run["config"], run["config"]["serve"]
    chunks = traced_chunks(run)
    if not chunks:
        return None
    rows = -(-s["n_slots"] * c["num_experts_per_tok"] // 128) * 128
    seconds = calls = 0
    for width in (c["moe_intermediate_size"], c["moe_latent_size"]):
        kernel = ("tpu_custom_call", f" = f32[{rows},{width}]")
        seconds += trace_reduce.op_seconds(run["reduced"], *kernel)
        calls += trace_reduce.op_calls(run["reduced"]["trace"], *kernel)
    want = len(chunks) * s["chunk"] * flops_nemotron.n_layers(c, "E") * 2
    if seconds <= 0 or calls != want:
        return None
    ops, nbytes = flops_nemotron.latent_experts_work(
        c, sum(live for _, live in chunks), sum(p for p, _ in chunks))
    return flops.roofline_share(ops, nbytes, seconds, run["peaks"])[0]
