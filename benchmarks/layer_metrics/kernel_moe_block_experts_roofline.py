"""Kernels: the expert layers' grouped matmuls' share of their roofline
in the traced decode chunk of a family that generates by diffusion over
blocks: every forward of a block routes ``slots x block x top_k`` rows a
layer. Time: summed device time, inside the traced window, of the
``tpu_custom_call`` events whose result is ``f32[slots x block x top_k,
expert width]`` (gate and up) or ``f32[..., hidden]`` (down): three a
layer a forward. Work (``flops_sdar.moe_work``): the larger of each LIVE
expert's weights read once a forward and each live pair computed once,
from the program's own device counters of the chunk that ran inside the
traced window (``ServingMetrics.moe_by_chunk``: pairs and distinct
experts over the rows of blocks that can still deliver, summed over
layers and forwards). Returns nothing when the program has no such
counters, when the traced chunk's counters cannot be found, or when the
kernel's calls in the trace are not ``chunks x blocks x (denoising steps
+ 1) x layers x 3`` (then the attribution would be a guess)."""
from benchmarks import flops, flops_sdar, trace_reduce
from benchmarks.entries.serve_paged_greedy_sdar import (forwards,
                                                        traced_chunks)


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    chunks = traced_chunks(run, "moe_by_chunk")
    if not chunks:
        return None
    rows = (s["n_slots"] * c["generation"]["block_length"]
            * c["num_experts_per_tok"])
    seconds = calls = 0
    for width in (c["moe_intermediate_size"], c["hidden_size"]):
        kernel = ("tpu_custom_call", f" = f32[{rows},{width}]")
        seconds += trace_reduce.op_seconds(run["reduced"], *kernel)
        calls += trace_reduce.op_calls(run["reduced"]["trace"], *kernel)
    want = forwards(c, len(chunks)) * c["num_hidden_layers"] * 3
    if seconds <= 0 or calls != want:
        return None
    ops, nbytes = flops_sdar.moe_work(
        c, sum(ch[1] for ch in chunks), sum(ch[0] for ch in chunks))
    return flops.roofline_share(ops, nbytes, seconds, run["peaks"])[0]
