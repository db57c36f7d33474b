"""Kernels: the expert layer's grouped matmuls' share of their roofline
in the traced decode chunk. Time: summed device time, inside the traced
window, of the ``tpu_custom_call`` events whose result is
``f32[slots x top_k, expert width]`` (the two up projections) or
``f32[slots x top_k, hidden]`` (the down projection): three a MoE layer
a step. Work (``flops_lfm2.moe_work``): the larger of each live
expert's weights read once and each routed pair computed once, from
the program's own device counters of the chunk that ran inside the
traced window (``ServingMetrics.moe_by_chunk``: pairs and distinct
experts of the slots that OWN a request, over steps and MoE layers).
An idle slot routes too and its experts are fetched as well, so the
count is a floor on what the kernel does and the share reads low,
never high (as PERF.md says of the attend's). Returns nothing when the
program has no such counters, when the chunks of the ``on_token``
record do not find their counters, or when the kernel's calls in the
trace are not ``chunk x MoE layers x 3`` (then the attribution would be
a guess)."""
from benchmarks import flops, flops_lfm2, trace_reduce


def traced_chunks(run):
    """[(pairs, experts live)] of the chunks delivered inside the traced
    window, or None when a chunk's counters cannot be found."""
    t0, t1, pauses = run["traced"]
    out = []
    for b in run["bursts"]:
        by_chunk = getattr(b.outs.metrics, "moe_by_chunk", None)
        for i, (when, _) in enumerate(b.log.deliveries(pauses=pauses)):
            if t0 < when <= t1:
                if not by_chunk or i >= len(by_chunk):
                    return None
                out.append(by_chunk[i][:2])
    return out


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    chunks = traced_chunks(run)
    if not chunks:
        return None
    rows = s["n_slots"] * c["num_experts_per_tok"]
    seconds = calls = 0
    for width in (c["moe_intermediate_size"], c["hidden_size"]):
        kernel = ("tpu_custom_call", f" = f32[{rows},{width}]")
        seconds += trace_reduce.op_seconds(run["reduced"], *kernel)
        calls += trace_reduce.op_calls(run["reduced"]["trace"], *kernel)
    want = len(chunks) * s["chunk"] * flops_lfm2.n_moe_layers(c) * 3
    if seconds <= 0 or calls != want:
        return None
    ops, nbytes = flops_lfm2.moe_work(
        c, sum(live for _, live in chunks), sum(p for p, _ in chunks))
    return flops.roofline_share(ops, nbytes, seconds, run["peaks"])[0]
