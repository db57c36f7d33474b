"""Kernels: the HELD experts' grouped matmuls' share of their roofline in
the traced decode chunk (a chip of the deployment holds 16 of the
router's 256 experts). Time: summed device time, inside the traced
window, of the ``tpu_custom_call`` events whose result is ``f32[slots x
top_k, expert width]`` (the two up projections) or ``f32[slots x top_k,
hidden]`` (the down projection): three an expert layer a step. Work
(``flops_gigachat.held_experts_work``): the LARGER of each live HELD
expert's weights read once (88.1 MB) at the HBM peak and each held pair
computed once at the MXU peak, from the program's own device counters
of the chunks that ran inside the traced window
(``ServingMetrics.moe_by_chunk``: the held experts hit and the pairs
held, of the slots that OWN a request, over steps and expert layers).
An idle slot routes too and its experts are fetched as well, so the
count is a floor on what the kernel does and the share reads low, never
high. Returns nothing when the program has no such counters (the
parent), when the chunks of the ``on_token`` record do not find their
counters, or when the kernel's calls in the trace are not ``chunks x
chunk x expert layers x 3`` (a refill of the 64 bucket inside the trace
has calls of the same shape: then the attribution would be a guess)."""
from benchmarks import flops, flops_gigachat, trace_reduce


def traced_chunks(run):
    """[(held pairs, held experts live)] of the chunks delivered inside
    the traced window, or None when a chunk's counters cannot be
    found."""
    t0, t1, pauses = run["traced"]
    out = []
    for b in run["bursts"]:
        by_chunk = getattr(b.outs.metrics, "moe_by_chunk", None)
        for i, (when, _) in enumerate(b.log.deliveries(pauses=pauses)):
            if t0 < when <= t1:
                if (not by_chunk or i >= len(by_chunk)
                        or len(by_chunk[i]) < 6):
                    return None
                out.append((by_chunk[i][4], by_chunk[i][1]))
    return out


def read(run):
    if not run["traced"]:
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
    want = len(chunks) * s["chunk"] * flops_gigachat.n_moe_layers(c) * 3
    if seconds <= 0 or calls != want:
        return None
    ops, nbytes = flops_gigachat.held_experts_work(
        c, sum(live for _, live in chunks), sum(p for p, _ in chunks))
    return flops.roofline_share(ops, nbytes, seconds, run["peaks"])[0]
