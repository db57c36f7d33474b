"""Kernels: the paged decode-attend Pallas kernel's share of its
roofline. Time: summed device time, inside the traced window, of the
``tpu_custom_call`` events whose result is ``[slots, heads, 1, head]``
(the kernel carries no name of its own; the trace prints
``%closed_call.N``). Work: the bytes of K and V it has to fetch — per
decode step and slot the live length rounded up to the 128-token page,
all heads and layers — worked out from the ``on_token`` record of the
chunks that ran inside the traced window. Slots that were idle or had
finished mid-chunk still cost the kernel a block each and are not
counted, so the share reads a little low, never high. Memory bounds it.
Returns nothing when the chunks of the record and the kernel's calls in
the trace do not match up (then the attribution would be a guess)."""
from benchmarks import flops, trace_reduce


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    head = c["n_embd"] // c["n_head"]
    kernel = ("tpu_custom_call",
              f" = bf16[{s['n_slots']},{c['n_head']},1,{head}]")
    seconds = trace_reduce.op_seconds(run["reduced"], *kernel)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *kernel)
    t0, t1, pauses = run["traced"]
    nbytes = chunks = 0
    for b in run["bursts"]:
        for when, tokens in b.log.deliveries(pauses=pauses):
            if t0 < when <= t1:
                chunks += 1
                nbytes += flops.decode_attend_bytes(
                    [len(b.prompts[rid]) + idx for rid, idx in tokens],
                    s["page_tokens"], c["n_head"], head, c["n_layer"])
    if not chunks or seconds <= 0 or calls != chunks * s["chunk"] * c["n_layer"]:
        return None
    return flops.roofline_share(0.0, nbytes, seconds, run["peaks"])[0]
