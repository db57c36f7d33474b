"""Kernels: the Mamba-2 layers' decode update's share of its roofline in
the traced decode chunks. Time: summed device time, inside the traced
window, of the events named ``%ssd_update*`` (one a Mamba-2 layer a
step: the Mosaic call, or a ``fusion`` XLA wraps it in under its name).
Work (``flops_nemotron.ssd_update_work``): the state of the DELIVERING
slots read and written once a call, at the chip's HBM peak: the
slot-steps that could still deliver a token, from the program's own
record of the chunks that ran inside the traced window
(``ServingMetrics.state_steps_by_chunk``, ``RequestBook.left`` clipped
to the chunk). The kernel moves every slot's state, idle or not, so the
share reads LOW by the idle slot-steps and a later program that skips
them cannot read over 100. Returns nothing when the program has no such
call or record (a parent, a fallback to plain JAX), when a traced
chunk's record cannot be found, or when the calls in the trace are not
``chunks x chunk x Mamba-2 layers`` (then the attribution would be a
guess)."""
from benchmarks import flops, flops_nemotron, trace_reduce

KERNEL = ("%ssd_update",)


def traced_slot_steps(run):
    """[delivering slot-steps] of the chunks delivered inside the traced
    window, or None when a chunk's record cannot be found."""
    t0, t1, pauses = run["traced"]
    out = []
    for b in run["bursts"]:
        by_chunk = getattr(b.outs.metrics, "state_steps_by_chunk", None)
        for i, (when, _) in enumerate(b.log.deliveries(pauses=pauses)):
            if t0 < when <= t1:
                if not by_chunk or i >= len(by_chunk):
                    return None
                out.append(by_chunk[i])
    return out


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *KERNEL)
    steps = traced_slot_steps(run)
    layers = flops_nemotron.n_layers(c, "M")
    if (not steps or not calls or seconds <= 0
            or calls != len(steps) * s["chunk"] * layers):
        return None
    nbytes = (sum(steps) * layers
              * flops_nemotron.ssd_update_work(c, 1)["bytes"])
    return flops.roofline_share(0.0, nbytes, seconds, run["peaks"])[0]
