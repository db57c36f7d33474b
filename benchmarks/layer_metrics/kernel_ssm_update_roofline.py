"""Kernels: the Mamba layers' decode update's share of its roofline in
the traced decode chunks. Time: summed device time, inside the traced
window, of the events named ``%ssm_update*`` (one a Mamba layer a step:
the Mosaic call, or a ``fusion`` XLA wraps it in under its name). Work (``flops_jamba.ssm_update_work``): every
slot's scan state read and written once a call, at the chip's HBM peak;
the scan has no matrix product. Every slot's state moves, owned or idle,
so the share does not read low by the idle slots. Returns nothing when
the program has no such call (a parent, or a fallback to plain JAX),
or when the calls in the trace are not ``chunks x chunk x Mamba
layers`` (then the attribution would be a guess)."""
from benchmarks import flops, flops_jamba, trace_reduce

KERNEL = ("%ssm_update",)


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *KERNEL)
    t0, t1, pauses = run["traced"]
    chunks = sum(t0 < when <= t1 for b in run["bursts"]
                 for when, _ in b.log.deliveries(pauses=pauses))
    want = chunks * s["chunk"] * flops_jamba.n_mamba_layers(c)
    if not calls or seconds <= 0 or calls != want:
        return None
    nbytes = calls * flops_jamba.ssm_update_work(c, s["n_slots"])["bytes"]
    return flops.roofline_share(0.0, nbytes, seconds, run["peaks"])[0]
