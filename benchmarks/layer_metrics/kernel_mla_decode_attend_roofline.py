"""Kernels: the latent (MLA) paged decode attend's share of its roofline
in the traced decode chunk. Time: summed device time, inside the traced
window, of the ``tpu_custom_call`` events named
``%paged_flash_decode_attend*`` whose result is ``[slots, 1, heads,
kv_lora_rank]``: one a layer a step. Work
(``flops_gigachat.latent_attend_work``), counted from the REQUESTS and
not from the kernel's shapes, so that it is the same work whatever
implements it: for every token delivered inside the traced window, the
cached rows its step attended (the request's prompt + the tokens before
it), each read once for all heads (``[c_kv | k_rope]``, 1,152 B) and met
by every head twice (the scores over 576 values, ``P @ V`` over 512);
the LARGER of the bytes at the HBM peak and the operations at the MXU
peak (121 FLOP/B: half the v5e's ridge, so bytes bound it on paper).
Slots that delivered nothing cost the kernel a grid step and are not
counted, and the chunk's own staged rows are counted as cached rows:
the share reads a little low, never high. Returns nothing when the
program has no such call (the parent), or when the calls are not
``chunks x chunk x layers`` (then the attribution would be a guess)."""
from benchmarks import flops, flops_gigachat, trace_reduce


def read(run):
    if not run["traced"]:          # the window closed before the tracer ran
        return None
    c, s = run["config"], run["config"]["serve"]
    kernel = ("tpu_custom_call", "%paged_flash_decode_attend",
              f" = bf16[{s['n_slots']},1,{c['num_attention_heads']},"
              f"{c['kv_lora_rank']}]")
    seconds = trace_reduce.op_seconds(run["reduced"], *kernel)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *kernel)
    t0, t1, pauses = run["traced"]
    rows = chunks = 0
    for b in run["bursts"]:
        for when, tokens in b.log.deliveries(pauses=pauses):
            if t0 < when <= t1:
                chunks += 1
                rows += sum(len(b.prompts[rid]) + idx for rid, idx in tokens)
    layers = c["num_hidden_layers"]
    if not chunks or seconds <= 0 or calls != chunks * s["chunk"] * layers:
        return None
    ops, nbytes = flops_gigachat.latent_attend_work(c, rows * layers)
    return flops.roofline_share(ops, nbytes, seconds, run["peaks"])[0]
