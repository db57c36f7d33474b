"""Kernels: the prefill attention kernel's share of its roofline in the
traced refill (a suffix prefill behind a radix hit: the last request of
a burst of 8 askers a document is never its document's first). Time:
summed device time, inside the traced window, of the ``tpu_custom_call``
events named ``%flash_rows_attention*`` (one a layer a prefill). Work
(``flops_gigachat.prefill_attention_flops``), from the REQUESTS whose
first token came inside the traced window: each one's real rows (its
prompt less the pages it hit, from the serve call's own span record)
against its history, causal, K and V formed for every head at widths
192 / 192; compute bounds it (a head's K/V tile is read once a q
block). The bucket's padding rows and the keys above a block's diagonal
that the kernel still multiplies are not counted: the share reads low,
never high. Returns nothing when the program has no such call or no
span record (the parent), or when the calls are not ``refills x
layers``."""
from benchmarks import flops, flops_gigachat, trace_reduce

KERNEL = ("tpu_custom_call", "%flash_rows_attention")


def read(run):
    if not run["traced"]:
        return None
    c, pt = run["config"], run["config"]["serve"]["page_tokens"]
    seconds = trace_reduce.op_seconds(run["reduced"], *KERNEL)
    calls = trace_reduce.op_calls(run["reduced"]["trace"], *KERNEL)
    t0, t1, _ = run["traced"]
    work = refills = 0
    for b in run["bursts"]:
        hit = {sp.ids.get("rid"): sp.ids.get("hit_pages", 0)
               for sp in getattr(b.outs.metrics, "spans", ())
               if sp.name == "refill.prefill"}
        for rid, first in enumerate(b.log.first):
            if first is not None and t0 < first <= t1:
                if rid not in hit:
                    return None
                history = hit[rid] * pt
                refills += 1
                work += c["num_hidden_layers"] * \
                    flops_gigachat.prefill_attention_flops(
                        c, len(b.prompts[rid]) - history, history)
    if (not refills or seconds <= 0
            or calls != refills * c["num_hidden_layers"]):
        return None
    return flops.roofline_share(work, 0.0, seconds, run["peaks"])[0]
