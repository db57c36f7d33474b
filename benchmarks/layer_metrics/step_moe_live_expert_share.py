"""Step programs: how much of the expert weights a decode step has to
read: distinct experts hit by the slots that OWN a request, over
``experts x MoE layers x decode steps``, summed over the window's
bursts (``ServingMetrics.moe_experts_live`` over ``moe_experts x
moe_layer_steps``, counted on the device in every step and MoE layer).
An idle slot routes too and its experts are read as well, so what the
kernel reads is this or more: what an idle-slot mask or a larger batch
would move. Nothing to read where the program has no such counter."""


def read(run):
    m = [b.outs.metrics for b in run["bursts"]]
    triples = sum(getattr(x, "moe_experts", 0)
                  * getattr(x, "moe_layer_steps", 0) for x in m)
    if not triples:
        return None
    return 100.0 * sum(x.moe_experts_live for x in m) / triples
