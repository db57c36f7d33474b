"""Step programs: what of the routed (token, expert) pairs this chip's
share of the experts computes: the pairs whose expert is HELD here over
all the pairs the router chose, of the slots that OWN a request, summed
over the window's bursts, decode steps and expert layers
(``ServingMetrics.moe_pairs_held`` over ``moe_assignments``, counted on
the device). 16 of 256 experts under even routing read 6.25; 100 says
that a layer silently holds (or computes) everything, 0 that the share
is never reached. Nothing to read where the program has no such counter
(the parent), or holds every expert."""


def read(run):
    m = [b.outs.metrics for b in run["bursts"]]
    if not all(getattr(x, "moe_experts_held", 0) for x in m):
        return None
    routed = sum(x.moe_assignments for x in m)
    if not routed:
        return None
    return 100.0 * sum(x.moe_pairs_held for x in m) / routed
