"""Scheduler: share of admitted prompt tokens seated from the radix
cache instead of prefilled: ``prefix_pages_reused * page_tokens`` over
the prompt tokens of the window. In a cell whose prompts share nothing
the cache is on and bypassed: it reads 0, and `correct` holds the hits
to 0 there."""


def read(run):
    pt = run["config"]["serve"]["page_tokens"]
    reused = sum(b.outs.metrics.prefix_pages_reused for b in run["bursts"])
    admitted = sum(len(p) for b in run["bursts"] for p in b.prompts)
    return 100.0 * reused * pt / admitted
