"""Scheduler: time the window lost in waits that took far longer than
their like. The program reads its span record at each call's end
(``serving.stalled_spans``): a ``refill.prefill`` that took more than
twice the median of its bucket in that call (the call's first left out:
it waits for the pool's zero fill), or a ``chunk.step`` more than twice
the call's median chunk, is one stall, and its time above the median is
``ServingMetrics.stall_s``; summed over the window's calls. 0 on a quiet
machine; a machine's stop inside a prefill, a chunk that lost seconds,
or a program loaded inside the window show up at their size. The
benchmark's own profiler pauses fall inside ``on_token``, so inside
``refill.seat`` and ``chunk.deliver``, never in a waiting span: nothing
to take out. Nothing to read where the program does not count it."""


def read(run):
    lost = [getattr(b.outs.metrics, "stall_s", None) for b in run["bursts"]]
    if not lost or None in lost:
        return None
    return 1e3 * sum(lost)
