"""Scheduler: share of the serve calls' time spent outside the two
phases that wait on the device, ``refill.prefill`` and ``chunk.step``:
1 - their self seconds over ``call_s``, summed over the window's
bursts (``ServingMetrics.phase_s``, the program's spans). It is host
work plus dispatch: set-up, match, scatter dispatch, seat, grow, upload,
deliver, retire and the loop's own. In a traced run the benchmark's
callback starts and stops the profiler inside ``on_token``; those pauses
are the benchmark's, not the program's, and are taken out of both sides.
Nothing to read where the program has no phases."""

WAITS = ("refill.prefill", "chunk.step")


def read(run):
    ms = [b.outs.metrics for b in run["bursts"]]
    if not all(getattr(m, "phase_s", None) for m in ms):
        return None
    paused = sum(e - s for s, e in run["traced"][2]) if run.get("traced") \
        else 0.0
    call_s = sum(m.call_s for m in ms) - paused
    waits = sum(m.phase_s.get(k, 0.0) for m in ms for k in WAITS)
    return 100.0 * (call_s - waits) / call_s
