"""Scheduler: how long a request waited for a slot: serve call's entry
-> start of the refill that seated it (``RequestTelemetry.queue_wait_s``,
the program's span boundaries); nearest-rank p95 over every request of
the window, as ``ttft_p95_ms`` takes it. What is left of a request's
TTFT is its own refill. Nothing to read where the program does not
record it."""
from benchmarks import harness


def read(run):
    waits = [getattr(r, "queue_wait_s", None) for b in run["bursts"]
             for r in b.outs.metrics.per_request]
    if not waits or None in waits:
        return None
    return 1e3 * harness.percentile(waits, 0.95)
