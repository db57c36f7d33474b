"""Scheduler: host work of one refill, the prefill's wait left out: the
``refill.match`` + ``refill.scatter`` + ``refill.seat`` spans of the
refill that seated a request (``RequestTelemetry.refill_host_s``: SLO
gate, radix match, page allocation; the scatter's dispatch; seat, trie
insert, the first ``on_token``); median over the window's requests.
Nothing to read where the program does not record it."""
import statistics


def read(run):
    host = [getattr(r, "refill_host_s", None) for b in run["bursts"]
            for r in b.outs.metrics.per_request]
    if not host or None in host:
        return None
    return 1e3 * statistics.median(host)
