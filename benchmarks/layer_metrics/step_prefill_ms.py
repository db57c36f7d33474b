"""Step programs: time of one prefill as the loop pays it, the
``refill.prefill`` span of the refill that seated a request
(``RequestTelemetry.prefill_s``: pad, the history gather on a prefix
hit, the program's dispatch, and the host's wait for the first token);
median over the window's requests. Nothing to read where the program
does not record it."""
import statistics


def read(run):
    took = [getattr(r, "prefill_s", None) for b in run["bursts"]
            for r in b.outs.metrics.per_request]
    if not took or None in took:
        return None
    return 1e3 * statistics.median(took)
