"""Step programs: a prefill's wait alone. The ``refill.prefill`` span
(``step_prefill_ms``) holds the pad, the upload of the tokens and on a
prefix hit the history gather before the program is handed to the
device; its record marks that moment (``handed``), and
``RequestTelemetry.prefill_wait_s`` is hand-over -> the first token on
the host: dispatch, whatever the device still had queued (the previous
refill's scatter), the program, and the transfer of its argmax. Median
over the window's requests, as ``step_prefill_ms`` takes it; never above
it. Nothing to read where the program does not record it."""
import statistics


def read(run):
    took = [getattr(r, "prefill_wait_s", None) for b in run["bursts"]
            for r in b.outs.metrics.per_request]
    if not took or None in took:
        return None
    return 1e3 * statistics.median(took)
