"""Entry: the serve call's set-up, function entry -> its first refill
(admission, the ``PagedKV`` pool, the jit wrappers and the step
program): ``ServingMetrics.phase_s["serve.setup"]``, the program's own
span, median over the window's bursts. ``entry_first_token_ms`` is this
plus the first refill. Nothing to read where the program has no phases."""
import statistics


def read(run):
    spans = [getattr(b.outs.metrics, "phase_s", None) for b in run["bursts"]]
    if not all(s and "serve.setup" in s for s in spans):
        return None
    return 1e3 * statistics.median(s["serve.setup"] for s in spans)
