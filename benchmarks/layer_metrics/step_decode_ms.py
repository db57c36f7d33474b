"""Step programs: host time round one decode chunk (it ends in
``np.asarray``) over the chunk's length — ``ServingMetrics.itl_p50_s``,
median over the window's bursts. It times the chunk, not the gap a
client sees; ``tpot_p95_ms`` is that."""
import statistics


def read(run):
    return 1e3 * statistics.median(
        b.outs.metrics.itl_p50_s for b in run["bursts"])
