"""Scheduler: share of slots that own a request, per decode step, mean
over the window's bursts (``ServingMetrics.slot_occupancy_mean``)."""
import statistics


def read(run):
    return 100.0 * statistics.mean(
        b.outs.metrics.slot_occupancy_mean for b in run["bursts"])
