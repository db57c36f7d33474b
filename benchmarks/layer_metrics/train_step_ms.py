"""Step programs: median host time per optimizer step, over groups of
steps that each end in ``block_until_ready``."""
import statistics


def read(run):
    return 1e3 * statistics.median(run["group_s"]) / run["group_steps"]
