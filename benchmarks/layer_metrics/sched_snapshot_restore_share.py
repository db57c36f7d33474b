"""Scheduler: share of the window's seats that began from a restored
snapshot of the state layers (a radix hit cut back to a page that holds
one: the suffix alone is prefilled) over all its seats
(``ServingMetrics.state_snapshot_seats`` over ``prefills``). 0 where no
prompt shares a prefix or the store keeps no row; nothing to read where
the program has no such counter (the parent) or seated nobody."""


def read(run):
    m = [b.outs.metrics for b in run["bursts"]]
    if not all(hasattr(x, "state_snapshot_seats") for x in m):
        return None
    seats = sum(x.prefills for x in m)
    if not seats:
        return None
    return 100.0 * sum(x.state_snapshot_seats for x in m) / seats
