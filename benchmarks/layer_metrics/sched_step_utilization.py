"""Scheduler: share of decode slot-steps that delivered a token, over
the whole window: ``decode_tokens`` (counted where the deliver loop
consumes them) over ``decode_slot_steps`` (every chunk: chunk x slots),
summed over the bursts. It falls with slots that own nothing while a
closed burst drains AND with requests that end inside a chunk;
``sched_slot_occupancy`` sees only the first, at each chunk's start.
Nothing to read where the program does not count them."""


def read(run):
    ms = [b.outs.metrics for b in run["bursts"]]
    steps = sum(getattr(m, "decode_slot_steps", 0) for m in ms)
    if not steps:
        return None
    return 100.0 * sum(m.decode_tokens for m in ms) / steps
