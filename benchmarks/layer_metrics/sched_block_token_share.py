"""Scheduler: of the positions the window's decode chunks computed for a
family that generates by diffusion over blocks (``chunk x slots`` a
chunk, live and dead slots together), the share that was DELIVERED as a
token (``ServingMetrics.block_by_chunk``: positions and delivered, summed
over the bursts). The rest: a prompt's last ``P mod block`` tokens in
its first block, a last block's excess past the request's end, and the
blocks of slots with nothing left to deliver (idle while a closed burst
drains, or the request ended earlier in the chunk). Nothing to read
where the program counts no blocks."""


def read(run):
    chunks = [ch for b in run["bursts"]
              for ch in getattr(b.outs.metrics, "block_by_chunk", ())]
    positions = sum(ch[2] for ch in chunks)
    if not positions:
        return None
    return 100.0 * sum(ch[3] for ch in chunks) / positions
