"""Step programs: of the forwards the window's decode chunks ran for a
family that generates by diffusion over blocks, the share that STORED a
finished block (its K/V, no head, no token) where the others denoised
it (``ServingMetrics.forwards_store`` over ``forwards_denoise +
forwards_store``, summed over the bursts): 1 in ``denoising_steps + 1``,
20% at 4 steps a block. What a later change that folds a block's store
into the next block's first denoising forward moves. Nothing to read
where the program counts no such forwards."""


def read(run):
    m = [b.outs.metrics for b in run["bursts"]]
    every = sum(getattr(x, "forwards_denoise", 0)
                + getattr(x, "forwards_store", 0) for x in m)
    if not every:
        return None
    return 100.0 * sum(x.forwards_store for x in m) / every
