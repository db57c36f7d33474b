"""Driver for configurations whose entry is ``train.make_train_step_optax``
on a one-device mesh: set-up builds ONE compiled step with its state,
drives it from the seed through its first three steps (the numbers the
reference is compared on) and hands that same object to the window.
The plain reference follows those three steps after the window, when
the program's state is freed.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import harness, traffic, weights
from benchmarks.harness import check_line, say

CHECKED_STEPS = 3
# The limits are the configuration's own (``configs/<name>.json``:
# ``limits``, beside the readings they were set from); a missing one is
# an error.
TRACE_GROUP = 2              # the window's third group is steady


def leaf_norms(tree) -> dict:
    """name -> L2 norms, one per layer for a stacked leaf (leading
    ``[n_layer]`` axis), one for the others. A jitted reduction: only
    the norms come to the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(t):
        out = {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))[None]
               for k, v in t.items() if k != "layers"}
        for k, v in t["layers"].items():
            v = v.astype(jnp.float32).reshape(v.shape[0], -1)
            out["layers/" + k] = jnp.sqrt(jnp.sum(jnp.square(v), axis=1))
        return out
    return {k: np.asarray(v) for k, v in norms(tree).items()}


def _worst_leaf(values: dict, want: dict, of=np.max) -> float:
    """``of`` (the worst, by default) over all leaves of ``values``
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    ref = np.concatenate([want[k] for k in sorted(want)])
    val = np.concatenate([values[k] for k in sorted(want)])
    return float(of(val / np.maximum(ref, float(np.median(ref)))))


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The widest gap between the program's norm and the reference's."""
    return _worst_leaf({k: np.abs(got[k] - want[k]) for k in want}, want)


def worst_leaf_diff(diff: dict, want: dict, of=np.max) -> float:
    """The widest norm of a leaf's DIFFERENCE from the reference's.
    Unlike a gap of norms it sees rounding, which changes a tensor's
    entries and hardly its norm: the number a lower precision fails."""
    return _worst_leaf(diff, want, of)


def _unstage(tree):
    """[1, L, ...] stage-sliced layers back to [L, ...]."""
    out = dict(tree)
    out["layers"] = {k: v.reshape(v.shape[1:])
                     for k, v in tree["layers"].items()}
    return out


def _sub(a, b):
    import jax
    return jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(a, b)


def reference_steps(c, seed, batches, precision="f32", first_grad=None,
                    keep_grad=False):
    """(losses, first-gradient leaf norms, parameter-change leaf norms,
    leaf norms of first gradient - ``first_grad``) of the plain
    reference over the checked steps. ``first_grad`` is a host copy of
    the gradient to hold against the reference's; ``keep_grad`` returns
    the reference's own as a fifth value."""
    import jax
    import jax.numpy as jnp
    from benchmarks.reference import gpt2
    o = c["train"]["optimizer"]
    hp = tuple(jnp.float32(o[k]) for k in
               ("learning_rate", "b1", "b2", "eps", "weight_decay"))
    tree = weights.make_gpt2(c, seed, jnp.float32)
    m = jax.tree.map(jnp.zeros_like, tree)
    v = jax.tree.map(jnp.zeros_like, tree)
    losses, gnorm, gdiff = [], None, None
    for i, b in enumerate(batches):
        b = jnp.asarray(b)
        loss, g = gpt2.loss_and_grads(
            tree, b[:, :-1], b[:, 1:], n_head=c["n_head"],
            eps=c["layer_norm_epsilon"], precision=precision)
        losses.append(float(loss))
        if i == 0:
            gnorm = leaf_norms(g)
            if first_grad is not None:
                gdiff = leaf_norms(_sub(jax.device_put(first_grad), g))
            if keep_grad:
                kept = jax.device_get(g)
        tree, m, v = gpt2.adamw_step(tree, m, v, g, jnp.float32(i), hp)
        del g
    del m, v
    dnorm = leaf_norms(_sub(tree, weights.make_gpt2(c, seed, jnp.float32)))
    return (losses, gnorm, dnorm, gdiff) + ((kept,) if keep_grad else ())


def compare(got, want, limits: dict) -> bool:
    """Each number compared beside its limit; True when all hold."""
    (gl, gg, gd), (wl, wg, wd, diff) = got, want
    say("losses", program=gl, reference=wl)
    read = {"loss_gap": max(abs(a - b) for a, b in zip(gl, wl)),
            "first_grad_norm_gap": worst_leaf_gap(gg, wg),
            "param_change_norm_gap": worst_leaf_gap(gd, wd),
            "first_grad_diff": worst_leaf_diff(diff, wg)}
    ok = True
    for name, value in read.items():
        ok &= check_line(name, value, limits[name], value <= limits[name])
    return ok


class Trainer:
    """The compiled step with its state and its feed: built once in
    set-up, checked on its first steps, then timed."""

    def __init__(self, c: dict, t: dict, seed: int):
        import jax
        import jax.numpy as jnp
        import optax
        from mpi_acx_tpu import data
        from mpi_acx_tpu.models import transformer as tfm
        from mpi_acx_tpu.parallel.mesh import mesh_from_devices
        from mpi_acx_tpu.train import make_train_step_optax
        self.c, self.t = c, t
        tr, o = c["train"], c["train"]["optimizer"]
        self.b1 = o["b1"]
        opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                          eps=o["eps"], weight_decay=o["weight_decay"])
        mesh = mesh_from_devices({"dp": 1, "pp": 1, "tp": 1},
                                 jax.devices()[:1])
        self.step, n_stages = make_train_step_optax(
            harness.gpt2_program_config(c, c["compute_dtype"]), mesh,
            tr["n_micro"], opt, remat=tr["remat"],
            xent_chunk=tr["xent_chunk"])
        self.params = tfm.stage_slice(
            weights.make_gpt2(c, seed, jnp.dtype(c["weights_dtype"])),
            n_stages)
        self.opt_state = jax.jit(opt.init)(self.params)
        rows, seq = t["rows_per_step"], t["seq"]
        self.shape = (tr["n_micro"], rows // tr["n_micro"], seq)
        self.tokens_per_step = rows * seq
        ds = data.TokenDataset.from_array(
            traffic.train_dataset(t, seed, c["vocab_size"]))
        self.feed = data.prefetch(
            data.batches(ds, rows, seq, seed=int(seed)), size=2)

    def one_step(self, keep: list | None = None):
        """Next batch of the feed through the step; returns the loss
        (a device scalar, not waited for)."""
        b = next(self.feed)
        if keep is not None:
            keep.append(np.asarray(b))
        loss, self.params, self.opt_state = self.step(
            self.params, self.opt_state, b[:, :-1].reshape(self.shape),
            b[:, 1:].reshape(self.shape))
        return loss

    def first_grad(self):
        """The first gradient as the optimizer got it, on the host:
        after one Adam step its first moment is (1 - b1) * g."""
        import jax
        scale = 1.0 / (1.0 - self.b1)
        return jax.device_get(jax.jit(lambda mu: jax.tree.map(
            lambda x: x * scale, mu))(_unstage(self.opt_state[0].mu)))

    def checked_steps(self, seed):
        """(the batches the feed gave, (losses, first-gradient leaf
        norms, parameter-change leaf norms), the first gradient)."""
        import jax.numpy as jnp
        batches, losses, grad = [], [], None
        for i in range(CHECKED_STEPS):
            losses.append(float(self.one_step(keep=batches)))
            if i == 0:
                grad = self.first_grad()
        start = weights.make_gpt2(self.c, seed,
                                  jnp.dtype(self.c["weights_dtype"]))
        dnorm = leaf_norms(_sub(_unstage(self.params), start))
        return batches, (losses, leaf_norms(grad), dnorm), grad


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import jax
    c, t = cell.config, cell.traffic
    t_in = time.perf_counter()
    with harness.Watch() as setup_watch:
        trainer = Trainer(c, t, seed)
        batches, got, grad = trainer.checked_steps(seed)
    setup_s = time.perf_counter() - t_start
    say("setup", setup_s=setup_s, reach_chip_s=t_in - t_start,
        compile_s=setup_watch.compile_s,
        cache_hits=setup_watch.hits, cache_misses=setup_watch.misses)

    logdir = os.path.join(cell.root, ".bench_trace", cell.name)
    group, group_s, losses = t["group_steps"], [], []
    with harness.Watch() as window_watch:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            tracing = trace and len(group_s) == TRACE_GROUP
            if tracing:
                span = harness.start_trace(logdir)
            g0 = time.perf_counter()
            for _ in range(group):
                loss = trainer.one_step()
            losses.append(float(jax.block_until_ready(loss)))
            group_s.append(time.perf_counter() - g0)
            if tracing:
                harness.stop_trace(span)
        window_s = time.perf_counter() - t0
    peak = harness.memory_peak_bytes()
    steps = group * len(group_s)
    say("window", window_s=window_s, steps=steps,
        tokens_per_step=trainer.tokens_per_step, first_loss=got[0][0],
        last_loss=losses[-1], compiles=window_watch.misses,
        programs_loaded=window_watch.hits)

    del trainer
    ok = check_line("loss_falls", losses[-1], got[0][0],
                    losses[-1] < got[0][0])
    ok &= compare(got, reference_steps(c, seed, batches, first_grad=grad),
                  c["limits"])

    end_to_end = {"train_tok_s": steps * (t["rows_per_step"] * t["seq"])
                  / window_s, "setup_s": setup_s}
    return {"correct": ok, "attempted": steps, "failed": 0,
            "end_to_end": end_to_end, "memory_peak_bytes": peak,
            "group_s": group_s, "group_steps": group, "window_s": window_s,
            "window_watch": window_watch, "trace_dir": logdir,
            "config": c, "traffic": t}
