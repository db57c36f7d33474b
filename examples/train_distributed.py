"""End-to-end distributed training example: dp x pp x tp on any backend.

Runs a GPT-2-family (or Llama-family with --family llama) model through
the framework's single-program SPMD train step — pipeline stages over
'pp', tensor/sequence parallelism (ring attention) over 'tp', data
parallelism over 'dp' — with AdamW, checkpointing, and a resume.

Works anywhere:
  # 8 virtual CPU devices (laptop / CI):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_distributed.py
  # a real TPU slice: run as-is (one process per host with
  #   mpi_acx_tpu.parallel.multihost.initialize() for multi-host).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["gpt2", "llama", "moe"],
                    default="gpt2")
    ap.add_argument("--schedule", choices=["gpipe", "1f1b"],
                    default="gpipe",
                    help="pipeline schedule: GPipe (autodiff backward) "
                         "or 1F1B (O(pp) activation residency)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--virtual", type=int, default=1,
                    help="virtual chunks per device (interleaved pipeline "
                         "schedule; 1 = GPipe)")
    ap.add_argument("--data", default="",
                    help="binary uint16 token file to train on (streamed "
                         "through mpi_acx_tpu.data with device prefetch); "
                         "default: synthetic ramp task")
    args = ap.parse_args()
    # --schedule 1f1b composes with --virtual > 1: the interleaved 1F1B
    # schedule (O(v*pp) activation residency AND bubble/v).

    import jax
    import jax.numpy as jnp
    import optax

    from mpi_acx_tpu import backend
    from mpi_acx_tpu.models import llama as lm
    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.train import make_train_step_optax

    backend.enable_compile_cache()
    need = args.dp * args.pp * args.tp
    if len(jax.devices()) < need:
        raise SystemExit(
            f"need {need} devices (dp*pp*tp), have {len(jax.devices())} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "JAX_PLATFORMS=cpu for a virtual mesh")
    mesh = mesh_from_devices({"dp": args.dp, "pp": args.pp, "tp": args.tp})

    n_layers = 2 * args.pp * args.virtual
    if args.family == "llama":
        cfg = lm.tiny_llama(vocab=256, d_model=64, n_heads=4, n_kv_heads=2,
                            n_layers=n_layers, d_ff=128, max_seq=64)
        params = lm.init_params(jax.random.key(0), cfg)
    elif args.family == "moe":
        from mpi_acx_tpu.models import moe_transformer as mtf
        cfg = mtf.tiny_moe_config(vocab=256, d_model=64, n_heads=4,
                                  n_layers=n_layers, d_ff=128,
                                  n_experts=2 * args.tp,
                                  capacity_factor=4.0, max_seq=64)
        params = mtf.init_params(jax.random.key(0), cfg)
    else:
        cfg = tfm.tiny_config(vocab=256, d_model=64, n_heads=4,
                              n_layers=n_layers, d_ff=128, max_seq=64)
        params = tfm.init_params(jax.random.key(0), cfg)

    opt = optax.adamw(3e-3)
    # Interleaved schedule needs n_micro % pp == 0.
    M = args.pp if args.virtual > 1 else 2
    step, n_stages = make_train_step_optax(cfg, mesh, n_micro=M,
                                           optimizer=opt,
                                           n_virtual=args.virtual,
                                           schedule=args.schedule)
    if args.virtual > 1:
        p = tfm.stage_slice_interleaved(params, n_stages, args.virtual)
    else:
        p = tfm.stage_slice(params, n_stages)
    s = opt.init(p)

    mb, S = 2 * args.dp, 32
    if args.data:
        # Stream real tokens: memmap file -> [M, mb, S+1] windows staged
        # on device by a background prefetch thread, already dp-sharded
        # (and globally addressable, which the multi-host deployment the
        # header describes requires).
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mpi_acx_tpu.data import TokenDataset, batches, prefetch
        ds = TokenDataset(args.data)
        sh = NamedSharding(mesh, P(None, "dp"))

        def windows():
            for w in batches(ds, M * mb, S, seed=0, n_batches=args.steps):
                yield (w.astype(np.int32) % cfg.vocab).reshape(
                    M, mb, S + 1)

        def stream():
            for w in prefetch(windows(), sharding=sh):
                yield w[:, :, :-1], w[:, :, 1:]
        data_iter = stream()
    else:
        # Synthetic copy task: predict the next token of a ramp sequence.
        base = jnp.arange(S)[None, None, :] + jnp.arange(mb)[None, :, None]
        tokens = (base + jnp.arange(M)[:, None, None]) % cfg.vocab
        targets = jnp.roll(tokens, -1, axis=-1)
        data_iter = iter(lambda: (tokens, targets), None)  # repeat forever

    ck = None
    if args.ckpt:
        from mpi_acx_tpu.checkpoint import Checkpointer
        ck = Checkpointer(args.ckpt)

    for i in range(args.steps):
        tokens, targets = next(data_iter)
        loss, p, s = step(p, s, tokens, targets)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}", flush=True)
        if ck is not None and i and i % 10 == 0:
            ck.save(i, {"params": p, "opt": s})

    if ck is not None:
        ck.save(args.steps, {"params": p, "opt": s})
        restored = ck.restore(like={"params": p, "opt": s})
        l2, _, _ = step(restored["params"], restored["opt"], tokens, targets)
        print(f"resumed-from-checkpoint loss {float(l2):.4f}")
        ck.close()

    print("example OK")


if __name__ == "__main__":
    main()
