"""Continuous-batching serving demo (models/serving.py).

Requests with different prompt and output lengths stream through a
fixed pool of cache slots; finished requests are swapped out and queued
prompts swapped in mid-stream, so the device never drains to wait for
the longest request in a batch. Every output is bit-equal to the same
request's solo generate() run (per-slot positions).

Run (CPU):
  JAX_PLATFORMS=cpu python examples/serve_continuous.py \
      --requests 8 --slots 3 --chunk 4

The reference has no serving stack (SURVEY.md §0) — this demonstrates
framework-goal surface above it.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["gpt2", "llama", "moe"],
                    default="gpt2")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=4,
                    help="decode steps per host dispatch")
    ap.add_argument("--int8-kv", action="store_true",
                    help="serve from int8 slot caches (ops/kvquant.py)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel serving over N mesh ranks "
                         "(0 = single device; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N); the toy config's head counts scale "
                         "with N, and f32 is forced so TP outputs "
                         "match the single-device verify exactly")
    ap.add_argument("--verify", action="store_true",
                    help="check every output against its solo run")
    args = ap.parse_args()
    if args.tp and args.int8_kv and args.family == "moe":
        ap.error("--tp --int8-kv: gpt2/llama only for now")

    from mpi_acx_tpu import backend
    backend.enable_compile_cache()

    from mpi_acx_tpu.models import serving
    # Under --tp the toy geometry scales with the mesh so the TP
    # split's divisibility always holds (serve_tp.py's 2*tp pattern).
    heads = 2 * args.tp if args.tp else 4
    if args.family == "gpt2":
        from mpi_acx_tpu.models import transformer as mod
        cfg = mod.tiny_config(vocab=96, d_model=16 * heads,
                              n_heads=heads, n_layers=3,
                              d_ff=32 * heads, max_seq=128)
    elif args.family == "moe":
        from mpi_acx_tpu.models import moe_transformer as mod
        cfg = mod.tiny_moe_config(vocab=96, d_model=16 * heads,
                                  n_heads=heads, n_layers=3,
                                  d_ff=32 * heads, max_seq=128,
                                  n_experts=2 * args.tp if args.tp
                                  else 4)
    else:
        from mpi_acx_tpu.models import llama as mod
        cfg = mod.tiny_llama(vocab=96, d_model=16 * heads,
                             n_heads=heads,
                             n_kv_heads=args.tp if args.tp else 2,
                             n_layers=3, d_ff=32 * heads, max_seq=128)
    server_fns = None
    if args.tp:
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = mod.init_params(jax.random.key(0), cfg)
    if args.tp:
        from mpi_acx_tpu.parallel.mesh import mesh_from_devices
        from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns
        mesh = mesh_from_devices({"tp": args.tp},
                                 jax.devices()[:args.tp])
        server_fns = make_tp_server_fns(params, cfg, mesh,
                                        chunk=args.chunk,
                                        family=args.family,
                                        kv_int8=args.int8_kv)

    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, rng.integers(3, 14),
                            dtype=np.int32)
               for _ in range(args.requests)]
    n_new = [int(rng.integers(2, 12)) for _ in range(args.requests)]
    max_len = 14 + max(n_new) + args.chunk + 1

    t0 = time.perf_counter()
    outs = serving.serve_greedy(params, cfg, prompts, n_new,
                                n_slots=args.slots, max_len=max_len,
                                family=mod, chunk=args.chunk,
                                kv_int8=args.int8_kv,
                                server_fns=server_fns)
    dt = time.perf_counter() - t0
    total = sum(n_new)
    print(f"{args.requests} requests (lens "
          f"{[len(p) for p in prompts]} -> +{n_new}) through "
          f"{args.slots} slots, chunk={args.chunk}: "
          f"{total} tokens in {dt:.2f}s")
    for i, o in enumerate(outs[:3]):
        print(f"req {i}: {o.tolist()}")

    if args.verify:
        for p, g, n in zip(prompts, outs, n_new):
            want = mod.generate(params, cfg, jnp.asarray(p)[None], n,
                                max_len=max_len, kv_int8=args.int8_kv)
            np.testing.assert_array_equal(np.asarray(g),
                                          np.asarray(want)[0])
        print("all outputs equal their solo runs")
    print("example OK")


if __name__ == "__main__":
    main()
