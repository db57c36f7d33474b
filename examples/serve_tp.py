"""Tensor-parallel serving example: Megatron-split generation over a
device mesh, for the GPT-2, Llama, or MoE family.

Runs on real TPU chips or a virtual CPU mesh:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/serve_tp.py --family llama --tp 4 --temperature 0.8

The weights and KV cache are sharded over the 'tp' axis (Llama shards by
KV-head group, keeping GQA's small cache per rank); the entire prefill +
decode loop is one shard_map program with two psums per layer. Output is
token-identical to the single-device generate path.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["gpt2", "llama", "moe"],
                default="gpt2")
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--n-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--speculative", action="store_true",
                    help="draft-proposes / target-verifies decoding, "
                         "draft and target both TP-split")
    ap.add_argument("--ep-dispatch", default="auto",
                    choices=["auto", "sharded", "replicated"],
                    help="MoE family only: how tokens reach their "
                         "experts (auto = sharded when the call's "
                         "token count divides tp, else replicated)")
    args = ap.parse_args()

    import jax

    from mpi_acx_tpu import backend
    backend.enable_compile_cache()

    from mpi_acx_tpu.models import llama as lm
    from mpi_acx_tpu.models import moe_transformer as mtf
    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.parallel import (make_tp_generate,
                                      make_tp_generate_llama,
                                      make_tp_generate_moe,
                                      mesh_from_devices)

    n_dev = len(jax.devices())
    if args.tp > n_dev:
        raise SystemExit(f"--tp {args.tp} > available devices ({n_dev}); "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    mesh = mesh_from_devices({"tp": args.tp}, jax.devices()[:args.tp])

    if args.family == "llama":
        # KV heads must split over tp: scale the toy config with it.
        cfg = lm.tiny_llama(n_layers=2, n_heads=2 * args.tp,
                            n_kv_heads=args.tp)
        params = lm.init_params(jax.random.key(0), cfg)
        gen = make_tp_generate_llama(cfg, mesh, args.n_new,
                                     temperature=args.temperature,
                                     top_k=args.top_k, top_p=args.top_p)
        single = lambda p, t: lm.generate(  # noqa: E731
            p, cfg, t, args.n_new, max_len=t.shape[1] + args.n_new)
    elif args.family == "moe":
        # Experts split over tp: scale the expert count with it.
        cfg = mtf.tiny_moe_config(n_layers=2, n_heads=2 * args.tp,
                                  n_experts=2 * args.tp, top_k=2,
                                  capacity_factor=2 * args.tp)
        params = mtf.init_params(jax.random.key(0), cfg)
        gen = make_tp_generate_moe(cfg, mesh, args.n_new,
                                   temperature=args.temperature,
                                   top_k=args.top_k, top_p=args.top_p,
                                   ep_dispatch=args.ep_dispatch)
        single = lambda p, t: mtf.generate(  # noqa: E731
            p, cfg, t, args.n_new, max_len=t.shape[1] + args.n_new)
    else:
        cfg = tfm.tiny_config(n_layers=2)
        params = tfm.init_params(jax.random.key(0), cfg)
        gen = make_tp_generate(cfg, mesh, args.n_new,
                               temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p)
        single = lambda p, t: tfm.generate(  # noqa: E731
            p, cfg, t, args.n_new, max_len=t.shape[1] + args.n_new)

    if args.speculative:
        import dataclasses
        from mpi_acx_tpu.parallel import make_tp_speculative_generate
        dcfg = dataclasses.replace(cfg, n_layers=1)
        dinit = {"llama": lm.init_params, "moe": mtf.init_params,
                 "gpt2": tfm.init_params}[args.family]
        dparams = dinit(jax.random.key(7), dcfg)
        sgen = make_tp_speculative_generate(
            dcfg, cfg, mesh, args.n_new, k=4,
            temperature=args.temperature,
            ep_dispatch=args.ep_dispatch)
        prompt = jax.random.randint(jax.random.key(1), (1, 8), 0,
                                    cfg.vocab)
        out, stats = sgen(dparams, params, prompt, jax.random.key(2))
        print(f"family={args.family} tp={args.tp} speculative "
              f"rounds={int(stats['rounds'])} "
              f"accepted={int(stats['drafted_accepted'])}")
        print("output :", out[:, prompt.shape[1]:].tolist())
        return

    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    out = gen(params, prompt, jax.random.key(2))
    print(f"family={args.family} tp={args.tp} devices={n_dev}")
    print("prompt :", prompt.tolist())
    print("output :", out[:, prompt.shape[1]:].tolist())

    if args.temperature == 0.0:
        import numpy as np
        ref = single(params, prompt)
        match = bool((np.asarray(out) == np.asarray(ref)).all())
        print("matches single-device greedy:", match)
        if not match:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
