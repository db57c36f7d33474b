"""End-to-end inference example: KV-cache decode with greedy or sampled
continuation, on either model family — optionally speculative (a small
draft proposes, the target verifies k tokens per window pass) and
batched (rows advance independently).

  python examples/generate_text.py --family llama --temperature 0.8 \
      --top-k 40 --top-p 0.95
  python examples/generate_text.py --speculative --batch 4
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", choices=["gpt2", "llama"], default="gpt2")
    ap.add_argument("--n-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--speculative", action="store_true",
                    help="draft-proposes / target-verifies decoding "
                         "(greedy: output equals plain greedy decode)")
    ap.add_argument("--batch", type=int, default=1,
                    help="rows decode together; each row's output and "
                         "round count equal its own solo run")
    ap.add_argument("--int8-weights", action="store_true",
                    help="int8 weight-only quantization (ops/wquant.py):"
                         " halves the weight stream decode re-reads "
                         "every token")
    ap.add_argument("--int8-kv", action="store_true",
                    help="int8 KV cache (ops/kvquant.py): halves the "
                         "cache stream, the binding term at long "
                         "context")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mpi_acx_tpu import backend
    backend.enable_compile_cache()

    from mpi_acx_tpu.models import llama as lm
    from mpi_acx_tpu.models import transformer as tfm

    if args.family == "llama":
        cfg = lm.tiny_llama(n_layers=2)
        params = lm.init_params(jax.random.key(0), cfg)
        gen, gen_s = lm.generate, lm.generate_sample
    else:
        cfg = tfm.tiny_config(n_layers=2)
        params = tfm.init_params(jax.random.key(0), cfg)
        gen, gen_s = tfm.generate, tfm.generate_sample
    if args.speculative and args.int8_kv:
        ap.error("--int8-kv does not apply to the speculative path "
                 "(its verify windows manage their own cache); "
                 "--int8-weights composes with --speculative fine")
    if args.int8_weights:
        from mpi_acx_tpu.ops.wquant import (GPT2_WEIGHTS, LLAMA_WEIGHTS,
                                            quantize_weights_int8)
        wnames = (LLAMA_WEIGHTS if args.family == "llama"
                  else GPT2_WEIGHTS)
        params = quantize_weights_int8(params, wnames)

    base = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    prompt = jnp.tile(base, (args.batch, 1)).at[:, -1].add(
        jnp.arange(args.batch))
    if args.speculative:
        import dataclasses
        from mpi_acx_tpu.models.speculative import (speculative_generate,
                                                    speculative_sample)
        dcfg = dataclasses.replace(cfg, n_layers=1)
        if args.family == "llama":
            dparams = lm.init_params(jax.random.key(7), dcfg)
        else:
            dparams = tfm.init_params(jax.random.key(7), dcfg)
        if args.temperature == 0.0:
            out, stats = speculative_generate(dparams, dcfg, params, cfg,
                                              prompt, args.n_new, k=4)
        else:
            out, stats = speculative_sample(
                dparams, dcfg, params, cfg, prompt, args.n_new,
                jax.random.key(42), k=4, temperature=args.temperature)
        import numpy as np
        print("rounds per row:", np.asarray(stats["rounds"]).tolist())
    elif args.temperature == 0.0 and args.top_k is None and args.top_p is None:
        out = gen(params, cfg, prompt, n_new=args.n_new,
                  kv_int8=args.int8_kv)
    else:
        out = gen_s(params, cfg, prompt, n_new=args.n_new,
                    key=jax.random.key(42), temperature=args.temperature,
                    top_k=args.top_k, top_p=args.top_p,
                    kv_int8=args.int8_kv)
    for b in range(args.batch):
        print(f"{args.family} row {b}: ",
              out[b, prompt.shape[1]:].tolist())
    print("example OK")


if __name__ == "__main__":
    main()
