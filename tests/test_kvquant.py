"""Int8 KV cache (ops/kvquant.py): long-context decode streams the
cache, not the weights — int8 codes + per-(position, head) scales halve
that stream. These tests pin quality and mechanics on CPU; the
bandwidth claim is not measured (PERF.md question 2: the int8-page
cell).
"""

import jax
import jax.numpy as jnp
import numpy as np

from mpi_acx_tpu.models import llama as lm
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.ops.kvquant import kv_dequant, kv_quant
from tests.test_wquant import _trained_gpt2, _trained_llama


def test_kv_roundtrip_error_bound():
    """Per-vector symmetric int8: elementwise error <= scale/2."""
    x = jax.random.normal(jax.random.key(0), (3, 5, 4, 16)) * 2.0
    q, s = kv_quant(x)
    back = kv_dequant(q, s, jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    assert float(jnp.max(jnp.abs(back - x) / (amax / 127.0))) <= 0.5 + 1e-3
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32


def test_int8_kv_greedy_tokens_equal_gpt2():
    """Greedy decode with the quantized cache emits the same tokens as
    the bf16 cache on a trained model (well-separated argmaxes survive
    the per-vector quantization noise)."""
    cfg, params, tok = _trained_gpt2()
    prompt = tok[:2, :8]
    want = tfm.generate(params, cfg, prompt, 8, max_len=24)
    got = tfm.generate(params, cfg, prompt, 8, max_len=24, kv_int8=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_greedy_tokens_equal_llama():
    """Same for the GQA cache (scales stored per KV head — the
    un-repeated layout keeps its bandwidth win)."""
    cfg, params, tok = _trained_llama()
    prompt = tok[:2, :8]
    want = lm.generate(params, cfg, prompt, 8, max_len=24)
    got = lm.generate(params, cfg, prompt, 8, max_len=24, kv_int8=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_moe_generate_runs_and_matches():
    """The MoE family rides the shared scaffold: kv_int8 composes with
    the routed FFN (drop-free capacity) and matches the bf16-cache
    output."""
    from mpi_acx_tpu.models import moe_transformer as mtf
    cfg = mtf.tiny_moe_config(vocab=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, n_experts=4, top_k=1,
                              capacity_factor=4.0, max_seq=32)
    params = mtf.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    want = mtf.generate(params, cfg, prompt, 6, max_len=16)
    got = mtf.generate(params, cfg, prompt, 6, max_len=16, kv_int8=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_composes_with_int8_weights():
    """Both quantizations together — int8 weights (wquant) + int8 KV
    cache — still reproduce the separately-quantized greedy tokens."""
    from mpi_acx_tpu.ops.wquant import GPT2_WEIGHTS, quantize_weights_int8
    cfg, params, tok = _trained_gpt2()
    q = quantize_weights_int8(params, GPT2_WEIGHTS)
    prompt = tok[:2, :8]
    want = tfm.generate(q, cfg, prompt, 8, max_len=24)
    got = tfm.generate(q, cfg, prompt, 8, max_len=24, kv_int8=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_sampled_decode_matches():
    """The stochastic sampler (same key) over the quantized cache emits
    the same tokens — kv_int8 threads through generate_sample too."""
    cfg, params, tok = _trained_gpt2()
    prompt = tok[:2, :8]
    want = tfm.generate_sample(params, cfg, prompt, 8,
                               jax.random.key(3), temperature=0.8,
                               top_k=16, max_len=24)
    got = tfm.generate_sample(params, cfg, prompt, 8,
                              jax.random.key(3), temperature=0.8,
                              top_k=16, max_len=24, kv_int8=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_decode_logits_close():
    """Quality metric beyond greedy parity, THROUGH the cache path:
    run a prefill + decode chain with the bf16 and the int8 cache and
    bound the relative logit error per step — a scale-layout bug that
    degrades logits without flipping well-separated argmaxes fails
    here."""
    cfg, params, tok = _trained_gpt2()
    prompt = tok[:2, :8]

    def chain(kv_int8, steps=6):
        logits, cache = tfm.prefill(params, cfg, prompt, 24,
                                    last_only=True, kv_int8=kv_int8)
        out = [logits[:, -1]]
        toknext = jnp.argmax(logits[:, -1], axis=-1)
        for _ in range(steps):
            logits, cache = tfm.decode_step(params, cfg, cache, toknext)
            out.append(logits)
            toknext = jnp.argmax(logits, axis=-1)
        return jnp.stack(out)

    base = chain(False)
    q = chain(True)
    rel = float(jnp.linalg.norm(q - base) / jnp.linalg.norm(base))
    assert rel < 0.05, rel


def test_int8_cache_halves_storage():
    """The bandwidth numerator: int8 codes + f32/Dh scales vs bf16 —
    ~53% of the bf16 cache bytes at Dh=64."""
    cfg = tfm.tiny_config(vocab=64, d_model=128, n_heads=2, n_layers=2,
                          d_ff=128, max_seq=64)
    c16 = tfm.init_kv_cache(cfg, 4, 64)
    c8 = tfm.init_kv_cache(cfg, 4, 64, kv_int8=True)

    def nbytes(c):
        return sum(v.size * v.dtype.itemsize for k, v in c.items()
                   if k != "pos")

    assert nbytes(c8) < 0.6 * nbytes(c16), (nbytes(c8), nbytes(c16))


def test_scale_on_scores_matches_dequant_attend():
    """grouped_decode_attend with (codes, scales) tuples must compute
    the same attention as explicit dequantize-then-attend — the tuple
    path only re-factors the scale multiplies onto the logits/probs
    (the r05 chip A/B showed materializing the dequantized cache is a
    0.73x regression, so the factored path is the production one)."""
    from mpi_acx_tpu.models.decoding import (grouped_decode_attend,
                                             to_cache_layout)

    key = jax.random.key(3)
    B, W, Hkv, n_rep, D, L = 2, 3, 2, 2, 16, 12
    q = jax.random.normal(key, (B, W, Hkv * n_rep, D), jnp.float32)
    kf = jax.random.normal(jax.random.key(4), (B, L, Hkv, D))
    vf = jax.random.normal(jax.random.key(5), (B, L, Hkv, D))
    kq, ks = map(to_cache_layout, kv_quant(kf))
    vq, vs = map(to_cache_layout, kv_quant(vf))

    want = grouped_decode_attend(q, kv_dequant(kq, ks, q.dtype),
                                 kv_dequant(vq, vs, q.dtype), 4, L, n_rep)
    got = grouped_decode_attend(q, (kq, ks), (vq, vs), 4, L, n_rep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
