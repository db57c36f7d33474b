"""Int8 weight-only quantization (ops/wquant.py): the decode-roofline
optimization — weight bytes halve, so the bandwidth-bound decode floor
drops ~2x (no benchmark cell serves int8 weights yet: not measured).
These tests pin the quality and mechanics on CPU:

* quantized logits stay close to bf16 logits (per-channel int8 bound),
* greedy decode on a TRAINED model emits the same tokens (quantization
  noise must not flip well-separated argmaxes),
* the pytree keeps its structure (+_scale companions) so every decode
  scaffold — prefill, decode_step, generate — runs unchanged,
* weight_bytes reflects the ~2x storage cut (the roofline numerator).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mpi_acx_tpu.models import llama as lm
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.ops.wquant import (GPT2_WEIGHTS, LLAMA_WEIGHTS,
                                    quantize_weights_int8, weight_bytes,
                                    wread)


def test_wread_dequant_roundtrip_error_bound():
    """Per-channel symmetric int8: reconstruction error per element is
    bounded by scale/2 = amax/254 of its output channel."""
    w = jax.random.normal(jax.random.key(0), (4, 64, 32)) * 0.3
    lay = {"w": w}
    q = quantize_weights_int8({"layers": lay}, ["w"])["layers"]
    back = wread(q, "w", jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    assert float(jnp.max(jnp.abs(back - w) / (amax / 127.0))) <= 0.5 + 1e-3


def _train(mod, cfg, steps=60):
    """Shared Adam scaffold: train `mod`'s model on the repetition task
    so greedy argmaxes are well-separated before quantizing."""
    params = mod.init_params(jax.random.key(0), cfg)
    tok = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab)
    opt = optax.adam(3e-3)
    st = opt.init(params)

    @jax.jit
    def step(p, st):
        loss, g = jax.value_and_grad(mod.loss_fn)(p, cfg, tok, tok)
        up, st = opt.update(g, st)
        return optax.apply_updates(p, up), st, loss

    for _ in range(steps):
        params, st, _ = step(params, st)
    return params, tok


def _trained_gpt2():
    cfg = tfm.TransformerConfig(**{**tfm.tiny_config(
        vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq=32).__dict__, "dtype": jnp.float32})
    params, tok = _train(tfm, cfg)
    return cfg, params, tok


def _trained_llama():
    c = lm.tiny_llama(vocab=64, d_model=32, n_heads=4, n_kv_heads=2,
                      n_layers=2, d_ff=64, max_seq=32)
    cfg = lm.LlamaConfig(**{**c.__dict__, "dtype": jnp.float32})
    params, tok = _train(lm, cfg)
    return cfg, params, tok


def test_int8_weights_logits_close_and_greedy_tokens_equal():
    cfg, params, tok = _trained_gpt2()
    qparams = quantize_weights_int8(params, GPT2_WEIGHTS)

    logits = tfm.forward(params, cfg, tok[:2])
    qlogits = tfm.forward(qparams, cfg, tok[:2])
    # Quality bound: relative error of the logit vector, f32 reference.
    rel = float(jnp.linalg.norm(qlogits - logits)
                / jnp.linalg.norm(logits))
    assert rel < 0.05, rel

    # Greedy decode: same scaffold, same tokens on the trained task.
    prompt = tok[:2, :8]
    want = tfm.generate(params, cfg, prompt, 8, max_len=24)
    got = tfm.generate(qparams, cfg, prompt, 8, max_len=24)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_weights_llama_generate_runs_and_matches():
    cfg, params, tok = _trained_llama()
    qparams = quantize_weights_int8(params, LLAMA_WEIGHTS)
    prompt = tok[:2, :8]
    want = lm.generate(params, cfg, prompt, 8, max_len=24)
    got = lm.generate(qparams, cfg, prompt, 8, max_len=24)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_weight_bytes_roughly_halve():
    """The roofline numerator: GPT-2's layer matmuls dominate its
    parameter bytes, so int8 storage lands well under 60% of bf16."""
    cfg = tfm.tiny_config(vocab=64, d_model=64, n_heads=4, n_layers=4,
                          d_ff=256, max_seq=32)
    params = tfm.cast_params(tfm.init_params(jax.random.key(0), cfg),
                             jnp.bfloat16)
    q = quantize_weights_int8(params, GPT2_WEIGHTS)
    assert weight_bytes(q) < 0.6 * weight_bytes(params), (
        weight_bytes(q), weight_bytes(params))


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_int8_weights_speculative_matches(family):
    """Speculative decoding over quantized draft AND target (every
    weight read goes through wread, including the W-wide window's wo)
    must emit the same tokens as quantized target-only greedy — both
    families, as the docs claim."""
    import dataclasses
    from mpi_acx_tpu.models.speculative import speculative_generate

    if family == "gpt2":
        cfg, params, tok = _trained_gpt2()
        mod, names = tfm, GPT2_WEIGHTS
    else:
        cfg, params, tok = _trained_llama()
        mod, names = lm, LLAMA_WEIGHTS
    dcfg = dataclasses.replace(cfg, n_layers=1)
    dparams = mod.init_params(jax.random.key(9), dcfg)
    qp = quantize_weights_int8(params, names)
    qd = quantize_weights_int8(dparams, names)
    prompt = tok[:1, :8]
    want = mod.generate(qp, cfg, prompt, 8, max_len=24)
    got, _ = speculative_generate(qd, dcfg, qp, cfg, prompt, 8, k=3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moe_forward_rejects_quantized_experts():
    """block()/_hidden (the training+forward path) must refuse int8
    expert weights loudly — not only the serving _moe_ffn scaffold."""
    from mpi_acx_tpu.models import moe_transformer as mtf
    cfg = mtf.tiny_moe_config(vocab=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, n_experts=4, top_k=1,
                              capacity_factor=4.0, max_seq=32)
    params = mtf.init_params(jax.random.key(0), cfg)
    q = quantize_weights_int8(params, ("w1", "w2"))
    tok = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    with pytest.raises(ValueError, match="quantization"):
        mtf.forward(q, cfg, tok)


def test_tp_serving_int8_matches_single_device_gpt2():
    """TP serving over an int8 checkpoint (scale companions sharded
    alongside their weights, wread in the TP layer ops) must emit the
    same tokens as the single-device quantized generate."""
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_generate
    cfg, params, tok = _trained_gpt2()
    q = quantize_weights_int8(params, GPT2_WEIGHTS)
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    prompt = tok[:2, :8]
    want = tfm.generate(q, cfg, prompt, 8, max_len=24)
    gen = make_tp_generate(cfg, mesh, 8)
    got = gen(q, prompt, jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The same builder still serves the PLAIN checkpoint (separate
    # compiled program, same per-shard code).
    want_p = tfm.generate(params, cfg, prompt, 8, max_len=24)
    got_p = gen(params, prompt, jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))


def test_tp_serving_int8_matches_single_device_llama():
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_generate_llama
    cfg, params, tok = _trained_llama()
    q = quantize_weights_int8(params, LLAMA_WEIGHTS)
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    prompt = tok[:2, :8]
    want = lm.generate(q, cfg, prompt, 8, max_len=24)
    gen = make_tp_generate_llama(cfg, mesh, 8)
    got = gen(q, prompt, jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_speculative_int8_matches_single_device():
    """TP speculative decoding over a quantized draft AND target must
    emit the same tokens/stats as the single-device quantized run —
    the (draft, target) scale-key cache and both families' scale
    re-layouts compose with the speculative loop."""
    import dataclasses
    from mpi_acx_tpu.models.speculative import speculative_generate
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import \
        make_tp_speculative_generate
    cfg, params, tok = _trained_gpt2()
    dcfg = dataclasses.replace(cfg, n_layers=1)
    dparams = tfm.init_params(jax.random.key(9), dcfg)
    qp = quantize_weights_int8(params, GPT2_WEIGHTS)
    qd = quantize_weights_int8(dparams, GPT2_WEIGHTS)
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    prompt = tok[:1, :8]
    want, wstats = speculative_generate(qd, dcfg, qp, cfg, prompt, 8,
                                        k=3)
    gen = make_tp_speculative_generate(dcfg, cfg, mesh, 8, k=3)
    got, stats = gen(qd, qp, prompt, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(stats["rounds"]) == int(wstats["rounds"])


def test_tp_moe_quantized_attention_matches_single_device():
    """MoE TP serving with int8 ATTENTION weights (the supported
    subset) matches the single-device quantized generate; experts stay
    bf16."""
    from mpi_acx_tpu.models import moe_transformer as mtf
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_generate_moe
    cfg = mtf.tiny_moe_config(vocab=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, n_experts=4, top_k=1,
                              capacity_factor=4.0, max_seq=32)
    params = mtf.init_params(jax.random.key(0), cfg)
    q = quantize_weights_int8(params, ("wqkv", "wo"))
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    want = mtf.generate(q, cfg, prompt, 6, max_len=16)
    gen = make_tp_generate_moe(cfg, mesh, 6)
    got = gen(q, prompt, jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_serving_rejects_quantized_moe_experts():
    """Quantized MoE EXPERT weights stay unsupported in TP serving:
    the restricted scale-spec map must raise loudly."""
    from mpi_acx_tpu.models import moe_transformer as mtf
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_generate_moe
    cfg = mtf.tiny_moe_config(vocab=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, n_experts=4, top_k=1,
                              capacity_factor=4.0, max_seq=32)
    params = mtf.init_params(jax.random.key(0), cfg)
    q = quantize_weights_int8(params, ("w1", "w2"))
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    gen = make_tp_generate_moe(cfg, mesh, 4)
    prompt = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    with pytest.raises(ValueError, match="w1_scale"):
        gen(q, prompt, jax.random.key(2))


def test_weight_quantization_loss_delta_bounded():
    """Quality metric beyond greedy parity: teacher-forced mean NLL of
    a trained model moves by < 2% relative under int8 weights
    (per-channel scales keep logits close, so the measured loss barely
    moves)."""
    cfg, params, tok = _trained_gpt2()
    probe = jax.random.randint(jax.random.key(11), (8, 16), 0,
                               cfg.vocab)
    base = float(tfm.loss_fn(params, cfg, probe, probe))
    qw = float(tfm.loss_fn(quantize_weights_int8(params, GPT2_WEIGHTS),
                           cfg, probe, probe))
    assert abs(qw - base) / base < 0.02, (base, qw)


def test_unquantized_path_untouched():
    """wread without a _scale companion is exactly astype — the shared
    read path must not perturb normal checkpoints."""
    w = jax.random.normal(jax.random.key(0), (8, 8), jnp.float32)
    out = wread({"w": w}, "w", jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(w.astype(jnp.bfloat16)))
