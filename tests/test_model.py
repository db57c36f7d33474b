"""Transformer model family: shapes, gradient sanity, training progress,
and the MoE/expert-parallel layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mpi_acx_tpu.models import (
    MoeConfig, init_moe_params, moe_layer,
    gpt2_small, init_params, forward, loss_fn, tiny_config,
)
from mpi_acx_tpu.parallel import make_mesh


def test_forward_shapes_and_dtype():
    cfg = tiny_config()
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab)
    logits = jax.jit(lambda p, t: forward(p, cfg, t))(params, tokens)
    assert logits.shape == (2, 32, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_gpt2_small_is_125m():
    cfg = gpt2_small()
    params = init_params(jax.random.key(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert 115e6 < n < 135e6, n  # 124M + pos embeddings


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = tiny_config(n_layers=2)
    params = init_params(jax.random.key(0), cfg)
    t1 = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab)
    t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab)
    l1 = forward(params, cfg, t1)
    l2 = forward(params, cfg, t2)
    np.testing.assert_allclose(np.asarray(l1[0, :10]), np.asarray(l2[0, :10]),
                               atol=2e-3)


def test_loss_decreases_with_sgd():
    cfg = tiny_config(n_layers=2, d_model=64, d_ff=128, vocab=64)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(loss_fn)(p, cfg, tokens, targets)
        return l, jax.tree.map(lambda a, b: a - 0.5 * b, p, g)

    l0, params = step(params)
    for _ in range(10):
        l1, params = step(params)
    assert float(l1) < float(l0)


def test_moe_layer_single_device():
    cfg = MoeConfig(d_model=32, d_ff=64, n_experts=4)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    y = jax.jit(lambda p, x: moe_layer(p, x, cfg))(params, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y).max()) > 0


def test_moe_expert_parallel_matches_single_device():
    """EP over 8 devices == the same routing computed on one device."""
    mesh = make_mesh(8)
    cfg = MoeConfig(d_model=16, d_ff=32, n_experts=8, capacity_factor=8.0)
    params = init_moe_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (32, 16), jnp.float32)

    want = moe_layer(params, x, cfg)

    f = shard_map(
        lambda p, xx: moe_layer(p, xx, cfg, ep_axis="x"),
        mesh=mesh,
        in_specs=({"gate": P(), "w1": P("x"), "w2": P("x")}, P()),
        out_specs=P(),
        check_vma=False)
    got = f(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=1e-5)


# -- KV-cache decode -------------------------------------------------------


class TestDecode:
    def _setup(self, dtype=jnp.float32):
        import dataclasses
        from mpi_acx_tpu.models.transformer import TransformerConfig
        cfg = dataclasses.replace(tiny_config(n_layers=2), dtype=dtype)
        params = init_params(jax.random.key(0), cfg)
        tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab)
        return cfg, params, tokens

    def test_prefill_matches_forward(self):
        from mpi_acx_tpu.models.transformer import prefill
        cfg, params, tokens = self._setup()
        full = forward(params, cfg, tokens)
        pre, cache = prefill(params, cfg, tokens, max_len=32)
        np.testing.assert_allclose(np.asarray(full), np.asarray(pre),
                                   rtol=1e-4, atol=1e-4)
        assert int(cache["pos"]) == tokens.shape[1]
        assert cache["k"].shape == (cfg.n_layers, 2, cfg.n_heads,
                                    cfg.head_dim, 32)

    def test_decode_step_matches_forward(self):
        """Logits from cached single-token decode == logits from running
        the whole prefix densely (the KV cache is exact, not approximate)."""
        from mpi_acx_tpu.models.transformer import prefill, decode_step
        cfg, params, tokens = self._setup()
        _, cache = prefill(params, cfg, tokens, max_len=32)
        step = jax.jit(lambda c, t: decode_step(params, cfg, c, t))
        seq = tokens
        for i in range(4):
            nxt = jax.random.randint(jax.random.key(10 + i), (2,), 0,
                                     cfg.vocab)
            logits, cache = step(cache, nxt)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
            dense = forward(params, cfg, seq)[:, -1]
            np.testing.assert_allclose(np.asarray(logits), np.asarray(dense),
                                       rtol=2e-3, atol=2e-3)
        assert int(cache["pos"]) == tokens.shape[1] + 4

    def test_generate_greedy_matches_dense_rollout(self):
        from mpi_acx_tpu.models.transformer import generate
        cfg, params, tokens = self._setup()
        out = jax.jit(
            lambda p, t: generate(p, cfg, t, n_new=5))(params, tokens)
        assert out.shape == (2, tokens.shape[1] + 5)
        # naive rollout: full forward each step, greedy argmax
        seq = tokens
        for _ in range(5):
            nxt = jnp.argmax(forward(params, cfg, seq)[:, -1], axis=-1)
            seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)],
                                  axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))

    def test_decode_bf16(self):
        """The bf16 path stays finite and shape-correct."""
        from mpi_acx_tpu.models.transformer import generate
        cfg, params, tokens = self._setup(dtype=jnp.bfloat16)
        out = generate(params, cfg, tokens, n_new=3)
        assert out.shape == (2, 15)
        assert bool((out >= 0).all()) and bool((out < cfg.vocab).all())

    def test_cast_params_decode(self):
        """bf16-cast weights (the inference configuration) generate the
        same shapes and valid tokens."""
        from mpi_acx_tpu.models.transformer import cast_params, generate
        cfg, params, tokens = self._setup(dtype=jnp.bfloat16)
        p16 = cast_params(params)
        assert all(p.dtype == jnp.bfloat16 for p in jax.tree.leaves(p16))
        out = generate(p16, cfg, tokens, n_new=3)
        assert out.shape == (2, 15)
        assert bool((out >= 0).all()) and bool((out < cfg.vocab).all())

    def test_decode_from_empty_cache(self):
        """Decoding token-by-token from an init_kv_cache (no prefill)
        matches the dense forward at every step."""
        from mpi_acx_tpu.models.transformer import init_kv_cache, decode_step
        cfg, params, tokens = self._setup()
        cache = init_kv_cache(cfg, batch=2, max_len=16)
        step = jax.jit(lambda c, t: decode_step(params, cfg, c, t))
        for i in range(5):
            logits, cache = step(cache, tokens[:, i])
            dense = forward(params, cfg, tokens[:, :i + 1])[:, -1]
            np.testing.assert_allclose(np.asarray(logits), np.asarray(dense),
                                       rtol=2e-3, atol=2e-3)

    def test_generate_rejects_past_max_seq(self):
        cfg, params, tokens = self._setup()
        from mpi_acx_tpu.models.transformer import generate
        with pytest.raises(AssertionError):
            generate(params, cfg, tokens, n_new=cfg.max_seq)
