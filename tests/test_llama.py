"""Llama model family: RoPE, GQA, SwiGLU, causality, decode parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_acx_tpu.models.llama import (
    decode_step,
    forward,
    generate,
    init_kv_cache,
    init_params,
    llama3_8b,
    loss_fn,
    prefill,
    rope,
    tiny_llama,
)


@pytest.fixture
def setup():
    cfg = dataclasses.replace(tiny_llama(), dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    return cfg, params, tokens


def test_forward_shapes(setup):
    cfg, params, tokens = setup
    logits = jax.jit(lambda p, t: forward(p, cfg, t))(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_llama3_8b_geometry():
    cfg = llama3_8b()
    assert cfg.head_dim == 128
    assert cfg.n_heads % cfg.n_kv_heads == 0


def test_causality(setup):
    """A future-token change must not affect past logits."""
    cfg, params, tokens = setup
    t2 = tokens.at[0, 12].set((tokens[0, 12] + 1) % cfg.vocab)
    l1 = forward(params, cfg, tokens)
    l2 = forward(params, cfg, t2)
    np.testing.assert_allclose(np.asarray(l1[0, :12]),
                               np.asarray(l2[0, :12]), rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(l1[0, 12:] - l2[0, 12:]).max()) > 0


def test_rope_relative_position():
    """RoPE's defining property: <rope(q,i), rope(k,j)> depends only on
    i - j."""
    D = 32
    q = jax.random.normal(jax.random.key(0), (1, 1, 1, D))
    k = jax.random.normal(jax.random.key(1), (1, 1, 1, D))
    theta = 10000.0

    def dot_at(i, j):
        qi = rope(q, jnp.asarray([i]), theta)
        kj = rope(k, jnp.asarray([j]), theta)
        return float(jnp.sum(qi * kj))

    assert abs(dot_at(3, 1) - dot_at(10, 8)) < 1e-4     # same offset 2
    assert abs(dot_at(3, 1) - dot_at(5, 1)) > 1e-4      # different offset


def test_gqa_equals_mha_with_duplicated_weights(setup):
    """GQA must equal full multi-head attention whose K/V weight head
    blocks are the GQA weights explicitly duplicated per group — the
    property that pins the group-to-query-head routing."""
    cfg, params, tokens = setup
    n_rep = cfg.n_heads // cfg.n_kv_heads
    dh = cfg.head_dim

    def dup_heads(w):
        # [d, Hkv*dh] -> [d, Hkv, dh] -> repeat groups -> [d, Hq*dh];
        # query head g*n_rep + r must read KV group g.
        d = w.shape[0]
        w = w.reshape(d, cfg.n_kv_heads, 1, dh)
        w = jnp.broadcast_to(w, (d, cfg.n_kv_heads, n_rep, dh))
        return w.reshape(d, cfg.n_kv_heads * n_rep * dh)

    mha = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
    p_mha = dict(params)
    p_mha["layers"] = dict(params["layers"])
    p_mha["layers"]["wk"] = jax.vmap(dup_heads)(params["layers"]["wk"])
    p_mha["layers"]["wv"] = jax.vmap(dup_heads)(params["layers"]["wv"])

    out_gqa = forward(params, cfg, tokens)
    out_mha = forward(p_mha, mha, tokens)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_mha),
                               rtol=1e-5, atol=1e-5)

    # Decode path uses the grouped einsum (no cache repeat) — it must
    # agree with the same duplicated-weight MHA decode.
    _, cache_g = prefill(params, cfg, tokens, max_len=20)
    _, cache_m = prefill(p_mha, mha, tokens, max_len=20)
    nxt = jax.random.randint(jax.random.key(5), (2,), 0, cfg.vocab)
    lg, _ = decode_step(params, cfg, cache_g, nxt)
    lm, _ = decode_step(p_mha, mha, cache_m, nxt)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lm), rtol=1e-5,
                               atol=1e-5)


def test_grad_finite(setup):
    cfg, params, tokens = setup
    targets = jnp.roll(tokens, -1, axis=-1)
    loss, g = jax.value_and_grad(
        lambda p: loss_fn(p, cfg, tokens, targets))(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


def test_training_converges(setup):
    cfg, params, tokens = setup
    targets = jnp.roll(tokens, -1, axis=-1)
    step = jax.jit(lambda p: jax.value_and_grad(
        lambda p: loss_fn(p, cfg, tokens, targets))(p))
    l0 = None
    for i in range(8):
        loss, g = step(params)
        if l0 is None:
            l0 = float(loss)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, g)
    assert float(loss) < l0


class TestDecode:
    def test_prefill_matches_forward(self, setup):
        cfg, params, tokens = setup
        full = forward(params, cfg, tokens)
        pre, cache = prefill(params, cfg, tokens, max_len=32)
        np.testing.assert_allclose(np.asarray(full), np.asarray(pre),
                                   rtol=1e-4, atol=1e-4)
        assert cache["k"].shape == (cfg.n_layers, 2, cfg.n_kv_heads,
                                    cfg.head_dim, 32)

    def test_decode_matches_forward(self, setup):
        cfg, params, tokens = setup
        _, cache = prefill(params, cfg, tokens, max_len=32)
        step = jax.jit(lambda c, t: decode_step(params, cfg, c, t))
        seq = tokens
        for i in range(3):
            nxt = jax.random.randint(jax.random.key(20 + i), (2,), 0,
                                     cfg.vocab)
            logits, cache = step(cache, nxt)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
            dense = forward(params, cfg, seq)[:, -1]
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(dense), rtol=2e-3,
                                       atol=2e-3)

    def test_generate_matches_dense_rollout(self, setup):
        cfg, params, tokens = setup
        out = jax.jit(lambda p, t: generate(p, cfg, t, n_new=4))(params,
                                                                 tokens)
        seq = tokens
        for _ in range(4):
            nxt = jnp.argmax(forward(params, cfg, seq)[:, -1], axis=-1)
            seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)],
                                  axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))

    def test_decode_from_empty_cache(self, setup):
        cfg, params, tokens = setup
        cache = init_kv_cache(cfg, batch=2, max_len=16)
        step = jax.jit(lambda c, t: decode_step(params, cfg, c, t))
        for i in range(3):
            logits, cache = step(cache, tokens[:, i])
            dense = forward(params, cfg, tokens[:, :i + 1])[:, -1]
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(dense), rtol=2e-3,
                                       atol=2e-3)
