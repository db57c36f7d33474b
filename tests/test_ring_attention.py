"""Ring attention == full attention, causal and non-causal, plus grads;
an axis of ONE runs no ring and gives the ring-of-one's bits."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mpi_acx_tpu.ops.attention import flash_attention_lse
from mpi_acx_tpu.parallel import make_mesh
from mpi_acx_tpu.parallel.ring_attention import (
    _NEG,
    _dense_block,
    attention_calls_traced,
    blockwise_attention_reference,
    ring_attention_batched,
    ring_attention_sharded,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _qkv(key, s, h, d):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (s, h, d), jnp.float32)
    k = jax.random.normal(k2, (s, h, d), jnp.float32)
    v = jax.random.normal(k3, (s, h, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(mesh, causal, use_flash):
    # use_flash=True exercises the Pallas flash_attention_lse block path
    # (interpret mode on the CPU mesh) including the lax.switch dispatch
    # over full/diagonal/skipped K/V blocks.
    q, k, v = _qkv(jax.random.key(0), s=64, h=4, d=16)
    got = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                 use_flash=use_flash)
    want = blockwise_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_attention_grads_match(mesh, use_flash):
    q, k, v = _qkv(jax.random.key(1), s=32, h=2, d=8)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True,
                                              use_flash=use_flash) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(blockwise_attention_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=3e-5)


def test_ring_attention_jits_once(mesh):
    q, k, v = _qkv(jax.random.key(2), s=64, h=4, d=16)
    f = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh))
    out = f(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype


# -- an axis of one --------------------------------------------------------


def primitives(jaxpr, out=None):
    """Counter of the primitives of a jaxpr and of every nested body but
    a Pallas kernel's own (whose loops and ``pl.when`` are not the
    program's control flow)."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                primitives(sub, out)
    return out


def _batched(mesh, causal, use_flash, kv_repeat):
    """ring_attention_batched under shard_map, the sequence over ``x``."""
    spec = P(None, "x")
    return shard_map(
        functools.partial(ring_attention_batched, axis_name="x",
                          causal=causal, use_flash=use_flash,
                          kv_repeat=kv_repeat),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)


def _ring_of_one(q, k, v, causal, use_flash, kv_repeat):
    """What the ring computed at an axis of one before it was told its
    size: one block, merged by the logaddexp formula into an empty
    float32 accumulator."""
    mb, sq, h, dh = q.shape
    k, v = (jnp.repeat(x, kv_repeat, axis=2) for x in (k, v))
    if use_flash:
        o_b, lse_b = flash_attention_lse(q, k, v, causal=causal)
        o_b = o_b.astype(jnp.float32)
    else:
        mask = ((jnp.arange(sq)[None, :] <= jnp.arange(sq)[:, None])
                if causal else jnp.ones((sq, sq), bool))[None, None]
        o_b, lse_b = _dense_block(q.astype(jnp.float32), k, v, mask)
    lse_acc = jnp.full((mb, h, sq), _NEG, jnp.float32)
    lse_new = jnp.logaddexp(lse_acc, lse_b)
    wa, wb = jnp.exp(lse_acc - lse_new), jnp.exp(lse_b - lse_new)
    o = (jnp.zeros(q.shape, jnp.float32) * jnp.moveaxis(wa, 1, 2)[..., None]
         + o_b * jnp.moveaxis(wb, 1, 2)[..., None])
    return o.astype(q.dtype)


@pytest.mark.parametrize("kv_repeat", [1, 4])
@pytest.mark.parametrize("use_flash", [False, True])
@pytest.mark.parametrize("causal", [True, False])
def test_an_axis_of_one_is_one_direct_block(mesh, causal, use_flash,
                                            kv_repeat):
    """On a mesh of ONE device the call is one block: outputs and the
    gradients w.r.t. q, k, v bit-equal to the ring-of-one's merge, close
    to the reference, and no ``ppermute`` / ``cond`` / ``scan`` in the
    jaxpr, where the mesh of 8 keeps all three (``cond``: the causal
    flash path's switch)."""
    one = make_mesh(1)
    mb, s, h, d = 2, 32, 4, 16
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (mb, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (mb, s, h // kv_repeat, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (mb, s, h // kv_repeat, d), jnp.bfloat16)
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    direct = _batched(one, causal, use_flash, kv_repeat)
    merged = functools.partial(_ring_of_one, causal=causal,
                               use_flash=use_flash, kv_repeat=kv_repeat)

    @functools.partial(jax.jit, static_argnums=0)
    def out_and_grads(f):
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w)  # noqa: E731
        return (f(q, k, v),) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    before = attention_calls_traced()
    jaxpr_one = jax.make_jaxpr(direct)(q, k, v).jaxpr
    after = attention_calls_traced()
    assert (after["direct"] - before["direct"],
            after["ring"] - before["ring"]) == (1, 0)
    jaxpr_eight = jax.make_jaxpr(_batched(mesh, causal, use_flash,
                                          kv_repeat))(q, k, v).jaxpr
    assert (attention_calls_traced()["ring"] - after["ring"],
            attention_calls_traced()["direct"] - after["direct"]) == (1, 0)
    has_one, has_eight = primitives(jaxpr_one), primitives(jaxpr_eight)
    ring_only = {"ppermute", "scan"} | ({"cond"} if causal and use_flash
                                        else set())
    assert not {"ppermute", "scan", "cond"} & set(has_one), has_one
    assert ring_only <= set(has_eight), has_eight

    for name, got, want in zip(("o", "dq", "dk", "dv"),
                               out_and_grads(direct), out_and_grads(merged)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=f"{name} differs from the ring of one")
    expand = lambda x: jnp.repeat(x, kv_repeat, axis=2)  # noqa: E731
    want = jax.vmap(functools.partial(blockwise_attention_reference,
                                      causal=causal))(q, expand(k), expand(v))
    np.testing.assert_allclose(np.asarray(jax.jit(direct)(q, k, v),
                                          np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
