"""Llama family through the dp x pp x tp/sp distributed train step
(the reference's Llama-3-8B pipeline config): the parallel composition must compute EXACTLY
the same step as the single-device Llama implementation — RoPE with
global positions on sequence shards, GQA broadcast before ring attention,
SwiGLU tensor-parallel reduction, and the family's untied unembed head
all have to be right for parameters to match."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_acx_tpu.models import llama as lm
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.parallel.mesh import mesh_from_devices
from mpi_acx_tpu.train import make_train_step


@pytest.fixture(scope="module")
def setup():
    cfg = lm.tiny_llama(vocab=89, d_model=64, n_heads=4, n_kv_heads=2,
                        n_layers=4, d_ff=96, max_seq=32)
    mesh = mesh_from_devices({"dp": 2, "pp": 2, "tp": 2})
    params = lm.init_params(jax.random.key(0), cfg)
    M, mb, S = 3, 4, 16
    tokens = jax.random.randint(jax.random.key(1), (M, mb, S), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=-1)
    return cfg, mesh, params, tokens, targets


def _sequential_step(cfg, params, tokens, targets, lr):
    M, mb, S = tokens.shape
    flat_t = tokens.reshape(M * mb, S)
    flat_y = targets.reshape(M * mb, S)
    loss, grads = jax.value_and_grad(lm.loss_fn)(params, cfg, flat_t, flat_y)
    return loss, jax.tree.map(lambda p, g: p - lr * g, params, grads)


@pytest.mark.parametrize("shape,remat", [
    ({"dp": 2, "pp": 2, "tp": 2}, False),
    ({"dp": 1, "pp": 1, "tp": 1}, False),
    ({"dp": 1, "pp": 1, "tp": 1}, True)],
    ids=["dp2_pp2_tp2", "one_device", "one_device_remat"])
def test_llama_distributed_step_matches_sequential(setup, shape, remat):
    """On the 2x2x2 mesh (the ring, grouped-query K/V rotating
    un-expanded) and on ONE device with remat off and on (``tp = 1``: one
    direct block, K/V expanded as the ring's blocks expand them)."""
    cfg, _, params, tokens, targets = setup
    mesh = mesh_from_devices(shape)
    lr = 0.1
    step, n_stages = make_train_step(cfg, mesh, n_micro=tokens.shape[0],
                                     lr=lr, remat=remat)
    staged = tfm.stage_slice(params, n_stages)

    dist_loss, dist_new = step(staged, tokens, targets)
    seq_loss, seq_new = _sequential_step(cfg, params, tokens, targets, lr)

    np.testing.assert_allclose(float(dist_loss), float(seq_loss), rtol=2e-4)

    seq_staged = tfm.stage_slice(seq_new, n_stages)
    flat_d = jax.tree.leaves_with_path(jax.tree.map(np.asarray, dist_new))
    flat_s = dict(
        (jax.tree_util.keystr(k), v)
        for k, v in jax.tree.leaves_with_path(
            jax.tree.map(np.asarray, seq_staged)))
    for key, got in flat_d:
        want = flat_s[jax.tree_util.keystr(key)]
        np.testing.assert_allclose(
            got, want, atol=5e-4, rtol=5e-3,
            err_msg=f"param {jax.tree_util.keystr(key)} diverged")


def test_llama_distributed_training_converges(setup):
    cfg, mesh, params, tokens, targets = setup
    step, n_stages = make_train_step(cfg, mesh, n_micro=tokens.shape[0],
                                     lr=0.3)
    staged = tfm.stage_slice(params, n_stages)
    l0, staged = step(staged, tokens, targets)
    for _ in range(6):
        l1, staged = step(staged, tokens, targets)
    assert float(l1) < float(l0)
