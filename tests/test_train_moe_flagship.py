"""MoE router auxiliaries through the FLAGSHIP dp x pp x tp train step.

Round-3 verdict item: the scaled path was CE-only, risking expert
collapse at pp x tp scale. These tests pin the fix from both ends:
(1) the flagship scalar equals the dp+ep trainer's aux-regularized loss
on a pp=tp=1 mesh (same token groups => bit-equal routing, same
normalization), and (2) at pp=2 the aux actually does its job — training
with it keeps routing measurably more balanced than training without.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_acx_tpu.models import moe_transformer as mtf
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.parallel.mesh import mesh_from_devices
from mpi_acx_tpu.train import make_loss_and_grads, make_train_step


def _unstage(staged):
    """Invert tfm.stage_slice: [pp, per, ...] layer leaves -> [L, ...]."""
    out = dict(staged)
    out["layers"] = jax.tree.map(
        lambda p: p.reshape((-1,) + p.shape[2:]), staged["layers"])
    return out


@pytest.mark.parametrize("remat", [False, True])
def test_flagship_loss_matches_dp_ep_trainer_at_pp1(remat):
    """On a dp=2, pp=1, tp=1 mesh with n_micro=1 the flagship loss must
    equal make_moe_transformer_train_step's loss on the same data: the
    per-rank token groups coincide (B/dp x S tokens per router call), so
    routing is bit-equal, and both normalize aux per (layer, group).
    With remat on (the attention half at ``tp = 1`` is one direct block,
    recomputed in the backward) the loss is the same number and every
    gradient leaf the plain step's."""
    aw, zw = 1e-2, 1e-3
    dp = 2
    mesh = mesh_from_devices({"dp": dp, "pp": 1, "tp": 1},
                             jax.devices()[:dp])
    cfg = mtf.tiny_moe_config(vocab=67, d_model=32, n_heads=2, n_layers=2,
                              d_ff=64, n_experts=8, top_k=2,
                              capacity_factor=2.0, max_seq=16)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)   # exactness test
    params = mtf.init_params(jax.random.key(0), cfg)
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=-1)

    # dp+ep trainer (lr=0 would still update; just read the loss).
    ep_mesh = mesh_from_devices({"dp": dp}, jax.devices()[:dp])
    ep_step = mtf.make_moe_transformer_train_step(
        cfg, ep_mesh, axis="dp", lr=0.0, aux_weight=aw, z_weight=zw)
    ep_loss, _ = ep_step(params, tokens, targets)

    grad_fn, n_st = make_loss_and_grads(cfg, mesh, n_micro=1, remat=remat,
                                        aux_weight=aw, z_weight=zw)
    staged = tfm.stage_slice(params, n_st)
    flag_loss, grads = grad_fn(staged, tokens[None], targets[None])
    np.testing.assert_allclose(float(flag_loss), float(ep_loss),
                               rtol=1e-6)
    if remat:
        plain_fn, _ = make_loss_and_grads(cfg, mesh, n_micro=1,
                                          aux_weight=aw, z_weight=zw)
        _, plain = plain_fn(staged, tokens[None], targets[None])
        for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6, rtol=1e-5)


def test_flagship_aux_keeps_routing_balanced_at_pp2():
    """Train the flagship composition at dp=2, pp=2, tp=2 twice from the
    same init — with the router auxiliaries on (default weights, scaled
    up to bite at this tiny scale) and with them off — and measure the
    load-balance statistic of the trained model: the regularized run
    must end strictly more balanced. This is the 'trains with balanced
    routing at pp=2' guarantee the CE-only path could not make."""
    mesh = mesh_from_devices({"dp": 2, "pp": 2, "tp": 2})
    cfg = mtf.tiny_moe_config(vocab=32, d_model=32, n_heads=2, n_layers=4,
                              d_ff=64, n_experts=8, top_k=1,
                              capacity_factor=4.0, max_seq=16)
    params = mtf.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 4, 16), 0, 32)

    def train(aux_weight):
        step, n_st = make_train_step(cfg, mesh, n_micro=2, lr=0.5,
                                     aux_weight=aux_weight, z_weight=0.0)
        p = tfm.stage_slice(params, n_st)
        for _ in range(8):
            loss, p = step(p, tokens, tokens)
        return _unstage(p)

    def balance(p):
        # Layer-mean Switch balance statistic of the trained router on
        # the training tokens; 1.0 = perfectly uniform.
        _, aux = mtf.forward(p, cfg, tokens.reshape(-1, 16))
        return float(aux["load_balance"])

    bal_on = balance(train(aux_weight=0.5))
    bal_off = balance(train(aux_weight=0.0))
    assert bal_on < bal_off, (bal_on, bal_off)
    # And the regularized run is genuinely near-uniform, not just less
    # collapsed: the statistic's minimum is 1.0.
    assert bal_on < 1.5, bal_on


def test_flagship_aux_interleaved_matches_gpipe_schedule():
    """The aux accumulator is schedule-invariant: the interleaved
    pipeline (n_virtual=2) must produce the same loss as the plain GPipe
    schedule — both sum each (layer, microbatch) router call exactly
    once, fill/drain slots masked out."""
    mesh = mesh_from_devices({"dp": 2, "pp": 2, "tp": 2})
    cfg = mtf.tiny_moe_config(vocab=32, d_model=32, n_heads=2, n_layers=4,
                              d_ff=64, n_experts=8, top_k=1,
                              capacity_factor=2.0, max_seq=16)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)   # exactness test
    params = mtf.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 4, 16), 0, 32)
    targets = jnp.roll(tokens, -1, axis=-1)

    g1, n_st = make_loss_and_grads(cfg, mesh, n_micro=2)
    l1, _ = g1(tfm.stage_slice(params, n_st), tokens, targets)
    g2, _ = make_loss_and_grads(cfg, mesh, n_micro=2, n_virtual=2)
    l2, _ = g2(tfm.stage_slice_interleaved(params, n_st, 2), tokens,
               targets)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
