"""Disaggregated prefill/decode serving (models/disagg.py).

CPU parity: a disagg serve — prefill layer loop, per-layer Pready over
a real loopback partitioned channel, decode-side Parrived splice — is
bit-equal to the monolithic ``serve_greedy(..., kv_int8=True)``, for
both prefill-side cache variants (quantize-at-compute and
quantize-at-wire) and for the ship-after-full-prefill baseline. Plus
the failure path: a handoff that dies mid-round requeues the request
(uncharged when peer-loss shaped) and the retry still serves bit-equal
output."""

import numpy as np
import pytest

import jax

from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.models.serving import make_server_fns, serve_greedy


@pytest.fixture(scope="module")
def rt():
    from mpi_acx_tpu import runtime
    r = runtime.Runtime()
    yield r
    r.finalize()


@pytest.fixture(scope="module")
def setup():
    cfg = tfm.tiny_config()
    params = tfm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 11, 3, 17, 8)]
    n_new = [6, 3, 9, 4, 5]
    fns = make_server_fns(params, cfg, tfm, chunk=1, kv_int8=True)
    mono = serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                        max_len=64, kv_int8=True, server_fns=fns)
    return cfg, params, prompts, n_new, fns, mono


def test_pack_unpack_roundtrip():
    from mpi_acx_tpu.parallel.kv_ship import (layer_part_bytes,
                                              pack_layer, unpack_layer)
    rng = np.random.default_rng(3)
    bucket, H, D = 16, 4, 32
    kq = rng.integers(-127, 128, (H, D, bucket)).astype(np.int8)
    vq = rng.integers(-127, 128, (H, D, bucket)).astype(np.int8)
    ks = rng.random((H, 1, bucket)).astype(np.float32)
    vs = rng.random((H, 1, bucket)).astype(np.float32)
    row = np.zeros(layer_part_bytes(bucket, H, D), np.uint8)
    pack_layer(row, kq, ks, vq, vs)
    okq, oks, ovq, ovs = unpack_layer(row, bucket, H, D)
    np.testing.assert_array_equal(okq, kq)
    np.testing.assert_array_equal(ovq, vq)
    np.testing.assert_array_equal(oks, ks)
    np.testing.assert_array_equal(ovs, vs)


def test_pack_rejects_unquantized():
    """The EQuARX rule at the wire: bf16 K/V must never reach pack —
    the shipper quantizes first, always."""
    from mpi_acx_tpu.parallel.kv_ship import layer_part_bytes, pack_layer
    row = np.zeros(layer_part_bytes(8, 2, 4), np.uint8)
    k16 = np.zeros((2, 4, 8), np.float16)
    s = np.zeros((2, 1, 8), np.float32)
    with pytest.raises(AssertionError):
        pack_layer(row, k16, s, k16, s)


def test_layerwise_prefill_bit_equal(setup):
    """The hoisted per-layer loop reproduces the monolithic scan
    prefill bit for bit: logits, int8 codes, and f32 scales."""
    from mpi_acx_tpu.models.disagg import make_layerwise_prefill_fns
    cfg, params, _, _, _, _ = setup
    S, bucket = 11, 16
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :S] = np.arange(S) % cfg.vocab
    tokens = jax.numpy.asarray(tokens)
    # Weights as arguments, as both serve paths compile them
    # (backend.jit_bound): a program with the weights baked in as
    # constants folds them differently and agrees only to rounding.
    logits_m, cache_m = jax.jit(
        lambda p, t, li: tfm.prefill(p, cfg, t, bucket, kv_int8=True,
                                     last_index=li))(params, tokens, S - 1)
    embed_fn, layer_fn, head_fn, quant_fn = make_layerwise_prefill_fns(
        params, cfg)
    x = embed_fn(tokens)
    kq, ks, vq, vs = [], [], [], []
    for layer in range(cfg.n_layers):
        x, k, v = layer_fn(x, layer)
        a, b, c, d = quant_fn(k, v)
        kq.append(np.asarray(a))
        ks.append(np.asarray(b))
        vq.append(np.asarray(c))
        vs.append(np.asarray(d))
    np.testing.assert_array_equal(np.asarray(head_fn(x, S - 1)),
                                  np.asarray(logits_m))
    np.testing.assert_array_equal(np.stack(kq),
                                  np.asarray(cache_m["k"])[..., :bucket])
    np.testing.assert_array_equal(np.stack(ks),
                                  np.asarray(cache_m["ks"])[..., :bucket])
    np.testing.assert_array_equal(np.stack(vq),
                                  np.asarray(cache_m["v"])[..., :bucket])
    np.testing.assert_array_equal(np.stack(vs),
                                  np.asarray(cache_m["vs"])[..., :bucket])


def _assert_parity(mono, dis):
    assert len(mono) == len(dis)
    for i, (a, b) in enumerate(zip(mono, dis)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


def test_disagg_parity_bf16_prefill(rt, setup):
    """Quantize-at-wire variant (prefill stages bf16 K/V, codes are
    produced at pack time): bit-equal to the monolithic int8 serve."""
    from mpi_acx_tpu.models.disagg import (DisaggMetrics,
                                           serve_disagg_greedy)
    cfg, params, prompts, n_new, fns, mono = setup
    dis = serve_disagg_greedy(params, cfg, prompts, n_new, n_slots=2,
                              max_len=64, server_fns=fns, rt=rt,
                              prefill_kv_int8=False)
    _assert_parity(mono, dis)
    assert isinstance(dis.metrics, DisaggMetrics)
    assert len(dis.metrics.handoffs) == len(prompts)
    assert all(h.overlap for h in dis.metrics.handoffs)
    assert all(h.layers == cfg.n_layers for h in dis.metrics.handoffs)


def test_disagg_parity_int8_prefill(rt, setup):
    """Quantize-at-compute variant (prefill holds the int8 cache form):
    identical wire bytes, bit-equal output."""
    from mpi_acx_tpu.models.disagg import serve_disagg_greedy
    cfg, params, prompts, n_new, fns, mono = setup
    dis = serve_disagg_greedy(params, cfg, prompts, n_new, n_slots=2,
                              max_len=64, server_fns=fns, rt=rt,
                              prefill_kv_int8=True)
    _assert_parity(mono, dis)


def test_disagg_ship_after_prefill_parity(rt, setup):
    """overlap=False (the bench baseline: publish only after the full
    prompt pass) changes timing, never tokens."""
    from mpi_acx_tpu.models.disagg import serve_disagg_greedy
    cfg, params, prompts, n_new, fns, mono = setup
    dis = serve_disagg_greedy(params, cfg, prompts, n_new, n_slots=2,
                              max_len=64, server_fns=fns, rt=rt,
                              overlap=False)
    _assert_parity(mono, dis)
    assert not any(h.overlap for h in dis.metrics.handoffs)


def test_disagg_midhandoff_kill_requeues_uncharged(rt, setup):
    """A handoff that dies peer-loss shaped after Pready of an early
    layer: the request requeues WITHOUT charging its retry budget
    (infrastructure fault, serving.py's rule), the channel round is
    completed so the persistent channel stays restartable, and the
    retry serves bit-equal output."""
    from mpi_acx_tpu.models.disagg import serve_disagg_greedy
    from mpi_acx_tpu.runtime import ERR_PEER_DEAD, AcxPeerDeadError
    cfg, params, prompts, n_new, fns, mono = setup
    fired = []

    def ship_fault(rid, layer):
        if rid == 1 and layer == 2 and not fired:
            fired.append((rid, layer))
            raise AcxPeerDeadError("tpu-acx: peer dead (injected)",
                                   ERR_PEER_DEAD, 0, 0)

    dis = serve_disagg_greedy(params, cfg, prompts, n_new, n_slots=2,
                              max_len=64, server_fns=fns, rt=rt,
                              ship_fault=ship_fault,
                              max_request_retries=0)
    assert fired == [(1, 2)]
    _assert_parity(mono, dis)
    assert dis.metrics.peer_requeues >= 1
    assert dis.metrics.requeues >= 1
    assert dis.metrics.per_request[1].retries == 0  # uncharged


def test_fleet_roles_parsing(monkeypatch):
    from mpi_acx_tpu.models.disagg import fleet_roles
    monkeypatch.delenv("ACX_ROLE", raising=False)
    assert fleet_roles(3) == ["prefill", "decode", "decode"]
    monkeypatch.setenv("ACX_ROLE", "prefill,decode,decode")
    assert fleet_roles(3) == ["prefill", "decode", "decode"]
    monkeypatch.setenv("ACX_ROLE", "decode")
    assert fleet_roles(2) == ["prefill", "decode"]
    monkeypatch.setenv("ACX_ROLE", "prefill,prefill")
    with pytest.raises(ValueError):
        fleet_roles(2)
    monkeypatch.setenv("ACX_ROLE", "prefill,decode")
    with pytest.raises(ValueError):
        fleet_roles(3)
    monkeypatch.setenv("ACX_ROLE", "bogus")
    with pytest.raises(ValueError):
        fleet_roles(2)
