"""Pallas device-side ops: flag signaling kernels + flash attention.

On the CPU test mesh these run through Pallas interpret mode — the exact
same kernel bodies that compile via Mosaic on a real TPU chip (chip_smoke.py and
tests/test_tpu_compile.py exercise the compiled path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_acx_tpu.ops import (
    AVAILABLE, RESERVED, PENDING, COMPLETED,
    pready, pready_many, parrived, parrived_all, produce_and_pready,
    flash_attention, attention_reference,
)


def _table(n=16, state=RESERVED):
    return jnp.full((n,), state, jnp.int32)


class TestFlagKernels:
    def test_pready_sets_one_slot(self):
        flags = pready(_table(), 5)
        assert flags[5] == PENDING
        np.testing.assert_array_equal(
            np.delete(np.asarray(flags), 5), RESERVED)

    def test_pready_traced_index(self):
        # idx may be a traced value (e.g. scan counter) — jit the whole op.
        f = jax.jit(lambda t, i: pready(t, i))
        flags = f(_table(), jnp.int32(3))
        assert flags[3] == PENDING

    def test_pready_many(self):
        flags = pready_many(_table(32), jnp.array([1, 7, 31]))
        assert flags[1] == flags[7] == flags[31] == PENDING
        assert flags[0] == flags[30] == RESERVED

    def test_parrived_polls_without_blocking(self):
        flags = _table()
        assert int(parrived(flags, 4)) == 0          # RESERVED: not arrived
        flags = flags.at[4].set(COMPLETED)
        assert int(parrived(flags, 4)) == 1

    def test_parrived_all(self):
        flags = _table(8, COMPLETED).at[6].set(PENDING)
        assert int(parrived_all(flags, jnp.array([0, 1, 2]))) == 1
        assert int(parrived_all(flags, jnp.array([0, 6]))) == 0

    def test_produce_and_pready_fuses_payload_and_signal(self):
        x = jnp.ones((8, 128), jnp.float32)
        payload, flags = produce_and_pready(
            lambda b: b * 3.0, x, _table(), idx=2)
        np.testing.assert_allclose(np.asarray(payload), 3.0)
        assert flags[2] == PENDING
        assert flags[0] == RESERVED

    def test_state_machine_roundtrip_matches_native_protocol(self):
        # AVAILABLE->RESERVED->PENDING->...->COMPLETED, reference
        # mpi-acx-internal.h:196-203 / include/acx/state.h.
        flags = _table(8, AVAILABLE)
        flags = flags.at[0].set(RESERVED)            # host: slot allocate
        flags = pready(flags, 0)                     # device kernel
        assert flags[0] == PENDING
        flags = flags.at[0].set(COMPLETED)           # proxy: op completed
        assert int(parrived(flags, 0)) == 1


class TestFlashAttention:
    @pytest.mark.parametrize("s,d,causal", [
        (128, 64, True), (256, 64, True), (128, 128, True), (128, 64, False),
    ])
    def test_matches_reference(self, s, d, causal):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (2, s, 4, d), jnp.float32)
        k = jax.random.normal(k2, (2, s, 4, d), jnp.float32)
        v = jax.random.normal(k3, (2, s, 4, d), jnp.float32)
        out = flash_attention(q, k, v, causal=causal)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16_inputs(self):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        q = jax.random.normal(k1, (1, 128, 2, 64), jnp.bfloat16)
        kv = jax.random.normal(k2, (1, 128, 2, 64), jnp.bfloat16)
        out = flash_attention(q, kv, kv)
        ref = attention_reference(q, kv, kv)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    @pytest.mark.parametrize("streaming", [False, True])
    def test_cross_length_kv_attends_all_keys(self, streaming):
        # Non-causal with Sk != Sq: BOTH kernel paths must attend every
        # key (r3 code-review regression: the resident specs were built
        # from q's S and silently dropped keys past it).
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(k1, (1, 128, 2, 32), jnp.float32)
        k = jax.random.normal(k2, (1, 256, 2, 32), jnp.float32)
        v = jax.random.normal(k3, (1, 256, 2, 32), jnp.float32)
        out = flash_attention(q, k, v, causal=False, streaming=streaming,
                              block_q=64, block_k=64)
        ref = attention_reference(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_multiple_q_blocks_causality(self):
        # S spans several q/k blocks; late queries must not see the future.
        q = jnp.ones((1, 512, 1, 64), jnp.float32)
        k = jnp.ones((1, 512, 1, 64), jnp.float32)
        v = jnp.broadcast_to(
            jnp.arange(512, dtype=jnp.float32)[None, :, None, None],
            (1, 512, 1, 64))
        out = flash_attention(q, k, v, block_q=128, block_k=128)
        # With uniform scores, out[t] = mean(v[0..t]) = t/2.
        expect = jnp.arange(512, dtype=jnp.float32) / 2.0
        np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]),
                                   np.asarray(expect), atol=1e-3, rtol=1e-4)


class TestFlashAttentionStreaming:
    """The k-grid streaming kernel (one K/V tile in VMEM, scratch-carried
    online softmax) must match the resident kernel and the dense
    reference, values and grads — it is the long-context path past the
    resident kernel's VMEM ceiling."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(k1, (2, 256, 4, 64), jnp.float32)
        k = jax.random.normal(k2, (2, 256, 4, 64), jnp.float32)
        v = jax.random.normal(k3, (2, 256, 4, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, streaming=True,
                              block_q=64, block_k=64)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_grad_matches_dense(self):
        q = jax.random.normal(jax.random.key(8), (1, 128, 2, 32),
                              jnp.float32)
        k = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
        v = jax.random.normal(jax.random.key(10), q.shape, jnp.float32)
        gs = jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, streaming=True, block_q=64, block_k=64) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda q, k, v: (attention_reference(
            q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gs, gd):
            err = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert err < 1e-5, err

    def test_auto_policy_kicks_in_at_16k(self):
        # streaming=None must select the streaming kernel exactly where
        # the resident kernel's VMEM ceiling is (S >= 16384).
        from mpi_acx_tpu.ops import attention as A
        calls = []
        orig = A._flash

        def spy(qt, kt, vt, causal, bq, bk, streaming=False):
            calls.append(streaming)
            return orig(qt, kt, vt, causal, bq, bk, streaming)

        A._flash = spy
        try:
            x = jnp.zeros((1, 128, 1, 32), jnp.float32)
            A.flash_attention.__wrapped__(x, x, x)          # small: resident
            big = jnp.zeros((1, 16384, 1, 32), jnp.float32)
            A.flash_attention.__wrapped__(big, big, big)    # big: streaming
        finally:
            A._flash = orig
        assert calls == [False, True], calls


class TestFlashAttentionLse:
    """flash_attention_lse: values, the logsumexp output, the two-block
    merge identity (what ring attention builds on), and gradients through
    BOTH outputs."""

    def _qkv(self, key, s, h=2, d=32):
        k1, k2, k3 = jax.random.split(key, 3)
        return (jax.random.normal(k1, (1, s, h, d), jnp.float32),
                jax.random.normal(k2, (1, s, h, d), jnp.float32),
                jax.random.normal(k3, (1, s, h, d), jnp.float32))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference_and_lse(self, causal):
        from mpi_acx_tpu.ops.attention import flash_attention_lse
        q, k, v = self._qkv(jax.random.key(0), 128)
        o, lse = flash_attention_lse(q, k, v, causal=causal)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # lse against a dense computation.
        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
        if causal:
            mask = jnp.tril(jnp.ones((128, 128), bool))
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        want = jax.scipy.special.logsumexp(logits, axis=-1)   # [B,H,S]
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_two_block_merge_identity(self):
        # Attending to K/V halves separately and merging by logaddexp must
        # equal attending to the whole sequence — the ring-attention merge.
        from mpi_acx_tpu.ops.attention import flash_attention_lse
        q, k, v = self._qkv(jax.random.key(1), 128)
        o_full, _ = flash_attention_lse(q, k, v, causal=False)
        o1, l1 = flash_attention_lse(q, k[:, :64], v[:, :64], causal=False)
        o2, l2 = flash_attention_lse(q, k[:, 64:], v[:, 64:], causal=False)
        lse = jnp.logaddexp(l1, l2)
        w1 = jnp.moveaxis(jnp.exp(l1 - lse), 1, 2)[..., None]
        w2 = jnp.moveaxis(jnp.exp(l2 - lse), 1, 2)[..., None]
        merged = o1 * w1 + o2 * w2
        np.testing.assert_allclose(np.asarray(merged), np.asarray(o_full),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_through_both_outputs(self, causal):
        # The lse cotangent feeds dS = P*(dP - D + dLSE): check against
        # jax.grad of the dense formula for a loss that uses o AND lse.
        from mpi_acx_tpu.ops.attention import flash_attention_lse
        q, k, v = self._qkv(jax.random.key(2), 64)
        wl = jax.random.normal(jax.random.key(3), (1, 2, 64), jnp.float32)

        def loss_flash(q, k, v):
            o, lse = flash_attention_lse(q, k, v, causal=causal)
            return (o ** 2).sum() + (wl * lse).sum()

        def loss_dense(q, k, v):
            d = q.shape[-1]
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
            if causal:
                mask = jnp.tril(jnp.ones((64, 64), bool))
                logits = jnp.where(mask[None, None], logits, -jnp.inf)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            p = jnp.exp(logits - lse[..., None])
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            return (o ** 2).sum() + (wl * lse).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            err = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert err < 1e-5, (causal, err)


class TestFlashAttentionGrad:
    """The custom VJP (a Pallas backward kernel; blockwise in plain JAX
    from the streaming lengths on) must match gradients of the dense
    reference to machine precision."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_grad_matches_dense(self, causal):
        from mpi_acx_tpu.ops.attention import (attention_reference,
                                               flash_attention)
        S = 256
        q = jax.random.normal(jax.random.key(1), (1, S, 2, 64), jnp.float32)
        k = jax.random.normal(jax.random.key(2), (1, S, 2, 64), jnp.float32)
        v = jax.random.normal(jax.random.key(3), (1, S, 2, 64), jnp.float32)
        w = jax.random.normal(jax.random.key(4), q.shape, jnp.float32)
        gf = jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, causal=causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(lambda q, k, v: (attention_reference(
            q, k, v, causal=causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            err = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
            assert err < 1e-5, (causal, err)

    # name: (causal, Sq, Sk, dtype, requested block, lse cotangent).
    # Without an lse cotangent the gradient goes through flash_attention
    # (whose fwd rule emits the lse), with one through
    # flash_attention_lse; 384 is a length _fit_blocks shrinks 256 for.
    BWD_CASES = {
        "causal_f32_b128_dlse": (True, 256, 256, "float32", 128, True),
        "causal_f32_b128": (True, 256, 256, "float32", 128, False),
        "causal_f32_b512_dlse": (True, 512, 512, "float32", 512, True),
        "causal_f32_one_block": (True, 128, 128, "float32", 512, False),
        "causal_f32_shrunk_dlse": (True, 384, 384, "float32", 256, True),
        "causal_bf16_b128_dlse": (True, 256, 256, "bfloat16", 128, True),
        "causal_bf16_b512": (True, 512, 512, "bfloat16", 512, False),
        "full_f32_b128_dlse": (False, 256, 256, "float32", 128, True),
        "full_f32_sq_lt_sk_dlse": (False, 128, 384, "float32", 128, True),
        "full_f32_sq_gt_sk": (False, 256, 128, "float32", 128, False),
        "full_bf16_sq_ne_sk_dlse": (False, 256, 128, "bfloat16", 128, True),
    }

    @pytest.mark.parametrize("case", list(BWD_CASES))
    def test_backward_kernel_matches_blockwise_and_dense(self, case):
        """dq, dk, dv of the Pallas backward (what jax.vjp of the two
        public functions runs) against _flash_bwd_blockwise on the same
        residuals and against the dense reference's gradients."""
        from mpi_acx_tpu.ops import attention as A
        causal, sq, sk, dtype, block, with_dlse = self.BWD_CASES[case]
        ks = jax.random.split(jax.random.key(11), 5)
        q = jax.random.normal(ks[0], (1, sq, 2, 64)).astype(dtype)
        k = jax.random.normal(ks[1], (1, sk, 2, 64)).astype(dtype)
        v = jax.random.normal(ks[2], (1, sk, 2, 64)).astype(dtype)
        do = jax.random.normal(ks[3], q.shape).astype(dtype)
        dlse = jax.random.normal(ks[4], (1, 2, sq)) if with_dlse else None

        if with_dlse:
            (o, lse), vjp = jax.vjp(lambda *a: A.flash_attention_lse(
                *a, causal=causal, block_q=block, block_k=block), q, k, v)
            got = vjp((do, dlse))
        else:
            o, vjp = jax.vjp(lambda *a: A.flash_attention(
                *a, causal=causal, block_q=block, block_k=block), q, k, v)
            got = vjp(do)
            lse = A._reference_lse(q, k, v, causal=causal)[1]

        bq = A._fit_blocks(sq, block, block)[0]
        bk = A._fit_blocks(sk, block, block)[1]
        t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
        blockwise = [t(g) for g in A._flash_bwd_blockwise(
            t(q), t(k), t(v), t(o), t(do), causal, bq, bk, lse=lse,
            dlse=dlse)]

        f32 = lambda x: x.astype(jnp.float32)
        _, dense_vjp = jax.vjp(lambda *a: A._reference_lse(
            *a, causal=causal), f32(q), f32(k), f32(v))
        dense = dense_vjp((f32(do), dlse if with_dlse
                           else jnp.zeros((1, 2, sq))))

        tol = 1e-5 if dtype == "float32" else 2e-2
        for name, g, b, d in zip("qkv", got, blockwise, dense):
            assert g.dtype == jnp.dtype(dtype) and g.shape == d.shape
            for ref in (b, d):
                err = float(jnp.abs(f32(g) - f32(ref)).max()
                            / jnp.abs(f32(ref)).max())
                assert err < tol, (case, name, err)

    def test_streaming_lengths_take_the_blockwise_backward(self,
                                                           monkeypatch):
        """The backward rule chooses by shape: from the streaming
        forward's lengths on (S >= 16384) it builds no kernel; below, it
        builds the one Pallas call. Traced only, nothing runs."""
        from mpi_acx_tpu.ops import attention as A

        def traced(s, sk, refuse):
            if refuse:
                monkeypatch.setattr(A, "_flash_bwd_impl", lambda *a: 1 / 0)
            x = lambda n: jax.ShapeDtypeStruct((1, 1, n, 32), jnp.bfloat16)
            row = jax.ShapeDtypeStruct((1, 1, s), jnp.float32)
            return str(jax.make_jaxpr(
                lambda q, k, v, o, do, lse, dlse: A._flash_bwd(
                    q, k, v, o, do, sk == s, 512, 512, lse=lse, dlse=dlse))(
                x(s), x(sk), x(sk), x(s), x(s), row, row))

        assert "pallas_call" in traced(8192, 8192, refuse=False)
        assert "pallas_call" not in traced(16384, 16384, refuse=True)
        assert "pallas_call" not in traced(1024, 16384, refuse=True)

    def test_grad_through_model_loss(self):
        """value_and_grad through a model whose attention is the Pallas
        kernel (the configuration that crashes without the custom VJP)."""
        import dataclasses
        from mpi_acx_tpu.models import init_params, tiny_config
        from mpi_acx_tpu.models.transformer import loss_fn
        cfg = dataclasses.replace(tiny_config(n_layers=2), use_flash=True)
        params = init_params(jax.random.key(0), cfg)
        tokens = jax.random.randint(jax.random.key(1), (2, 128), 0,
                                    cfg.vocab)
        targets = jnp.roll(tokens, -1, axis=-1)
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, tokens, targets))(params)
        assert bool(jnp.isfinite(loss))
        assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))
