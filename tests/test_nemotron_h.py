"""Nemotron-H (models/nemotron_h.py) against the plain reference
(benchmarks/reference/nemotron_h.py) on seeded weights, by LOGITS, at a
tiny size on the CPU: the plain forward; prefill then paged decode
through the functions ``serve_paged_greedy`` runs; a suffix prefill
behind a restored snapshot; the Mamba-2 state at page ends and behind a
right-padded bucket; the serve loop and its new counters; the four
chips' shares of an expert layer adding up through the linear
up-projection; ``sorted_expert_ffn(w3=None)`` beside the SwiGLU path it
leaves alone; and ``ops/ssd.py``'s two Pallas calls (interpret mode
here) against the token-by-token recurrence. What Jamba's tests already
say of the shared plane (tests/test_jamba.py) is said here of layers
that are a mixer OR a feed-forward part alone and of a state of 3-D
heads.

Tolerances, each beside its reason: in float32 the program and the
reference compute the same sums in another order (a chunked scan of
matrix products against a token-by-token ``lax.scan``, sorted grouped
matmuls against one expert after the other, a different attention
formulation on a hit), which reads 1e-6..2e-5 on logits of size ~4:
``ATOL`` = 3e-4 leaves a decade of room, and the controls read
1e-2..5 (two decades above it). In bfloat16 weights and activations are
rounded to 8 bits of mantissa over 11 layers, and a router's near-tie
(top 3 of 8, weights times 5.0) then picks another expert for a token in
a layer: the logits read 0.16 relative RMS at this width of 32, held to
0.3 (a layer left out reads 1 and more).
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import control_nemotron, weights_nemotron  # noqa: E402
from benchmarks.entries import serve_paged_greedy_nemotron as entry  # noqa: E402
from benchmarks.reference import nemotron_h as ref  # noqa: E402
from mpi_acx_tpu.models import kvpage, moe, nemotron_h, serving  # noqa: E402
from mpi_acx_tpu.ops import ssd  # noqa: E402

ATOL = 3e-4
PT, MAX_LEN = 16, 128

# The tiny preset as a configuration FILE's keys (what the benchmark's
# entry and reference read): the published period's order MEMEMEM*EME,
# 4 Mamba-2 heads of 8 in 2 groups with a state of 16, chunks of 8, 4
# query heads on 2 K/V heads, 8 experts top 3 in a latent of 16 beside a
# shared expert, a snapshot every second page. ``init_scale`` ~
# 1/sqrt(d): the layers, not the embedding, decide the logits.
C = dict(vocab_size=96, hidden_size=32, num_hidden_layers=11,
         hybrid_override_pattern="MEMEMEM*EME", num_attention_heads=4,
         num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
         mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
         chunk_size=8, n_routed_experts=8, num_experts_per_tok=3,
         moe_latent_size=16, moe_intermediate_size=24,
         moe_shared_expert_intermediate_size=40, routed_scaling_factor=5,
         norm_topk_prob=True, n_group=1, topk_group=1, time_step_min=0.001,
         time_step_max=0.1, time_step_floor=1e-4, layer_norm_epsilon=1e-5,
         max_position_embeddings=256, init_scale=0.18, selection_bias_seed=44,
         serve={"snapshot_every": 2, "moe_block": 16})
PLAN, HP = weights_nemotron.plan(C), ref.hyper(C)
CFG = entry.program_config(C, "float32")
H, P, N, G = 4, 8, 16, 2


@pytest.fixture(scope="module")
def tree():
    return weights_nemotron.make_nemotron(C, 7, jnp.float32)


def _seq(n, seed):
    return np.random.default_rng(seed).integers(
        0, C["vocab_size"], n).astype(np.int32)


def _ref_logits(tree, seq, first, rows, **kw):
    return np.asarray(ref.logits_from(
        tree, jnp.asarray(seq), first, jnp.zeros((rows,), jnp.int8),
        plan=PLAN, hp=HP, **kw))


# -- the program's config and layout ------------------------------------------

def test_tiny_preset_and_the_file_mapping_agree():
    assert CFG == nemotron_h.tiny_nemotron(dtype=jnp.dtype("float32"))
    mine = nemotron_h.init_params(jax.random.key(0), CFG)
    made = weights_nemotron.make_nemotron(C, 1, jnp.float32)
    assert (jax.tree.map(lambda a: a.shape, mine)
            == jax.tree.map(lambda a: a.shape, made))
    assert weights_nemotron.n_params(C) == sum(
        a.size for a in jax.tree.leaves(mine))
    cast = nemotron_h.cast_params(mine)
    assert cast["seg0"][0]["A_log"].dtype == jnp.float32
    assert cast["seg0"][1]["gate"].dtype == jnp.float32
    assert cast["seg0"][0]["w_in"].dtype == jnp.bfloat16
    # the recurrence's own draws: a in [-16, -1], softplus(dt_bias) in
    # [time_step_floor, time_step_max]
    m = mine["seg0"][0]
    assert (np.exp(m["A_log"]) >= 1).all() and (np.exp(m["A_log"]) <= 16).all()
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert (dt >= 1e-4 * 0.999).all() and (dt <= 0.1 * 1.001).all()


def test_layers_that_are_one_mixer_compress_into_whole_periods():
    segs = nemotron_h.segments(CFG)
    M, A, E = (nemotron_h._KINDS[ch] for ch in "M*E")
    assert [(s.period, s.repeats) for s in segs] == [
        ((M, E), 3), ((M,), 1), ((A,), 1), ((E,), 1), ((M,), 1), ((E,), 1)]
    assert (M.ffn, M.cache) == ("none", "state")
    assert (E.operator, E.cache, E.ffn) == ("none", "none", "moe")
    assert [(k, len(p), r) for k, p, r in weights_nemotron.stretches(C)] == [
        (s.key, len(s.period), s.repeats) for s in segs]
    # the published stack: 88 layers, 40 M, 40 E, 8 *
    pub = nemotron_h.nemotron3_super_120b()
    assert (pub.n_layers, pub.pattern.count("M"), pub.pattern.count("E"),
            pub.pattern.count("*")) == (88, 40, 40, 8)
    assert sum(len(s.period) * s.repeats
               for s in nemotron_h.segments(pub)) == 88


def test_the_spec_and_int8_pages_by_name():
    spec = kvpage.paged_spec(nemotron_h, CFG)
    assert (spec.n_page_layers, spec.n_state_layers) == (1, 5)
    assert spec.state["ssm"].shape == (H, P, N)
    assert spec.state["ssm"].dtype == jnp.float32
    assert spec.state["conv"].shape == (3 * (H * P + 2 * G * N),)
    assert spec.experts_held == (0, 8) and spec.moe_row_dim == 16
    assert spec.built("operator") == "attention+mamba2"
    assert spec.built("ffn").startswith("moe:_shared_ffn+latent:")
    pub = kvpage.paged_spec(nemotron_h, nemotron_h.nemotron3_super_120b())
    # 4.19 MB + 61 KB a slot a layer at the published widths
    assert pub.state_bytes_slot // pub.n_state_layers == 4194304 + 61440
    with pytest.raises(NotImplementedError, match="kv_int8"):
        kvpage.PagedKV(CFG, nemotron_h, 2, MAX_LEN, PT, 8, kv_int8=True)


# -- ops/ssd.py: the two Pallas calls (interpret mode) -------------------------

def _scan_args(S, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (S, H))),
            jax.random.normal(k[2], (S, G, N)),
            jax.random.normal(k[3], (S, G, N)),
            -jnp.exp(jax.random.uniform(k[4], (H,), maxval=2.0)),
            jax.random.normal(k[5], (H, P, N)))


@pytest.mark.parametrize("S,chunk,snapshot", [(32, 8, 16), (20, 8, 8),
                                              (5, 8, None), (16, 16, 16)])
def test_ssd_scan_kernel_is_the_plain_recurrence(S, chunk, snapshot):
    """The chunked form (matrix products, the state carried between
    chunks) against the recurrence token by token: values, snapshots at
    page ends, end state; a sequence that is no whole number of chunks
    is padded inside."""
    args = _scan_args(S)
    want = ssd.ssd_scan_ref(*args, snapshot=snapshot)
    got = ssd.ssd_scan(*args, snapshot=snapshot, chunk=chunk)
    assert [g.shape for g in got] == [w.shape for w in want]
    assert got[1].shape[0] == (S // snapshot if snapshot else 0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-5)


def test_padding_with_dt_zero_leaves_the_state_of_the_last_real_token():
    x, dt, b, c, a, h0 = _scan_args(24, seed=1)
    real = 13
    dt = dt.at[real:].set(0.0)
    for scan in (ssd.ssd_scan_ref,
                 lambda *t, **kw: ssd.ssd_scan(*t, chunk=8, **kw)):
        _, snaps, end = scan(x, dt, b, c, a, h0, snapshot=8)
        _, _, want = ssd.ssd_scan_ref(x[:real], dt[:real], b[:real],
                                      c[:real], a, h0)
        np.testing.assert_allclose(end, want, atol=2e-5)
        # the snapshot behind the last real token is that state too
        np.testing.assert_allclose(snaps[1], want, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
def test_ssd_update_kernel_is_the_plain_update_in_place(layer):
    k = jax.random.split(jax.random.key(3), 5)
    L, B = 3, 5
    h = jax.random.normal(k[0], (L, B, H, P, N))
    x, dt, b, c, a, _ = _scan_args(B, seed=2)
    y0, h0 = ssd.ssd_update_ref(h, layer, dt, x, b, c, a)
    y1, h1 = ssd.ssd_update(jnp.copy(h), layer, dt, x, b, c, a)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(h1, h0, atol=2e-6)
    others = [l for l in range(L) if l != layer]
    assert (np.asarray(h1)[others] == np.asarray(h)[others]).all()


_MASKS = {"all_live": [1, 1, 1, 1, 1], "none_live": [0, 0, 0, 0, 0],
          "every_other": [1, 0, 1, 0, 1], "live_last": [0, 0, 0, 1, 1],
          "one_live": [0, 0, 1, 0, 0]}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_ssd_update_visits_the_live_slots_alone(mask, layer):
    """Told which slots can still deliver (``live``), the update gives
    a live slot the ``y`` and the state of the call that knows no mask,
    bit for bit; a dead slot's rows of the state stay as they were, not
    read and not written (NaN in a dead slot's state and inputs reaches
    nothing), also where NO slot lives, and its ``y`` is zeros. The
    plain twin says the same."""
    k = jax.random.split(jax.random.key(5), 5)
    L, B = 3, 5
    live = np.asarray(_MASKS[mask], bool)
    dead = ~live
    h = jax.random.normal(k[0], (L, B, H, P, N))
    x, dt, b, c, a, _ = _scan_args(B, seed=4)
    y0, h0 = ssd.ssd_update(jnp.copy(h), layer, dt, x, b, c, a)
    # what a dead slot holds is nobody's business
    h = h.at[layer, dead].set(jnp.nan)
    x = x.at[dead].set(jnp.nan)
    for update in (ssd.ssd_update, ssd.ssd_update_ref):
        y1, h1 = update(jnp.copy(h), layer, dt, x, b, c, a,
                        live=jnp.asarray(live))
        y1, h1 = np.asarray(y1), np.asarray(h1)
        assert np.isfinite(y1).all() and (y1[dead] == 0).all()
        assert (h1[layer, dead].view(np.uint32)
                == np.asarray(h)[layer, dead].view(np.uint32)).all()
        if update is ssd.ssd_update:
            assert (y1[live] == np.asarray(y0)[live]).all()
            assert (h1[layer, live] == np.asarray(h0)[layer, live]).all()
        else:
            np.testing.assert_allclose(y1[live], np.asarray(y0)[live],
                                       atol=2e-5)
            np.testing.assert_allclose(h1[layer, live],
                                       np.asarray(h0)[layer, live], atol=2e-6)
        others = [l for l in range(L) if l != layer]
        assert (h1[others] == np.asarray(h)[others]).all()


def test_the_scan_is_the_update_step_by_step():
    """Prefill's scan and decode's update are one recurrence: the state
    after S tokens of the scan is S updates' state, and the ``y`` rows
    are the updates' ``y``."""
    S = 12
    x, dt, b, c, a, h0 = _scan_args(S, seed=4)
    y, _, end = ssd.ssd_scan(x, dt, b, c, a, h0, chunk=8)
    h = h0[None, None]                                  # [L=1, B=1, ...]
    for t in range(S):
        yt, h = ssd.ssd_update(h, 0, dt[t][None], x[t][None], b[t][None],
                               c[t][None], a)
        np.testing.assert_allclose(yt[0], y[t], atol=3e-5)
    np.testing.assert_allclose(h[0, 0], end, atol=2e-5)


# -- the expert layer ----------------------------------------------------------

def _old_sorted_expert_ffn(x, w1, w3, w2, idx, p):
    """``moe.sorted_expert_ffn`` as the parent commit had it (all
    experts held, no layer, no live)."""
    T, d = x.shape
    k, n = idx.shape[1], w1.shape[0]
    key = idx.reshape(-1)
    held = (key >= 0) & (key < n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    xs = x[order // k]
    h = moe.ragged_dot_matmul(xs, w1, sizes)
    g = moe.ragged_dot_matmul(xs, w3, sizes)
    y = moe.ragged_dot_matmul((jax.nn.silu(h) * g).astype(x.dtype), w2, sizes)
    w = jnp.where(held, p.reshape(-1), 0.0)[order]
    y = jnp.where(held[order][:, None], y * w[:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    return y[back].reshape(T, k, d).sum(axis=1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_expert_of_two_matrices_leaves_the_swiglu_path_bit_equal(dtype):
    """``w3=None`` is a branch in Python: what LFM2 and GigaChat trace
    (three matrices) is bit for bit what it was; two matrices and a
    given activation are the dense sum to numerics."""
    k = jax.random.split(jax.random.key(5), 6)
    T, d, f, n, top = 10, 16, 24, 6, 3
    x = jax.random.normal(k[0], (T, d)).astype(dtype)
    w1, w3 = (jax.random.normal(kk, (n, d, f)).astype(dtype) * 0.3
              for kk in k[1:3])
    w2 = jax.random.normal(k[3], (n, f, d)).astype(dtype) * 0.3
    idx = jnp.argsort(jax.random.normal(k[4], (T, n)), axis=-1)[:, :top]
    p = jax.random.uniform(k[5], (T, top))
    new = moe.sorted_expert_ffn(x, w1, w3, w2, idx, p,
                                grouped_matmul=moe.ragged_dot_matmul)
    old = _old_sorted_expert_ffn(x, w1, w3, w2, idx, p)
    assert (np.asarray(new) == np.asarray(old)).all()
    if dtype == "float32":
        two = moe.sorted_expert_ffn(x, w1, None, w2, idx, p,
                                    act=nemotron_h._relu2)
        want = sum(
            jnp.where((idx == e).any(-1, keepdims=True),
                      (p * (idx == e)).sum(-1, keepdims=True)
                      * (nemotron_h._relu2(x @ w1[e]) @ w2[e]), 0.0)
            for e in range(n))
        np.testing.assert_allclose(two, want, atol=1e-4)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_layer(tree):
    """A chip of the deployment holds a quarter of the experts: the four
    shares' routed parts, summed IN THE LATENT (what an exchange would
    carry), through the one linear up-projection, plus the shared expert
    ONCE, are the uncut layer; program and reference alike."""
    lp = jax.tree.map(lambda a: a[1], tree["seg0"][1])      # an E layer
    x = jax.random.normal(jax.random.key(6), (1, 12, 32))
    whole, idx, _ = nemotron_h._moe_ffn(CFG, lp, x)
    u = nemotron_h.rmsnorm(x, lp["norm1"], CFG.norm_eps).reshape(-1, 32)
    parts = []
    for first in range(0, 8, 2):
        import dataclasses
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=2)
        share = dict(lp, w1=lp["w1"][first:first + 2],
                     w2=lp["w2"][first:first + 2])
        r, idx_s, _ = nemotron_h._routed_latent(cfg, share, u)
        assert (np.asarray(idx_s) == np.asarray(idx)).all()  # one router
        parts.append(r)
    summed = (sum(parts) @ lp["w_up"]
              + nemotron_h._shared_ffn(CFG, lp, u)).reshape(x.shape)
    np.testing.assert_allclose(x + summed, whole, atol=2e-5)
    # the reference's pieces, one expert at a time, are its whole
    hp = dict(HP)
    ru = jnp.asarray(u)
    one = sum(ref.routed_latent(ru, tree["seg0"][1], 1, hp, only=e)
              for e in range(8))
    np.testing.assert_allclose(
        one, ref.routed_latent(ru, tree["seg0"][1], 1, hp), atol=2e-5)
    np.testing.assert_allclose(
        ref._experts(ru, tree["seg0"][1], 1, hp).reshape(x.shape),
        summed, atol=5e-5)


# -- forward -------------------------------------------------------------------

@pytest.mark.parametrize("dtype,kernel", [
    ("float32", False), ("float32", True), ("bfloat16", False)])
def test_forward_against_the_reference(tree, dtype, kernel):
    import dataclasses
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype), ssm_kernel=kernel)
    seq = _seq(40, 0)
    params = tree if dtype == "float32" else nemotron_h.cast_params(tree)
    got = np.asarray(nemotron_h.forward(params, cfg, jnp.asarray(seq)[None]))[0]
    want = _ref_logits(tree, seq, 0, 40)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=ATOL)
    else:
        rel = np.sqrt(np.square(got - want).mean() / np.square(want).mean())
        assert rel < 0.3, rel


def test_what_the_reference_can_leave_out_matters(tree):
    seq = _seq(24, 1)
    want = _ref_logits(tree, seq, 0, 24)
    for parts in (("routed",), ("shared",)):
        off = _ref_logits(tree, seq, 0, 24, parts=parts)
        assert np.abs(off - want).max() > 100 * ATOL


@pytest.mark.parametrize("control", ["bf16_state", "no_shared_expert",
                                     "held_experts_left_out", "plain_topk"])
def test_a_broken_program_does_not_pass(tree, control):
    """The controls the benchmark's limits are read against, underneath
    the plain forward: a part of the mathematics left out moves the
    logits by decades more than the tolerance; a state rounded to
    bfloat16 after every token (most heads forget within tens of tokens,
    so little adds up over 40) by a hundred times what separates the
    sound program from the reference."""
    import dataclasses
    seq = _seq(40, 2)
    want = _ref_logits(tree, seq, 0, 40)
    sound = np.abs(np.asarray(nemotron_h.forward(
        tree, CFG, jnp.asarray(seq)[None]))[0] - want).max()
    patch = control_nemotron._Patches()
    control_nemotron.BROKEN[control](patch)
    jax.clear_caches()
    try:
        # (a config of its own: jit's cache holds the sound program)
        cfg = dataclasses.replace(CFG, max_seq=257)
        got = np.asarray(nemotron_h.forward(tree, cfg,
                                            jnp.asarray(seq)[None]))[0]
    finally:
        patch.undo()
        jax.clear_caches()
    err = np.abs(got - want).max()
    assert sound < ATOL / 10
    assert err > (50 * sound if control == "bf16_state" else 30 * ATOL), err


# -- prefill, paged decode, a snapshot hit --------------------------------------

def _pkv(n_slots=2, prefix_cache=True, n_snapshots=None, n_pages=None):
    return kvpage.PagedKV(CFG, nemotron_h, n_slots, MAX_LEN, PT,
                          n_pages or n_slots * MAX_LEN // PT,
                          prefix_cache=prefix_cache, n_snapshots=n_snapshots)


def _refill(pkv, params, b, prompt, cfg=CFG):
    """What ``serve_paged_greedy``'s refill does, through the same
    programs; returns (logits at the last prompt token, hit pages)."""
    S = len(prompt)
    hit = pkv.prefix.match(prompt) if pkv.prefix is not None else []
    fresh = pkv.alloc_evicting(kvpage.pages_needed(S, PT) - len(hit))
    kw = dict(cfg=cfg, family=nemotron_h, kv_int8=False, on_tpu=False,
              page_tokens=PT)
    if hit:
        Pn = len(hit) * PT
        padded = serving._padded(prompt[Pn:], MAX_LEN - Pn)
        hk, hv = pkv.gather_history(hit)
        logits, one = serving.paged_suffix_prefill(
            params, jnp.asarray(padded), hk, hv, pkv.restore_tail(hit[-1]),
            S - Pn - 1, **kw)
    else:
        padded = serving._padded(prompt, MAX_LEN)
        logits, one = serving.paged_prefill(params, jnp.asarray(padded),
                                            S - 1, **kw)
    end = one.pop("end")
    pkv.scatter_prompt(one, fresh, whole=(S - len(hit) * PT) // PT)
    pkv.seat(b, hit, fresh, S, state=end)
    if pkv.prefix is not None:
        pkv.prefix.insert(prompt, pkv.pages[b])
    return np.asarray(logits[0, 0]), len(hit)


_STEP = jax.jit(lambda p, s, t: kvpage.paged_decode_step(
    p, CFG, s, t, PT, nemotron_h))


def _decode(pkv, params, toks):
    """One step of every slot; grows the tables first."""
    for b in range(pkv.n_slots):
        if pkv.pages[b]:
            assert pkv.grow(b, int(pkv.pos[b]) // PT + 1)
    logits, state = _STEP(params, pkv.device_state(), jnp.asarray(toks))
    pkv.absorb(state)
    return np.asarray(logits)


def test_prefill_then_paged_decode_against_the_references_full_forward(tree):
    """A layer with no FFN and a layer with no operator and no cache
    ride the paged step: two slots at different lengths, cold prefill
    (one right-padded: 37 in a bucket of 64) then six decode steps, each
    slot's logits the reference's at that position of its own
    sequence."""
    pkv = _pkv(prefix_cache=False)
    seqs = [_seq(50, 10), _seq(60, 11)]
    lens = [37, 20]
    for b in range(2):
        got, _ = _refill(pkv, tree, b, seqs[b][:lens[b]])
        np.testing.assert_allclose(
            got, _ref_logits(tree, seqs[b], lens[b] - 1, 1)[0], atol=ATOL)
    for t in range(6):
        got = _decode(pkv, tree, [s[n + t] for s, n in zip(seqs, lens)])
        for b in range(2):
            np.testing.assert_allclose(
                got[b], _ref_logits(tree, seqs[b], lens[b] + t, 1)[0],
                atol=ATOL)


def test_pages_and_snapshots_are_the_references(tree):
    """What the cache holds after a prefill: the ``*`` layer's pages, and
    at the end of every second whole page the Mamba-2 layers' state and
    conv window, against the reference's states."""
    pkv = _pkv()
    seq = _seq(70, 12)
    _refill(pkv, tree, 0, seq)
    head = seq[:64 + 1]
    pages = pkv.prefix.match(head)
    assert len(pages) == 4                          # 64 tokens, a snapshot
    k, v = pkv.gather_history(pages)
    snap = pkv.restore_tail(pages[-1])
    rk, rv, ru, rh = ref.states(tree, jnp.asarray(seq[:64]), plan=PLAN,
                                hp=HP, h_at=(63,))
    np.testing.assert_allclose(np.asarray(k[0]).transpose(2, 0, 1), rk[0],
                               atol=ATOL)
    np.testing.assert_allclose(np.asarray(v[0]).transpose(2, 0, 1), rv[0],
                               atol=ATOL)
    np.testing.assert_allclose(snap["ssm"], rh[:, 0], atol=ATOL)
    window = np.asarray(snap["conv"]).reshape(5, 3, -1)
    np.testing.assert_allclose(window, np.asarray(ru)[:, 61:64], atol=ATOL)


@pytest.mark.parametrize("shared,hit_pages", [(64, 4), (57, 2), (40, 2),
                                              (20, 0)])
def test_a_suffix_prefill_behind_a_restored_snapshot_reads_as_cold(
        tree, shared, hit_pages):
    """A radix hit is cut back to a page that holds a snapshot (every
    second page here); the suffix prefill starts every Mamba-2 layer
    from the restored state and window and attention from the gathered
    pages, and its logits, and the decode steps behind it, are the
    reference's full forward."""
    pkv = _pkv()
    a = _seq(80, 13)
    b = np.concatenate([a[:shared], _seq(30, 14)])
    _refill(pkv, tree, 0, a[:70])
    got, hit = _refill(pkv, tree, 1, b[:shared + 9])
    assert hit == hit_pages
    assert pkv.tail_restores == (1 if hit else 0)
    np.testing.assert_allclose(got, _ref_logits(tree, b, shared + 8, 1)[0],
                               atol=ATOL)
    seqs, lens = [a, b], [70, shared + 9]
    for t in range(3):
        out = _decode(pkv, tree, [s[n + t] for s, n in zip(seqs, lens)])
        for s in range(2):
            np.testing.assert_allclose(
                out[s], _ref_logits(tree, seqs[s], lens[s] + t, 1)[0],
                atol=ATOL)


def test_a_wrong_snapshot_does_not_pass(tree):
    """The same hit restored from another page's row: the logits leave
    the reference's by decades more than the tolerance."""
    patch = control_nemotron._Patches()
    control_nemotron.BROKEN["wrong_snapshot_row"](patch)
    try:
        pkv = _pkv()
        a = _seq(80, 13)
        b = np.concatenate([a[:64], _seq(30, 14)])
        _refill(pkv, tree, 0, a[:70])
        got, hit = _refill(pkv, tree, 1, b[:73])
    finally:
        patch.undo()
    assert hit == 4
    assert np.abs(got - _ref_logits(tree, b, 72, 1)[0]).max() > 30 * ATOL


# -- the serve loop --------------------------------------------------------------

def _serve(tree, prompts, n_new, **kw):
    kw = dict(dict(n_slots=2, max_len=MAX_LEN, family=nemotron_h, chunk=4,
                   page_tokens=PT, prefix_cache=True, n_snapshots=8), **kw)
    return serving.serve_paged_greedy(tree, CFG, prompts, n_new, **kw)


def _gaps(tree, prompts, outs):
    """Per request, per served token: how far its reference logit lies
    below the reference's best."""
    for p, o in zip(prompts, outs):
        logits = _ref_logits(tree, o, len(p) - 1, len(o) - len(p))
        yield logits.max(-1) - logits[np.arange(len(o) - len(p)), o[len(p):]]


def test_serve_paged_greedy_serves_it_restores_and_counts(tree):
    """The same loop as every family's: three requests into two slots, a
    shared prompt of two snapshot pages behind two of them; the tokens
    are the reference's own choices (or lie within the tolerance of
    them), and the new counters say what was restored, what state the
    chunks were asked to move and what the latent experts were sent."""
    shared = _seq(32, 20)
    prompts = [np.concatenate([shared, _seq(n, 21 + i)])
               for i, n in enumerate((5, 9, 3))]
    outs = _serve(tree, prompts, [6, 3, 5])
    m = outs.metrics
    assert [len(o) - len(p) for o, p in zip(outs, prompts)] == [6, 3, 5]
    for g in _gaps(tree, prompts, outs):
        assert g.max() <= ATOL
    assert m.prefix_hits == 2 and m.prefix_pages_reused == 4
    assert m.state_snapshot_restores == m.conv_tail_restores == 2
    assert m.state_snapshot_seats == 2 and m.prefills == 3
    assert m.state_bytes_slot == 5 * (H * P * N * 4 + 3 * (H * P + 2 * G * N)
                                      * 4)
    # delivering slot-steps: every decode token is one (the first token
    # of a request is its prefill's)
    assert m.state_slot_steps == sum(m.state_steps_by_chunk) == 5 + 2 + 4
    assert len(m.state_steps_by_chunk) == m.phase_n["chunk.step"]
    assert m.state_bytes_moved == 2 * 11 * m.state_bytes_slot
    # every expert is held here: the latent rows dispatched are the
    # routed pairs of the delivering slot-steps, 3 a token an E layer
    assert m.moe_row_dim == 16
    assert m.moe_latent_rows == m.moe_pairs_held == m.moe_assignments \
        == 11 * 3 * 5
    assert m.paged_operator == "attention+mamba2"
    assert m.paged_ffn.startswith("moe:_shared_ffn+latent:sorted_expert_ffn")


def test_a_share_of_the_experts_counts_its_own_pairs(tree):
    """``experts_held``: a quarter of the router's experts computes (and
    is sent latent rows for) its own pairs only; the routing counters
    keep the router's width."""
    import dataclasses
    cfg = dataclasses.replace(CFG, experts_first=2, experts_held=2)
    share = jax.tree.map(lambda a: a, tree)
    for key, place in (("seg0", 1), ("seg3", None), ("seg5", None)):
        lp = share[key][place] if place is not None else share[key]
        lp = dict(lp, w1=lp["w1"][:, 2:4], w2=lp["w2"][:, 2:4])
        if place is None:
            share[key] = lp
        else:
            share[key] = tuple(lp if j == place else l
                               for j, l in enumerate(share[key]))
    prompts = [_seq(20, 30), _seq(11, 31)]
    outs = serving.serve_paged_greedy(
        share, cfg, prompts, 5, n_slots=2, max_len=MAX_LEN,
        family=nemotron_h, chunk=4, page_tokens=PT, n_snapshots=4)
    m = outs.metrics
    assert m.moe_experts == 8 and m.moe_experts_held == 2
    assert m.moe_assignments == 8 * 3 * 5
    assert 0 < m.moe_latent_rows == m.moe_pairs_held < m.moe_assignments


def test_requests_that_end_mid_chunk_and_dead_slots_keep_the_tokens(tree):
    """``live=`` reaches the latent experts: a chunk of 8 steps with
    requests of 2 and 11 tokens serves the tokens one-token chunks
    serve."""
    prompts = [_seq(18, 40), _seq(25, 41)]
    a = _serve(tree, prompts, [2, 11], chunk=8, prefix_cache=False)
    b = _serve(tree, prompts, [2, 11], chunk=1, prefix_cache=False)
    assert [o.tolist() for o in a] == [o.tolist() for o in b]
    assert a.metrics.moe_pairs_dead > 0


@pytest.mark.parametrize("name", ["nemotron_kernel", "nemotron_plain",
                                  "jamba", "lfm2"])
def test_a_dead_slot_step_moves_no_state_and_is_counted(name, tree,
                                                        monkeypatch):
    """``live=`` reaches the state operator: three requests of 2, 11 and
    5 tokens through two slots in chunks of 8 (requests that end inside
    a chunk, a slot that idles at the end) get the tokens of the same
    call with every slot said to be live throughout, which is the update
    of every slot's state that the parent ran, with ``ssd_update`` (the
    Pallas call, interpret mode) and with its plain twin; the program
    counts the slot-steps it was told are dead, once a step, and with
    the delivering ones they are all the chunks' slot-steps. Jamba's and
    LFM2's operators take ``live`` and do not read it: they count
    none."""
    import dataclasses
    from mpi_acx_tpu.models import jamba, lfm2
    if name.startswith("nemotron"):
        family, params = nemotron_h, tree
        cfg = dataclasses.replace(CFG, ssm_kernel=name.endswith("kernel"))
    else:
        family = {"jamba": jamba, "lfm2": lfm2}[name]
        cfg = getattr(family, "tiny_" + name)()
        params = family.init_params(jax.random.key(0), cfg)
    prompts = [_seq(18, 50) % cfg.vocab, _seq(25, 51) % cfg.vocab,
               _seq(9, 52) % cfg.vocab]

    def serve():
        return serving.serve_paged_greedy(
            params, cfg, prompts, [2, 11, 5], n_slots=2, max_len=MAX_LEN,
            family=family, chunk=8, page_tokens=PT)

    told = serve()
    m = told.metrics
    assert m.decode_slot_steps == 8 * 2 * m.steps
    assert m.state_slot_steps == m.decode_tokens == 1 + 10 + 4
    if family is nemotron_h:
        assert m.state_slot_steps + m.state_steps_dead == m.decode_slot_steps
        assert m.state_dead_share == m.state_steps_dead / m.decode_slot_steps
        assert 0.5 < m.state_dead_share < 1
    else:
        assert m.state_steps_dead == 0 == m.state_dead_share
    monkeypatch.setattr(serving.RequestBook, "left",
                        lambda self: np.full(self.n_slots, self.chunk,
                                             np.int32))
    untold = serve()
    assert untold.metrics.programs_traced == 0
    assert [o.tolist() for o in told] == [o.tolist() for o in untold]
    assert untold.metrics.state_steps_dead == 0
