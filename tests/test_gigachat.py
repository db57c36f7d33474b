"""GigaChat3 / DeepSeek-V3 block (models/gigachat.py) against the plain
reference (benchmarks/reference/gigachat.py) on seeded weights, by
LOGITS, at a tiny size on the CPU: the plain forward; cold prefill,
suffix prefill behind a radix hit and the ABSORBED decode through a
latent page pool, across a page boundary and a chunk's flush, through
the functions ``serve_paged_greedy`` runs and through the loop itself;
the latent attend kernel (interpret mode) against the gathered dense
attend, dead slots included; group-limited routing against a selection
written as a loop, ties included; and the shares test: every share's
held experts plus the shared expert counted once add up to the uncut
layer.

Tolerances, each beside its reason: in float32 the program and the
reference compute the same sums in another order (absorbed against
formed K and V, scans, grouped matmuls, blocks of tokens), which reads
1e-6..1e-5 on logits of size ~3: ``ATOL`` = 2e-4 leaves a decade and
more of room, and what is left out on purpose (an expert, the shared
expert, the group limit) reads 1e-2..1.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import weights_gigachat  # noqa: E402
from benchmarks.entries import serve_paged_greedy_gigachat as entry  # noqa: E402
from benchmarks.reference import gigachat as ref  # noqa: E402
from mpi_acx_tpu.models import gigachat, kvpage, moe, serving  # noqa: E402
from mpi_acx_tpu.ops import flash_decode as fd  # noqa: E402
from mpi_acx_tpu.ops.attention import flash_rows_attention  # noqa: E402

ATOL = 2e-4
PT, MAX_LEN = 16, 128

# The tiny preset as a configuration FILE's keys (what the benchmark's
# entry and reference read): one dense layer + two expert layers of 2
# groups x 4 experts beside a shared expert, 4 heads on a 32 + 16 wide
# latent row. ``init_scale`` 1/sqrt(d): the layers decide the logits.
C = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
         q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
         qk_rope_head_dim=16, v_head_dim=24, intermediate_size=96,
         moe_intermediate_size=32, num_hidden_layers=3,
         first_k_dense_replace=1, n_routed_experts=8, n_shared_experts=1,
         num_experts_per_tok=2, n_group=2, topk_group=1,
         routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-6,
         rope_theta=100000,
         rope_scaling=dict(beta_fast=32, beta_slow=1, factor=4.0, mscale=1,
                           mscale_all_dim=1,
                           original_max_position_embeddings=64),
         max_position_embeddings=512, init_scale=0.125,
         selection_bias_seed=3, serve=dict(moe_block=16))
PLAN, HP = weights_gigachat.plan(C), ref.hyper(C)
CFG = entry.program_config(C, "float32")


@pytest.fixture(scope="module")
def tree():
    return weights_gigachat.make_gigachat(C, 7, jnp.float32)


def _seq(n, seed):
    return np.random.default_rng(seed).integers(
        0, C["vocab_size"], n).astype(np.int32)


def _ref_logits(tree, seq, first, rows, c=C):
    return np.asarray(ref.logits_from(
        tree, jnp.asarray(seq), first, jnp.zeros((rows,), jnp.int8),
        plan=weights_gigachat.plan(c), hp=ref.hyper(c)))


# -- the program's config, layout and spec -----------------------------------

def test_tiny_preset_the_file_mapping_and_the_spec_agree():
    assert CFG == gigachat.tiny_gigachat(dtype=jnp.dtype("float32"))
    assert jax.tree.structure(
        gigachat.init_params(jax.random.key(0), CFG)) == jax.tree.structure(
            jax.eval_shape(lambda: weights_gigachat.make_gigachat(
                C, 0, jnp.float32)))
    spec = kvpage.paged_spec(gigachat, CFG)
    assert (spec.n_page_layers, spec.n_state_layers, spec.n_rep) == (3, 0, 4)
    assert (spec.n_kv_heads, spec.head_dim, spec.v_dim) == (1, 48, 32)
    assert spec.built("operator") == "latent_attention"
    assert spec.built("ffn") == ("dense:_dense_ffn+moe:_shared_ffn+"
                                 "sorted_expert_ffn/ragged_dot_matmul")
    assert [(s.period[0].ffn, s.repeats) for s in spec.segments] == [
        ("dense", 1), ("moe", 2)]
    pub = gigachat.gigachat31_702b()
    assert [(s.period[0].ffn, s.repeats) for s in gigachat.segments(pub)] \
        == [("dense", 3), ("moe", 61)]
    assert abs(gigachat.attn_scale(pub) - 192 ** -0.5 * 1.4159 ** 2) < 1e-4
    with pytest.raises(NotImplementedError, match="kv_int8.*gigachat"):
        kvpage.PagedKV(CFG, gigachat, 2, MAX_LEN, PT, 8, kv_int8=True)
    # a latent pool is ONE pool of one row a token: no V, no scales
    pkv = kvpage.PagedKV(CFG, gigachat, 2, MAX_LEN, PT, 8)
    assert {k: v.shape for k, v in pkv.pool.items()} == {
        "k": (3, 8 + 2, 1, 48, PT)}


def test_yarn_frequencies_blend_between_the_two_betas():
    cfg = gigachat.gigachat31_702b()
    got = gigachat.yarn_inv_freq(cfg)
    base = 100000.0 ** (-np.arange(0, 64, 2) / 64)
    # by hand: pair(32 turns) = 64 ln(4096 / (64 pi)) / (2 ln 1e5) =
    # 8.38 -> 8; pair(1 turn) = 18.01 -> 19
    np.testing.assert_allclose(got[:9], base[:9], rtol=1e-6)
    np.testing.assert_allclose(got[19:], base[19:] / 64, rtol=1e-6)
    assert (np.diff(got) < 0).all()
    np.testing.assert_allclose(
        got, np.asarray(ref.inv_freq(dict(ref.hyper(dict(
            C, qk_rope_head_dim=64, rope_scaling=dict(
                beta_fast=32, beta_slow=1, factor=64, mscale=1,
                mscale_all_dim=1,
                original_max_position_embeddings=4096)))))), rtol=1e-5)


# -- routing -------------------------------------------------------------------

def _loop_route(s, bias, top_k, n_group, topk_group, scale):
    """The selection written as loops over tokens, groups and experts."""
    T, E = s.shape
    per = E // n_group
    idx = np.zeros((T, top_k), np.int64)
    p = np.zeros((T, top_k))
    kept = np.zeros((T, n_group), bool)
    for t in range(T):
        sb = s[t] + bias
        score = []
        for g in range(n_group):
            mine = sorted(sb[g * per:(g + 1) * per], reverse=True)
            score.append(mine[0] + mine[1])
        # the best groups, the lower index first among equals
        for g in sorted(range(n_group), key=lambda g: (-score[g], g))[
                :topk_group]:
            kept[t, g] = True
        inside = [e for e in range(E) if kept[t, e // per]]
        idx[t] = sorted(inside, key=lambda e: (-sb[e], e))[:top_k]
        p[t] = s[t, idx[t]] / (s[t, idx[t]].sum() + 1e-20) * scale
    return idx, p, kept


@pytest.mark.parametrize("case", ["random", "tied_experts", "tied_groups",
                                  "bias_decides", "published_shape"])
def test_group_limited_routing_against_a_loop(case):
    rng = np.random.default_rng(3)
    T, E, k, G, kg = 12, 16, 3, 4, 2
    if case == "published_shape":
        T, E, k, G, kg = 6, 256, 8, 8, 4
    logits = rng.normal(size=(T, E))
    bias = rng.uniform(-0.05, 0.05, E)
    if case == "tied_experts":      # equal scores inside a group
        logits[:, 1] = logits[:, 2] = logits[:, 0]
        bias[:3] = 0.01
    elif case == "tied_groups":     # two groups' scores equal: the lower
        logits[:, 4:8] = logits[:, 0:4]
        bias[4:8] = bias[0:4]
    elif case == "bias_decides":    # the bias selects, the score weighs
        logits[:] = 0.0
        bias = np.linspace(0.05, -0.05, E)
    x = jnp.eye(T, dtype=jnp.float32)
    idx, p, kept = moe.route_sigmoid_group_topk(
        x, jnp.asarray(logits, jnp.float32), jnp.asarray(bias, jnp.float32),
        k, G, kg, 2.5, True)
    s = 1 / (1 + np.exp(-logits.astype(np.float32).astype(np.float64)))
    want_idx, want_p, want_kept = _loop_route(
        s, bias.astype(np.float32).astype(np.float64), k, G, kg, 2.5)
    np.testing.assert_array_equal(np.asarray(kept), want_kept)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(p), want_p, rtol=1e-5)
    # every choice lies in a kept group, and the reference's router
    # (one-hot weights over E) chooses the same
    assert np.take_along_axis(np.asarray(kept), np.asarray(idx) // (E // G),
                              1).all()
    comb = np.asarray(ref.route(
        x, jnp.asarray(logits, jnp.float32), jnp.asarray(bias, jnp.float32),
        dict(n_group=G, topk_group=kg, top_k=k, norm_topk=True, scale=2.5)))
    np.testing.assert_array_equal(
        np.sort(np.argsort(~(comb > 0), -1, kind="stable")[:, :k], -1),
        np.sort(want_idx, -1))


# -- forward -------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_against_the_reference(tree, dtype):
    seq = _seq(40, 1)
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype))
    params = (tree if dtype == "float32"
              else gigachat.cast_params(tree, jnp.bfloat16))
    got = np.asarray(gigachat.forward(params, cfg, jnp.asarray(seq)[None])[0])
    # the reference reads the SAME (rounded) weights, in float32
    want = _ref_logits(params, seq, 0, 40)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    else:
        # 8 bits of mantissa over 3 layers read 0.01-0.03 relative RMS a
        # row unless a router's near-tie flipped upstream (0.1-0.8 in
        # that row and after): the best quarter of the rows is held
        centre = lambda a: a - a.mean(-1, keepdims=True)
        rows = np.sqrt(np.square(centre(got - want)).sum(-1)
                       / np.square(centre(want)).sum(-1))
        assert np.sort(rows)[:10].max() < 0.05, np.sort(rows)


@pytest.mark.parametrize("left_out", ["an_expert", "the_shared_expert",
                                      "the_group_limit"])
def test_what_is_left_out_does_not_pass(tree, left_out, monkeypatch):
    """The controls of the test above (and of the cell's ``mean_gap``):
    each moves the logits by far more than the tolerance."""
    seq = _seq(40, 1)
    want = _ref_logits(tree, seq, 0, 40)
    if left_out == "an_expert":
        route = moe.route_sigmoid_group_topk
        monkeypatch.setattr(
            moe, "route_sigmoid_group_topk", lambda *a, **k: (
                lambda idx, p, kept: (idx, p.at[:, -1].set(0.0), kept))(
                    *route(*a, **k)))
    elif left_out == "the_shared_expert":
        monkeypatch.setattr(gigachat, "_shared_ffn",
                            lambda cfg, lp, u: jnp.zeros_like(u))
    else:
        monkeypatch.setattr(
            moe, "route_sigmoid_group_topk",
            lambda x, gate, bias, top_k, n_group, topk_group, *a:
            moe.route_sigmoid_topk(x, gate, bias, top_k, a[0], a[1], )
            + (jnp.ones((x.shape[0], n_group), bool),))
    got = np.asarray(gigachat.forward(tree, CFG, jnp.asarray(seq)[None])[0])
    assert np.abs(got - want).max() > 50 * ATOL


# -- prefill, suffix prefill, absorbed decode through the pool ---------------

KW = dict(cfg=CFG, family=gigachat, kv_int8=False, on_tpu=False,
          page_tokens=None)


def _refill(pkv, params, b, prompt, n_new):
    """Seat ``prompt`` in slot b as ``serve_paged_greedy.refill`` does;
    (the prefill's logits row, pages hit)."""
    hit = pkv.prefix.match(prompt) if pkv.prefix is not None else []
    need = kvpage.pages_needed(len(prompt) + n_new, PT)
    fresh = pkv.alloc_evicting(need - len(hit))
    if hit:
        P = len(hit) * PT
        suffix = prompt[P:]
        hk, hv = pkv.gather_history(hit)
        assert hv is None
        logits, one = serving.paged_suffix_prefill(
            params, jnp.asarray(serving._padded(suffix, MAX_LEN - P)), hk, hv,
            pkv.restore_tail(hit[-1]), len(suffix) - 1, **KW)
    else:
        logits, one = serving.paged_prefill(
            params, jnp.asarray(serving._padded(prompt, MAX_LEN)),
            len(prompt) - 1, **KW)
    assert set(one) == {"k"}
    pkv.scatter_prompt(one, fresh)
    pkv.seat(b, hit, fresh, len(prompt))
    if pkv.prefix is not None:
        pkv.prefix.insert(prompt, pkv.pages[b])
    return np.asarray(logits[0, 0]), len(hit)


def _chunk(pkv, params, seqs, lens, chunk, cfg=CFG):
    """One decode CHUNK (staged, flushed) fed the sequences' own next
    tokens is not what the chunk does (it feeds its own argmax): so the
    steps are run one at a time through ``paged_decode_step`` WITH a
    stage, as the chunk's scan does, and flushed as it does."""
    state = pkv.device_state()
    keys = tuple(k for k in kvpage._POOL_KEYS if k in state)
    pos0 = state["pos"]
    v_dim = pkv.spec.v_dim
    state["stage"] = (fd.new_kv_stage([state[k] for k in keys], len(seqs),
                                      chunk, v_dim), jnp.int32(0))
    out = []
    for j in range(chunk):
        tok = jnp.asarray([s[n + j] for s, n in zip(seqs, lens)], jnp.int32)
        logits, state = kvpage.paged_decode_step(params, cfg, state, tok, PT,
                                                 gigachat)
        out.append(np.asarray(logits))
    stage, _ = state.pop("stage")
    write = fd.select_paged_kv_write(cfg.decode_flash, PT)
    pools = tuple(state[k] for k in keys)
    for layer in range(stage[0].shape[0]):
        pools = fd.paged_kv_write_runs(
            write, pools, fd.stage_tokens(stage, layer, v_dim), layer,
            state["table"], pos0, PT)
    pkv.absorb(dict(state, **dict(zip(keys, pools))))
    return np.stack(out)


def _pkv(n_slots=2, prefix_cache=True):
    return kvpage.PagedKV(CFG, gigachat, n_slots, MAX_LEN, PT, 8 * n_slots,
                          prefix_cache=prefix_cache)


@pytest.mark.parametrize("decode_flash", [False, True],
                         ids=["gathered_attend", "walk_kernel_interpreted"])
def test_prefill_then_absorbed_decode_against_the_references_full_forward(
        tree, decode_flash):
    """Two slots at different positions (a prompt inside one page, one
    over three), two chunks of six steps that cross a page boundary and
    a flush: the cold prefill's and every absorbed step's logits are the
    reference's, which forms K and V for every head; the rows the pool
    holds afterwards (prefilled AND staged and flushed) are the
    reference's ``[c_kv | k_rope]``."""
    cfg = dataclasses.replace(CFG, decode_flash=decode_flash)
    seqs, lens, steps = [_seq(70, 2), _seq(70, 3)], [11, 37], 6
    pkv = _pkv()
    for b in range(2):
        first, hits = _refill(pkv, tree, b, seqs[b][:lens[b]], 2 * steps)
        assert hits == 0
        np.testing.assert_allclose(
            first, _ref_logits(tree, seqs[b], lens[b] - 1, 1)[0], atol=ATOL,
            rtol=0)
    got = np.concatenate([
        _chunk(pkv, tree, seqs, [n + j * steps for n in lens], steps, cfg)
        for j in range(2)])
    for b in range(2):
        np.testing.assert_allclose(
            got[:, b], _ref_logits(tree, seqs[b], lens[b], 2 * steps),
            atol=ATOL, rtol=0)
        T = lens[b] + 2 * steps
        rows, none = pkv.gather_history(pkv.pages[b])
        assert none is None
        np.testing.assert_allclose(
            np.asarray(rows)[:, 0, :, :T].transpose(0, 2, 1),
            np.asarray(ref.states(tree, jnp.asarray(seqs[b][:T]), plan=PLAN,
                                  hp=HP)), atol=1e-5, rtol=0)
    # the routing counters: both slots, every step and expert layer; the
    # held experts are all 8, so every pair is held; a token keeps ONE of
    # the two groups and the held experts' own (group 0) some of the time
    assert len(pkv.moe_chunks) == 2
    # (a state built without ``left``: no slot is masked, none is dead)
    for pairs, live, fullest, layer_steps, held, hits, dead in pkv.moe_chunks:
        assert (pairs, layer_steps, held, dead) == (
            2 * 2 * 2 * steps, 2 * steps, pairs, 0)
        assert fullest <= live <= pairs and 0 <= hits <= 2 * 2 * steps


def test_absorbed_decode_equals_unabsorbed_attention(tree):
    """One layer, one token behind 20 cached rows: the absorbed query
    against the rows and ``W_UV`` behind the attend give what formed K
    and V give, to float32's rounding."""
    lp = jax.tree.map(lambda a: a[0], tree["seg0"])
    x = jax.random.normal(jax.random.key(1), (1, 21, CFG.d_model))
    full, rows = gigachat._sequence_attention(CFG, lp, x, jnp.arange(21))
    q, row = gigachat._decode_qkv(CFG, lp, x[:, 20:], jnp.asarray([20]))
    np.testing.assert_allclose(np.asarray(row[0, 0, 0]),
                               np.asarray(rows[20]), atol=1e-6)
    s = jnp.einsum("hd,td->ht", q[0, 0], rows) * gigachat.attn_scale(CFG)
    u = jax.nn.softmax(s, -1) @ rows[:, :CFG.kv_lora_rank]
    got = gigachat._decode_attn_out(CFG, lp, x[:, 20:], u.reshape(1, 1, -1))
    np.testing.assert_allclose(np.asarray(got[0, 0]),
                               np.asarray(full[0, 20]), atol=2e-5, rtol=0)


def test_a_radix_hit_gives_the_logits_of_a_cold_prefill(tree):
    """Two prompts sharing two whole pages: the second is seated from
    the first's latent pages, up-projects them beside its suffix, and
    reads what a cold prefill of it reads, prefill and absorbed decode."""
    a = _seq(60, 5)
    b = np.concatenate([a[:2 * PT], _seq(28, 6)])
    la, lb, steps = 40, 2 * PT + 7, 5
    pkv = _pkv()
    _refill(pkv, tree, 0, a[:la], steps)
    first, hits = _refill(pkv, tree, 1, b[:lb], steps)
    assert hits == 2
    warm = _chunk(pkv, tree, [a, b], [la, lb], steps)[:, 1]
    np.testing.assert_allclose(
        np.concatenate([first[None], warm]),
        _ref_logits(tree, b, lb - 1, steps + 1), atol=ATOL, rtol=0)


# -- the kernels ----------------------------------------------------------------

@pytest.mark.parametrize("staged", [False, True], ids=["pool", "pool+stage"])
def test_the_latent_walk_against_the_gathered_attend(staged):
    """``paged_flash_decode_attend`` on a latent pool (interpret mode)
    against ``paged_gather_attend``: 5 slots of 0..3 pages, two of them
    dead at this step, with and without a chunk's stage; live rows agree
    to float32's rounding, dead rows are exactly zero in both, and a NaN
    in a dead slot's pages, or in the stage's rows that the chunk has
    not filled yet, reaches nothing."""
    rng = np.random.default_rng(0)
    L, B, H, D, Dv, pt, P, chunk = 2, 5, 4, 48, 32, 16, 12, 8
    pool = jnp.asarray(rng.normal(size=(L, P + B, 1, D, pt)), jnp.float32)
    table = jnp.asarray(rng.permutation(P)[:B * 2].reshape(B, 2), jnp.int32)
    table = jnp.concatenate([table, P + jnp.arange(B)[:, None]], 1)
    pos = jnp.asarray([0, 9, 16, 30, 33], jnp.int32)
    left = jnp.asarray([5, 0, 9, 2, 4], jnp.int32)     # slots 1, 3 dead
    step = 3
    pool = pool.at[:, table[1]].set(jnp.nan)
    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
    stage = None
    if staged:
        arrays = fd.new_kv_stage([pool], B, chunk, Dv)
        for j in range(step + 1):
            arrays = fd.stage_put(arrays, (jnp.asarray(rng.normal(
                size=(B, 1, 1, D)), jnp.float32),), 1, jnp.int32(j), Dv)
        arrays = (arrays[0].at[:, :, step + 1:].set(jnp.nan),)
        stage, pos = (arrays, jnp.int32(step)), pos + step
    kw = dict(layer=1, stage=stage, left=left if staged else left - step,
              v_dim=Dv, scale=0.17)
    got = fd.paged_flash_decode_attend(q, pool, None, table, pos, pt, H, **kw)
    want = fd.paged_gather_attend(q, pool, None, table, pos, pt, H, **kw)
    assert got.shape == want.shape == (B, 1, H * Dv)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all() and (got[[1, 3]] == 0).all()
    assert (want[[1, 3]] == 0).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("offset,S,Sk", [(0, 32, 32), (48, 16, 64),
                                         (40, 24, 64)],
                         ids=["whole", "suffix", "suffix_with_padding"])
def test_rows_attention_kernel_against_plain_attention(offset, S, Sk):
    """``flash_rows_attention`` (interpret mode) with K wider than V:
    rows ``offset ..`` against all keys, padding columns masked."""
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(3, n, 40)), jnp.float32)
            for n in (S, Sk))
    v = jnp.asarray(rng.normal(size=(3, Sk, 24)), jnp.float32)
    got = flash_rows_attention(q, k, v, q_offset=offset, scale=0.2,
                               block_q=8, block_k=16)
    cfg = dataclasses.replace(CFG, use_flash=False, qk_nope_head_dim=24,
                              qk_rope_head_dim=16, rope_factor=1.0)
    want = gigachat._rows_attend(cfg, q * (0.2 / gigachat.attn_scale(cfg)),
                                 k, v, offset)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


# -- the serve loop itself -----------------------------------------------------

def _serve(tree, prompts, n_new, cfg=CFG, **kw):
    kw = dict(dict(n_slots=2, max_len=MAX_LEN, family=gigachat, chunk=4,
                   page_tokens=PT, prefix_cache=True,
                   max_request_retries=0), **kw)
    return serving.serve_paged_greedy(tree, cfg, prompts, n_new, **kw)


def _gaps(tree, prompts, outs, c=C):
    """Every served token's reference gap (0: the reference's choice)."""
    g = []
    for p, o in zip(prompts, outs):
        rows = _ref_logits(tree, np.pad(o, (0, MAX_LEN - len(o))), len(p) - 1,
                           len(o) - len(p), c)
        g += list(rows.max(-1) - rows[np.arange(len(rows)), o[len(p):]])
    return np.asarray(g)


@pytest.mark.parametrize("use_flash", [False, True],
                         ids=["plain_prefill", "rows_kernel_interpreted"])
def test_serve_paged_greedy_serves_it_hits_and_counts(tree, use_flash):
    """Through ``serve_paged_greedy`` itself: six requests into two
    slots, two of them sharing two whole pages with an earlier one,
    outputs that end mid-chunk; every served token is the reference's
    choice to ATOL; the metrics name what was built and count the
    routing, the staged tokens, the walked pages and the reused pages of
    the latent pool."""
    cfg = dataclasses.replace(CFG, use_flash=use_flash)
    base = _seq(50, 10)
    prompts = [base[:41], _seq(9, 11), _seq(23, 12),
               np.concatenate([base[:32], _seq(6, 13)]),
               np.concatenate([base[:32], _seq(15, 14)]), _seq(35, 15)]
    n_new = [7, 3, 9, 6, 7, 2]
    outs = _serve(tree, prompts, n_new, cfg=cfg)
    m = outs.metrics
    assert _gaps(tree, prompts, outs).max() <= ATOL
    assert m.prefix_hits == 2 and m.prefix_pages_reused == 4
    assert m.paged_operator == "latent_attention"
    assert m.paged_ffn == ("dense:_dense_ffn+moe:_shared_ffn+"
                           "sorted_expert_ffn/ragged_dot_matmul")
    assert m.paged_decode_attend == "paged_gather_attend"
    assert m.kv_bytes_token == 3 * 48 * 4 and m.state_bytes_slot == 0
    assert m.moe_experts == m.moe_experts_held == 8
    assert m.moe_layer_steps == 2 * 4 * m.steps
    assert m.moe_pairs_held == m.moe_assignments > 0
    assert 0 < m.moe_group_hits <= m.moe_assignments // 2
    assert 0 < m.moe_live_expert_share <= 4 / 8
    # the counters count what the expert layer was left with: the pairs
    # of slot-steps that deliver a token, and beside them the pairs the
    # mask took (requests end mid-chunk, the burst drains)
    assert sum(c[0] for c in m.moe_by_chunk) == m.moe_assignments
    assert m.moe_assignments == 2 * 2 * m.decode_tokens
    assert m.moe_pairs_dead == sum(c[-1] for c in m.moe_by_chunk) > 0
    assert m.moe_assignments + m.moe_pairs_dead == 2 * 2 * m.moe_layer_steps
    assert m.kv_tokens_staged == 4 * 2 * m.steps
    assert m.kv_page_rewrites >= 2 * m.steps
    assert 0 < m.attend_dead_share < 1 and m.attend_pages_walked > 0
    # the same requests with nothing cached: the same tokens
    cold = _serve(tree, prompts, n_new, cfg=cfg, prefix_cache=False)
    assert cold.metrics.prefix_hits == 0
    assert all((a == b).all() for a, b in zip(outs, cold))


def test_a_share_of_the_experts_is_served_and_counted():
    """The experts 4..7 held (group 1 of 2): the loop serves the share
    the reference computes when given the same share, and the counters
    say what of the routing the share holds."""
    c = dict(C, n_routed_experts=4, experts_held=dict(first=4, count=4, of=8))
    cfg = entry.program_config(c, "float32")
    assert (cfg.n_experts, cfg.experts_first, cfg.n_held) == (8, 4, 4)
    tree = weights_gigachat.make_gigachat(c, 7, jnp.float32)
    assert tree["seg1"]["w1"].shape == (2, 4, 64, 32)
    assert tree["seg1"]["gate"].shape == (2, 64, 8)
    prompts = [_seq(20, 30), _seq(33, 31), _seq(9, 32)]
    outs = _serve(tree, prompts, 6, cfg=cfg)
    m = outs.metrics
    assert _gaps(tree, prompts, outs, c).max() <= ATOL
    assert (m.moe_experts, m.moe_experts_held) == (8, 4)
    # one group kept a token: its two choices are both held or both not
    assert m.moe_pairs_held == 2 * m.moe_group_hits
    assert 0 < m.moe_pairs_held < m.moe_assignments
    assert 0 < m.moe_live_expert_share <= 1
    assert m.moe_pairs_dead > 0         # three requests into two slots
    assert m.moe_assignments + m.moe_pairs_dead == 2 * 2 * m.moe_layer_steps


# -- the shares add up ----------------------------------------------------------

def test_the_shares_held_experts_and_the_shared_expert_once_add_up(tree):
    """One expert layer of the uncut model (8 experts) against its
    SHARES: four holders of 2 experts each. Every share's held-expert
    part (the program's ``sorted_expert_ffn`` told ``first``) summed,
    plus what every chip computes alike, the shared expert, counted
    ONCE, is the uncut reference layer; a share by itself is not."""
    h = dict(HP)
    lp = tree["seg1"]
    u = jax.random.normal(jax.random.key(5), (24, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._moe(u, lp, 1, h))
        shared = np.asarray(ref._moe(u, lp, 1, h) - ref._moe(
            u, lp, 1, h, shared=False))
    idx, p, _ = moe.route_sigmoid_group_topk(
        u, lp["gate"][1], lp["bias"][1], CFG.top_k, CFG.n_group,
        CFG.topk_group, CFG.routed_scaling_factor, CFG.norm_topk_prob)
    parts = [np.asarray(moe.sorted_expert_ffn(
        u, lp["w1"][1, f:f + 2], lp["w3"][1, f:f + 2], lp["w2"][1, f:f + 2],
        idx, p, first=f)) for f in range(0, 8, 2)]
    np.testing.assert_allclose(sum(parts) + shared, want, atol=ATOL, rtol=0)
    assert all(np.abs(part + shared - want).max() > 50 * ATOL
               for part in parts)
    # the program's own layer, as one holder of a share, gives that
    # share's part + the shared expert (and the residual)
    cfg = dataclasses.replace(CFG, experts_first=2, experts_held=2)
    lp1 = {n: a[1] for n, a in lp.items()}
    lp1.update({n: lp[n][1, 2:4] for n in ("w1", "w3", "w2")})
    x = jax.random.normal(jax.random.key(6), (1, 24, CFG.d_model))
    un = np.asarray(gigachat.rmsnorm(x, lp1["ffn_norm"], CFG.norm_eps))[0]
    idx, p, _ = moe.route_sigmoid_group_topk(
        jnp.asarray(un), lp1["gate"], lp1["bias"], CFG.top_k, CFG.n_group,
        CFG.topk_group, CFG.routed_scaling_factor, CFG.norm_topk_prob)
    part = np.asarray(moe.sorted_expert_ffn(
        jnp.asarray(un), lp1["w1"], lp1["w3"], lp1["w2"], idx, p, first=2))
    got = np.asarray(gigachat._moe_ffn(cfg, lp1, x)[0])[0]
    np.testing.assert_allclose(
        got - np.asarray(x[0]),
        part + np.asarray(gigachat._shared_ffn(cfg, lp1, jnp.asarray(un))),
        atol=ATOL, rtol=0)


def test_the_tally_of_a_share_counts_held_pairs_and_group_hits():
    idx = jnp.asarray([[0, 1], [2, 3], [4, 5], [6, 1]])
    kept = jnp.asarray([[True, False], [True, False], [False, True],
                        [True, True]])
    owns = jnp.asarray([True, False, True, True])
    # experts 0..1 held of 8 (group 0 of 2): pairs routed by owners 6,
    # held experts hit {0, 1}, fullest 2 (expert 1), 1 layer-step, held
    # pairs 3, owners whose kept groups include group 0: slots 0 and 3
    # and, appended, the pairs a mask left out: without one, none
    tally = kvpage._moe_tally(idx, owns, 8, (0, 2), kept)
    assert list(np.asarray(tally)) == [6, 2, 2, 1, 3, 2, 0]
    tally = kvpage._moe_tally(idx, owns, 8, (4, 4), kept)
    assert list(np.asarray(tally)) == [6, 3, 1, 1, 3, 2, 0]
    # slot 3 can deliver no token at this step, nor can the idle slot 1:
    # owners live are slots 0 and 2, 4 pairs, held {0, 1} once each,
    # group 0 kept by slot 0; 2 slots x top 2 left out
    live = jnp.asarray([True, False, True, False])
    tally = kvpage._moe_tally(idx, owns, 8, (0, 2), kept, live=live)
    assert list(np.asarray(tally)) == [4, 2, 1, 1, 2, 1, 4]
    tally = kvpage._moe_tally(idx, owns, 8, (4, 4), kept,
                              live=jnp.zeros((4,), bool))
    assert list(np.asarray(tally)) == [0, 0, 0, 1, 0, 0, 8]


def test_a_layer_in_blocks_masks_each_blocks_dead_tokens(tree):
    """32 tokens through the layer in two blocks of ``moe_block`` 16
    (``lax.map``), a share of the experts held: the mask goes to each
    block with its rows. Live tokens get the layer's own result bit for
    bit, dead ones the shared expert alone (every token's, whatever the
    rows)."""
    cfg = dataclasses.replace(CFG, experts_first=2, experts_held=4)
    lp = {n: a[1] for n, a in tree["seg1"].items()}
    lp.update({n: tree["seg1"][n][1, 2:6] for n in ("w1", "w3", "w2")})
    x = jax.random.normal(jax.random.key(8), (1, 32, CFG.d_model))
    live = np.arange(32) % 3 == 0
    live[16:] = False                   # the second block: no live token
    live[20] = True
    want, idx, kept = gigachat._moe_ffn(cfg, lp, x)
    got, idx2, kept2 = gigachat._moe_ffn(cfg, lp, x, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(kept2))
    want, got = np.asarray(want)[0], np.asarray(got)[0]
    np.testing.assert_array_equal(got[live], want[live])
    u = gigachat.rmsnorm(x, lp["ffn_norm"], CFG.norm_eps)[0]
    alone = np.asarray(x[0] + gigachat._shared_ffn(cfg, lp, u))
    np.testing.assert_allclose(got[~live], alone[~live], atol=1e-6, rtol=0)
    held = ((np.asarray(idx) >= 2) & (np.asarray(idx) < 6)).any(-1)
    assert np.abs(want - alone)[held & ~live].max() > 1e-3
