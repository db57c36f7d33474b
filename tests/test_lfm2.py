"""LFM2-MoE (models/lfm2.py) against the plain reference
(benchmarks/reference/lfm2.py) on seeded weights, by LOGITS, at a tiny
size on the CPU: the plain forward; prefill then paged decode through
the functions ``serve_paged_greedy`` runs (``serving.paged_prefill`` /
``paged_suffix_prefill``, ``PagedKV``, ``kvpage.paged_decode_step``); a
radix hit and a preempted, resumed request (the conv tail kept with a
page is what these two test); the GQA pages and the conv state the
chunk writes; the drop-free expert layer and its shares; and the
controls (an expert left out, tails in 8 bits), which must not pass.

Tolerances, each beside its reason: in float32 the program and the
reference compute the same sums in another order (scans, grouped
matmuls, a different attention formulation on a hit), which reads
1e-6..1e-5 on logits of size ~3: ``ATOL`` = 2e-4 leaves a decade and
more of room, and the controls read 1e-2..1 (three decades above it). In
bfloat16 weights and activations are rounded to 8 bits of mantissa
(relative 2**-9 = 0.002 a value, accumulating over 9 layers): a row of
logits reads 0.01-0.03 relative RMS unless a router near-tie flipped
(the forward test says how that is held).
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

from benchmarks import weights_lfm2  # noqa: E402
from benchmarks.entries import serve_paged_greedy_lfm2 as entry  # noqa: E402
from benchmarks.reference import lfm2 as ref  # noqa: E402
from mpi_acx_tpu.models import kvpage, lfm2, llama, moe, serving  # noqa: E402
from mpi_acx_tpu.models import transformer as tfm  # noqa: E402

ATOL = 2e-4
PT, MAX_LEN = 16, 128

# The tiny preset as a configuration FILE's keys (what the benchmark's
# entry and reference read): one dense conv layer + two periods (attn,
# conv, conv, conv), 8 experts top 2, d = 64. ``init_scale`` 1/sqrt(d):
# the layers, not the tied embedding's echo, decide the logits.
C = dict(vocab_size=96, hidden_size=64, num_attention_heads=4,
         num_key_value_heads=2, intermediate_size=96,
         moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
         layer_types=["conv"] + ["full_attention", "conv", "conv", "conv"] * 2,
         num_hidden_layers=9, num_dense_layers=1, conv_L_cache=3,
         norm_eps=1e-5, rope_parameters={"rope_theta": 1000000},
         norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
         max_position_embeddings=256, init_scale=0.125)
PLAN, HP = weights_lfm2.plan(C), ref.hyper(C)
CFG = entry.program_config(C, "float32")


@pytest.fixture(scope="module")
def tree():
    return weights_lfm2.make_lfm2(C, 7, jnp.float32)


def _seq(n, seed):
    return np.random.default_rng(seed).integers(
        0, C["vocab_size"], n).astype(np.int32)


def _ref_logits(tree, seq, first, rows):
    return np.asarray(ref.logits_from(
        tree, jnp.asarray(seq), first, jnp.zeros((rows,), jnp.int8),
        plan=PLAN, hp=HP))


# -- the program's config and layout ------------------------------------------

def test_tiny_preset_and_the_file_mapping_agree():
    assert CFG == lfm2.tiny_lfm2(dtype=jnp.dtype("float32"))
    assert jax.tree.structure(lfm2.init_params(jax.random.key(0), CFG)) == \
        jax.tree.structure(jax.eval_shape(
            lambda: weights_lfm2.make_lfm2(C, 0, jnp.float32)))


@pytest.mark.parametrize("kinds,want", [
    (lfm2.layer_kinds(lfm2.lfm2_24b_a2b()), [(1, 2), (4, 9), (1, 1), (1, 1)]),
    (lfm2.layer_kinds(CFG), [(1, 1), (4, 2)]),
    ([kvpage.LayerKind()] * 48, [(1, 48)]),
    ([kvpage.LayerKind()], [(1, 1)]),
], ids=["published_40", "the_cut", "gpt2_xl", "one_layer"])
def test_layers_compress_into_whole_periods(kinds, want):
    segs = kvpage.compress_layers(kinds)
    assert [(len(s.period), s.repeats) for s in segs] == want
    flat = [k for s in segs for _ in range(s.repeats) for k in s.period]
    assert flat == list(kinds)
    # the benchmark's weights find the same stretches by themselves
    if kinds and kinds[0].operator == "conv":
        c = dict(C, layer_types=["full_attention" if k.operator == "attention"
                                 else "conv" for k in kinds],
                 num_dense_layers=sum(k.ffn == "dense" for k in kinds))
        assert [(len(p), r) for _, p, r in weights_lfm2.stretches(c)] == want


def test_a_family_without_a_spec_and_int8_pages_raise_by_name():
    with pytest.raises(NotImplementedError, match="llama"):
        kvpage.paged_spec(llama, llama.tiny_llama())
    with pytest.raises(NotImplementedError, match="kv_int8.*lfm2"):
        kvpage.PagedKV(CFG, lfm2, 2, MAX_LEN, PT, 8, kv_int8=True)
    spec = kvpage.paged_spec(None, tfm.tiny_config())
    assert (spec.n_page_layers, spec.n_state_layers, spec.n_rep) == (4, 0, 1)
    assert spec.built("operator") == "attention"
    assert spec.built("ffn") == "dense:_mlp"
    spec = kvpage.paged_spec(lfm2, CFG)
    assert (spec.n_page_layers, spec.n_state_layers, spec.n_rep) == (2, 7, 2)
    assert spec.state.shape == (2, 64) and spec.snapshot_every == 1
    assert spec.built("operator") == "attention+conv"


# -- forward ------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_against_the_reference(tree, dtype):
    seq = _seq(40, 1)
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype))
    params = jax.tree.map(lambda a: a.astype(dtype)
                          if dtype == "bfloat16" else a, tree)
    got = np.asarray(lfm2.forward(params, cfg, jnp.asarray(seq)[None])[0])
    # the reference reads the SAME (rounded) weights, in float32
    want = _ref_logits(params, seq, 0, 40)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    else:
        # Row by row: a row none of whose tokens' routers flipped an
        # expert upstream reads 0.01-0.03 (8 bits of mantissa over 9
        # layers); a flip (a near-tie among 8 experts decided the other
        # way by the rounding) reads 0.1-0.8 in that row and those
        # after it. So the best quarter of the rows is held to 0.05,
        # and float32 above is the test of the mathematics.
        centre = lambda a: a - a.mean(-1, keepdims=True)
        rows = np.sqrt(np.square(centre(got - want)).sum(-1)
                       / np.square(centre(want)).sum(-1))
        assert np.isfinite(rows).all() and rows.min() > 1e-4
        assert np.quantile(rows, 0.25) < 0.05, np.sort(rows)


# -- prefill + paged decode, through the functions the serve loop runs --------

def _refill(pkv, params, b, prompt, reserve):
    """``serve_paged_greedy``'s refill, call for call: match, prefill
    (the suffix alone on a hit, from the last matched page's tail),
    scatter, seat, insert. Returns the prefill's logits [vocab]."""
    kw = dict(cfg=CFG, family=lfm2, kv_int8=False, on_tpu=False,
              page_tokens=PT)
    hit = pkv.prefix.match(prompt) if pkv.prefix is not None else []
    fresh = pkv.alloc_evicting(
        kvpage.pages_needed(len(prompt) + reserve, PT) - len(hit))
    if hit:
        P = len(hit) * PT
        suffix = prompt[P:]
        hk, hv = pkv.gather_history(hit)
        logits, one = serving.paged_suffix_prefill(
            params, jnp.asarray(serving._padded(suffix, MAX_LEN - P)), hk, hv,
            pkv.restore_tail(hit[-1]), len(suffix) - 1, **kw)
    else:
        logits, one = serving.paged_prefill(
            params, jnp.asarray(serving._padded(prompt, MAX_LEN)),
            len(prompt) - 1, **kw)
    end = one.pop("end")
    pkv.scatter_prompt(one, fresh)
    pkv.seat(b, hit, fresh, len(prompt), state=end)
    if pkv.prefix is not None:
        pkv.prefix.insert(prompt, pkv.pages[b])
    return np.asarray(logits[0, 0]), len(hit)


_STEP = jax.jit(lambda p, s, t: kvpage.paged_decode_step(p, CFG, s, t, PT,
                                                         lfm2))


def _decode(pkv, params, seqs, lens, steps):
    """``steps`` lockstep decode steps, slot b fed ``seqs[b][lens[b] +
    j]``: logits [steps, B, vocab]; the state goes back to ``pkv``."""
    out = []
    for j in range(steps):
        tok = jnp.asarray([s[n + j] for s, n in zip(seqs, lens)], jnp.int32)
        logits, state = _STEP(params, pkv.device_state(), tok)
        pkv.absorb(state)
        out.append(np.asarray(logits))
    return np.stack(out)


def _pkv(n_slots=2, prefix_cache=True):
    return kvpage.PagedKV(CFG, lfm2, n_slots, MAX_LEN, PT, 8 * n_slots,
                          prefix_cache=prefix_cache)


def test_prefill_then_paged_decode_against_the_references_full_forward(tree):
    """Two slots at different positions (a prompt inside one page, one
    over three), six steps: every logit row the reference's."""
    seqs, lens, steps = [_seq(60, 2), _seq(60, 3)], [11, 37], 6
    pkv = _pkv()
    for b in range(2):
        first, hits = _refill(pkv, tree, b, seqs[b][:lens[b]], steps)
        assert hits == 0
        np.testing.assert_allclose(
            first, _ref_logits(tree, seqs[b], lens[b] - 1, 1)[0], atol=ATOL,
            rtol=0)
    got = _decode(pkv, tree, seqs, lens, steps)
    for b in range(2):
        np.testing.assert_allclose(
            got[:, b], _ref_logits(tree, seqs[b], lens[b], steps), atol=ATOL,
            rtol=0)
    # the routing counters counted both slots, every step and MoE layer
    assert len(pkv.moe_chunks) == steps and pkv.tail_restores == 0
    # (a state built without ``left``: no slot is masked, none is dead)
    for pairs, live, fullest, layer_steps, dead in pkv.moe_chunks:
        assert (pairs, layer_steps, dead) == (2 * 2 * 8, 8, 0)
        assert fullest <= live <= pairs


def test_pages_and_conv_state_the_chunk_writes_are_the_references(tree):
    """GQA pages (prefilled AND written by decode steps) equal the
    reference's keys and values; the slot's conv state after the steps
    is the reference's z at the last two tokens; the tails kept with
    the prompt's whole pages are its z at the pages' ends."""
    seq, n, steps = _seq(60, 4), 37, 7
    pkv = _pkv(n_slots=1)
    _refill(pkv, tree, 0, seq[:n], steps)
    _decode(pkv, tree, [seq], [n], steps)
    T = n + steps
    k, v, z = (np.asarray(a) for a in ref.states(
        tree, jnp.asarray(seq[:T]), plan=PLAN, hp=HP))
    gk, gv = pkv.gather_history(pkv.pages[0])          # [L, Hkv, Dh, pages*PT]
    np.testing.assert_allclose(np.asarray(gk)[..., :T].transpose(0, 3, 1, 2),
                               k, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(gv)[..., :T].transpose(0, 3, 1, 2),
                               v, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(pkv.held)[:, 0], z[:, T - 2:T],
                               atol=1e-5, rtol=0)
    for j in range(n // PT):
        np.testing.assert_allclose(
            np.asarray(pkv.restore_tail(pkv.pages[0][j])),
            z[:, (j + 1) * PT - 2:(j + 1) * PT], atol=1e-5, rtol=0)


def test_a_radix_hit_gives_the_logits_of_a_cold_prefill(tree):
    """Two prompts sharing two whole pages: the second is seated from
    the first's pages and their tail, prefills its suffix alone, and
    reads what a cold prefill of it reads, prefill and decode."""
    a = _seq(60, 5)
    b = np.concatenate([a[:2 * PT], _seq(28, 6)])
    la, lb, steps = 40, 2 * PT + 7, 5
    pkv = _pkv()
    _refill(pkv, tree, 0, a[:la], steps)
    first, hits = _refill(pkv, tree, 1, b[:lb], steps)
    assert hits == 2 and pkv.tail_restores == 1
    warm = _decode(pkv, tree, [a, b], [la, lb], steps)[:, 1]
    cold_pkv = _pkv(prefix_cache=False)
    cold_first, _ = _refill(cold_pkv, tree, 1, b[:lb], steps)
    cold = _decode(cold_pkv, tree, [a, b], [0, lb], steps)[:, 1]
    np.testing.assert_allclose(first, cold_first, atol=ATOL, rtol=0)
    np.testing.assert_allclose(warm, cold, atol=ATOL, rtol=0)
    np.testing.assert_allclose(
        np.concatenate([first[None], warm]),
        _ref_logits(tree, b, lb - 1, steps + 1), atol=ATOL, rtol=0)


def test_a_request_preempted_and_resumed_reads_as_uninterrupted(tree):
    """Seat, decode, release (a preemption drops the slot's pages and
    its conv state), seat again: the resume hits the request's own
    whole pages in the trie and restores their tail; prefill and every
    step read what they read the first time, and the reference's."""
    seq, n, steps = _seq(70, 8), 45, 4
    pkv = _pkv(n_slots=1)
    first, _ = _refill(pkv, tree, 0, seq[:n], steps)
    before = _decode(pkv, tree, [seq], [n], steps)
    pkv.release(0)
    again, hits = _refill(pkv, tree, 0, seq[:n], steps)
    assert hits == 2 and pkv.tail_restores == 1
    after = _decode(pkv, tree, [seq], [n], steps)
    np.testing.assert_allclose(again, first, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after, before, atol=ATOL, rtol=0)
    np.testing.assert_allclose(after[:, 0], _ref_logits(tree, seq, n, steps),
                               atol=ATOL, rtol=0)


def test_a_wrong_tail_does_not_pass(tree, monkeypatch):
    """The control of the two tests above: a hit that starts its convs
    from zeros (no tail kept), or from a tail in 8 bits, reads logits
    off the reference by far more than the tolerance."""
    seq, n = _seq(60, 9), 2 * PT + 9
    want = _ref_logits(tree, seq, n - 1, 1)[0]
    restore = kvpage.PagedKV.restore_tail

    def int8(self, page):
        t = restore(self, page)
        s = jnp.max(jnp.abs(t), -1, keepdims=True) / 127
        return jnp.round(t / s) * s
    for wrong, least in ((lambda self, page: jnp.zeros_like(
            restore(self, page)), 1e-1), (int8, 5 * ATOL)):
        pkv = _pkv(n_slots=1)
        _refill(pkv, tree, 0, seq[:n], 0)
        pkv.release(0)
        monkeypatch.setattr(kvpage.PagedKV, "restore_tail", wrong)
        got, hits = _refill(pkv, tree, 0, seq[:n], 0)
        monkeypatch.setattr(kvpage.PagedKV, "restore_tail", restore)
        assert hits == 2 and np.abs(got - want).max() > least


# -- the serve loop itself ----------------------------------------------------

def _serve(tree, prompts, n_new, **kw):
    kw = dict(dict(n_slots=2, max_len=MAX_LEN, family=lfm2, chunk=4,
                   page_tokens=PT, prefix_cache=True,
                   max_request_retries=0), **kw)
    return serving.serve_paged_greedy(tree, CFG, prompts, n_new, **kw)


def _gaps(tree, prompts, outs):
    """Every served token's reference gap (0: the reference's choice)."""
    g = []
    for p, o in zip(prompts, outs):
        rows = _ref_logits(tree, np.pad(o, (0, MAX_LEN - len(o))), len(p) - 1,
                           len(o) - len(p))
        g += list(rows.max(-1) - rows[np.arange(len(rows)), o[len(p):]])
    return np.asarray(g)


def test_serve_paged_greedy_serves_it_hits_and_counts(tree):
    """Through ``serve_paged_greedy`` itself: six requests into two
    slots, two of them sharing two whole pages with an earlier one;
    every served token is the reference's choice to ATOL; the metrics
    name what was built and count routing and tail restores."""
    base = _seq(50, 10)
    prompts = [base[:41], _seq(9, 11), _seq(23, 12),
               np.concatenate([base[:32], _seq(6, 13)]),
               np.concatenate([base[:32], _seq(15, 14)]), _seq(35, 15)]
    outs = _serve(tree, prompts, 7)
    m = outs.metrics
    assert _gaps(tree, prompts, outs).max() <= ATOL
    assert m.prefix_hits == 2 and m.conv_tail_restores == 2
    assert m.paged_operator == "attention+conv"
    assert m.paged_ffn == ("dense:_dense_ffn+moe:sorted_expert_ffn/"
                           "ragged_dot_matmul")
    assert m.moe_experts == 8 and m.moe_layer_steps == 8 * 4 * m.steps
    assert len(m.moe_by_chunk) == m.steps
    assert sum(c[0] for c in m.moe_by_chunk) == m.moe_assignments
    # only slot-steps that deliver a token are counted, and only they
    # reach an expert: top 2 pairs in each of the 8 MoE layers a decode
    # token; what the mask took (a request's steps behind its last
    # token, an idle slot's) is counted beside them
    assert m.moe_assignments == 2 * 8 * m.decode_tokens
    assert m.moe_pairs_dead == sum(c[-1] for c in m.moe_by_chunk) > 0
    assert m.moe_assignments + m.moe_pairs_dead == 2 * 2 * m.moe_layer_steps
    assert 0 < m.moe_live_expert_share <= 4 / 8
    assert 1.0 <= m.moe_load_max_over_mean <= 8.0
    # the same requests with nothing cached: the same tokens
    cold = _serve(tree, prompts, 7, prefix_cache=False)
    assert cold.metrics.conv_tail_restores == 0
    assert all((a == b).all() for a, b in zip(outs, cold))
    # GPT-2 through the same loop reports its own kinds and no routing
    g_cfg = tfm.tiny_config(vocab=61, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq=64)
    g = serving.serve_paged_greedy(
        tfm.init_params(jax.random.key(0), g_cfg), g_cfg, [_seq(5, 1) % 61],
        3, n_slots=1, max_len=32, page_tokens=8)
    assert (g.metrics.paged_operator, g.metrics.paged_ffn) == (
        "attention", "dense:_mlp")
    assert g.metrics.moe_layer_steps == g.metrics.conv_tail_restores == 0
    assert g.metrics.moe_live_expert_share == 0.0


def test_requests_that_end_mid_chunk_get_the_references_tokens(tree,
                                                                monkeypatch):
    """Outputs of 2 to 9 tokens against a chunk of 4, five requests into
    two slots: requests end mid-chunk and the last drains beside an
    empty slot. The loop tells each chunk what every slot still owes
    (``state['left']``): the attend zeroes the rows of the slot-steps
    that can deliver nothing and the expert layers route none of their
    pairs; the conv operators and the dense weights run on as they did.
    Every served token is still the reference's choice to ATOL, and the
    tokens are, bit for bit, those of the same call with every slot
    said to be live throughout, which masks no pair and counts the
    owners' pairs whether they deliver or not."""
    prompts = [_seq(41, 20), _seq(9, 21), _seq(23, 22), _seq(35, 23),
               _seq(17, 24)]
    n_new = [6, 3, 9, 2, 5]
    told = _serve(tree, prompts, n_new)
    assert [len(o) - len(p) for o, p in zip(told, prompts)] == n_new
    assert _gaps(tree, prompts, told).max() <= ATOL
    assert 0 < told.metrics.attend_dead_share < 1
    monkeypatch.setattr(serving.RequestBook, "left",
                        lambda self: np.full(self.n_slots, self.chunk,
                                             np.int32))
    untold = _serve(tree, prompts, n_new)
    assert untold.metrics.attend_pages_dead == 0
    assert untold.metrics.attend_pages_walked == (
        told.metrics.attend_pages_walked + told.metrics.attend_pages_dead)
    assert all((a == b).all() for a, b in zip(told, untold))
    t, u = told.metrics, untold.metrics
    assert t.moe_assignments == 2 * 8 * t.decode_tokens < u.moe_assignments
    assert t.moe_pairs_dead > 0 == u.moe_pairs_dead
    assert t.moe_assignments + t.moe_pairs_dead == 2 * 2 * t.moe_layer_steps
    assert t.moe_experts_live < u.moe_experts_live


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["prefix_cache_on", "prefix_cache_off"])
def test_serve_loop_preempts_and_resumes_to_the_same_tokens(tree,
                                                            prefix_cache):
    """A pool too small for both requests' growth: the later one is
    preempted (its pages and its conv state dropped) and resumed by a
    prefill (under this pressure the trie has been drained, so a cold
    one; the resume that hits its own pages is the test above), and
    every token it was served is still the reference's choice."""
    prompts = [_seq(30, 20), _seq(29, 21)]
    outs = _serve(tree, prompts, 40, n_pages=8, prefix_cache=prefix_cache)
    m = outs.metrics
    assert m.preemptions >= 1 and m.rejections == 0
    assert m.conv_tail_restores == m.prefix_hits
    assert _gaps(tree, prompts, outs).max() <= ATOL
    calm = _serve(tree, prompts, 40, prefix_cache=prefix_cache)
    assert calm.metrics.preemptions == 0
    assert all((a == b).all() for a, b in zip(outs, calm))


def test_an_idle_slots_experts_are_not_counted():
    idx = jnp.asarray([[0, 1], [2, 3], [0, 5]])
    owns = jnp.asarray([True, False, True])
    tally = kvpage._moe_tally(idx, owns, 8)
    # pairs, live, max, 1, and the pairs a mask left out: there is none
    assert list(np.asarray(tally)) == [4, 3, 2, 1, 0]
    tally = kvpage._moe_tally(idx, jnp.asarray([False] * 3), 8)
    assert list(np.asarray(tally)) == [0, 0, 0, 1, 0]


@pytest.mark.parametrize("live,want", [
    ([True, False, True], [4, 3, 2, 1, 2]),      # the idle slot's 2 pairs
    ([True, False, False], [2, 2, 1, 1, 4]),     # + an owner that ended
    ([False, False, False], [0, 0, 0, 1, 6]),    # a chunk's last steps
    ([True, True, True], [4, 3, 2, 1, 0]),       # told nothing is dead
], ids=["idle", "ended", "none_live", "all_live"])
def test_the_tally_counts_what_the_mask_leaves_and_what_it_takes(live, want):
    """With the mask the expert layer was handed, the tally counts the
    pairs of slots that own a request AND are live (what the kernel
    computes), and, last, ``top_k`` pairs for every slot the mask left
    out, owner or not."""
    idx = jnp.asarray([[0, 1], [2, 3], [0, 5]])
    owns = jnp.asarray([True, False, True])
    tally = kvpage._moe_tally(idx, owns, 8, live=jnp.asarray(live))
    assert list(np.asarray(tally)) == want


# -- the expert layer ---------------------------------------------------------

def _experts(E, d, f, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
    return dict(gate=mk(d, E), bias=jnp.asarray(
        rng.uniform(-0.05, 0.05, E), jnp.float32), w1=mk(E, d, f),
        w3=mk(E, d, f), w2=mk(E, f, d))


def _dense_loop(x, e, idx, p):
    """The layer as a loop over tokens and their experts, float64."""
    x, out = np.asarray(x, np.float64), np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for i, w in zip(np.asarray(idx[t]), np.asarray(p[t], np.float64)):
            h = x[t] @ np.asarray(e["w1"][i], np.float64)
            g = x[t] @ np.asarray(e["w3"][i], np.float64)
            out[t] += w * ((h / (1 + np.exp(-h)) * g)
                           @ np.asarray(e["w2"][i], np.float64))
    return out


def _ref_moe(x, e, k, first=0, count=None):
    """The reference's expert layer on the same leaves."""
    count = count or e["w1"].shape[0]
    lp = {n: (v[first:first + count] if n in ("w1", "w3", "w2") else v)[None]
          for n, v in e.items()}
    h = dict(top_k=k, norm_topk=True, scale=1.0, use_bias=True, first=first)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref._moe(x, lp, 0, h))


def test_uneven_routing_drops_no_token():
    """One expert takes most tokens, one none, T a multiple of nothing:
    every routed pair is computed (the dense loop's sum)."""
    E, d, f, k, T = 8, 16, 24, 2, 37
    e = _experts(E, d, f)
    e["bias"] = e["bias"].at[0].set(5.0).at[5].set(-5.0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((T, d)),
                    jnp.float32)
    idx, p = moe.route_sigmoid_topk(x, e["gate"], e["bias"], k)
    hits = np.bincount(np.asarray(idx).ravel(), minlength=E)
    assert hits[0] == T and hits[5] == 0 and hits.sum() == T * k
    got = np.asarray(moe.sorted_expert_ffn(x, e["w1"], e["w3"], e["w2"],
                                           idx, p))
    np.testing.assert_allclose(got, _dense_loop(x, e, idx, p), atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(got, _ref_moe(x, e, k), atol=1e-5, rtol=0)
    # the Pallas grouped matmul (interpret mode here) computes the same
    np.testing.assert_allclose(
        np.asarray(moe.sorted_expert_ffn(
            x, e["w1"], e["w3"], e["w2"], idx, p,
            grouped_matmul=moe.megablox_matmul)), got, atol=1e-5, rtol=0)


SHARES = _experts(64, 32, 16, seed=3)
SHARES_X = jnp.asarray(np.random.default_rng(4).standard_normal((21, 32)),
                       jnp.float32)


@pytest.mark.parametrize("share", range(8))
def test_a_share_of_8_experts_is_the_references_share(share):
    """The layer told it holds experts ``8 * share ..``: routes over all
    64, returns its own experts' part, as the reference given the same
    (first, count)."""
    e, x, first = SHARES, SHARES_X, 8 * share
    idx, p = moe.route_sigmoid_topk(x, e["gate"], e["bias"], 4)
    sl = slice(first, first + 8)
    got = np.asarray(moe.sorted_expert_ffn(
        x, e["w1"][sl], e["w3"][sl], e["w2"][sl], idx, p, first=first))
    np.testing.assert_allclose(got, _ref_moe(x, e, 4, first, 8), atol=1e-5,
                               rtol=0)
    mine = (np.asarray(idx) >= first) & (np.asarray(idx) < first + 8)
    assert (np.abs(got).sum(-1) > 0).tolist() == mine.any(-1).tolist()


def test_eight_shares_of_8_experts_add_up_to_the_whole_layer():
    e, x = SHARES, SHARES_X
    idx, p = moe.route_sigmoid_topk(x, e["gate"], e["bias"], 4)
    whole = np.asarray(moe.sorted_expert_ffn(x, e["w1"], e["w3"], e["w2"],
                                             idx, p))
    parts = sum(np.asarray(moe.sorted_expert_ffn(
        x, e["w1"][s:s + 8], e["w3"][s:s + 8], e["w2"][s:s + 8], idx, p,
        first=s)) for s in range(0, 64, 8))
    np.testing.assert_allclose(parts, whole, atol=1e-5, rtol=0)
    np.testing.assert_allclose(whole, _ref_moe(x, e, 4), atol=1e-5, rtol=0)
    np.testing.assert_allclose(whole, _dense_loop(x, e, idx, p), atol=1e-5,
                               rtol=0)


# how the layer is handed its experts -> (stack depth, the layer's index
# in it, first held expert, how many held); None: not stacked, all held
HANDED = {"whole": (None, None, 0, 64), "stacked": (3, 1, 0, 64),
          "share": (None, None, 16, 8), "stacked_share": (2, 1, 40, 16)}
# which of SHARES_X's 21 tokens anybody receives
LIVE = {"some": [0, 3, 4, 10, 17], "one": [12], "none": []}


@pytest.mark.parametrize("who", sorted(LIVE))
@pytest.mark.parametrize("how", sorted(HANDED))
def test_a_dead_tokens_pairs_reach_no_expert(how, who):
    """``sorted_expert_ffn(live=)``: a live token's rows are BIT-equal
    to the call without it, a dead token's exactly zero; the ``sizes``
    each of the three grouped matmuls is handed count the live tokens'
    held pairs and nothing else, so an expert that only dead tokens
    chose has no row (its matrices are not read); and with NO live
    token the result is finite zeros. Whole, as one layer of a stack,
    as a share of the experts, and both."""
    depth, layer, first, count = HANDED[how]
    e, x = SHARES, SHARES_X
    idx, p = moe.route_sigmoid_topk(x, e["gate"], e["bias"], 4)
    live = np.zeros(len(x), bool)
    live[LIVE[who]] = True
    w = [e[n][first:first + count] for n in ("w1", "w3", "w2")]
    kw = dict(first=first)
    if depth:
        w = [jnp.full((depth,) + a.shape, 7.0).at[layer].set(a) for a in w]
        kw["layer"] = jnp.int32(layer)
    seen = []

    def spy(xs, w, sizes):
        seen.append(np.asarray(sizes))
        return moe.ragged_dot_matmul(xs, w, sizes)

    want = np.asarray(moe.sorted_expert_ffn(x, *w, idx, p, **kw))
    got = np.asarray(moe.sorted_expert_ffn(
        x, *w, idx, p, live=jnp.asarray(live), grouped_matmul=spy, **kw))
    np.testing.assert_array_equal(got[live], want[live])
    assert (got[~live] == 0.0).all() and np.isfinite(got).all()
    chosen = np.asarray(idx)
    mine = np.bincount(chosen[live].ravel(), minlength=64)[
        first:first + count]
    sizes = mine
    if depth:
        sizes = np.zeros(depth * count, int)
        sizes[layer * count:(layer + 1) * count] = mine
    assert len(seen) == 3
    for s in seen:
        np.testing.assert_array_equal(s, sizes)
    # some held expert was chosen by dead tokens alone: it has no row
    everyone = np.bincount(chosen.ravel(), minlength=64)[first:first + count]
    assert ((everyone > 0) & (mine == 0)).any()
    if who == "none":
        assert not got.any() and not sizes.any()


@pytest.mark.parametrize("who", ["some", "none"])
def test_the_pallas_grouped_matmul_takes_dead_tokens_too(who):
    """The same through ``megablox.gmm`` (interpret mode here): with NO
    live pair its grid has no active tile, and the rows it leaves
    unwritten never reach the sum."""
    e, x = SHARES, SHARES_X
    idx, p = moe.route_sigmoid_topk(x, e["gate"], e["bias"], 4)
    live = np.zeros(len(x), bool)
    live[LIVE[who]] = True
    w = [e[n] for n in ("w1", "w3", "w2")]
    want = np.asarray(moe.sorted_expert_ffn(x, *w, idx, p))
    got = np.asarray(moe.sorted_expert_ffn(
        x, *w, idx, p, live=jnp.asarray(live),
        grouped_matmul=moe.megablox_matmul))
    np.testing.assert_allclose(got[live], want[live], atol=1e-5, rtol=0)
    assert (got[~live] == 0.0).all() and np.isfinite(got).all()


def test_router_tie_break_and_normalisation_are_the_references():
    """Equal scores: the lower index wins, in the program
    (``lax.top_k``) as in the reference (a stable sort). The bias
    selects and does not weigh. The weights sum to S / (S + 1e-6), not
    to 1."""
    d, E, k = 4, 6, 2
    gate = jnp.zeros((d, E)).at[0].set(jnp.asarray([1., 1., 1., 0, -1, -1]))
    x = jnp.asarray([[2.0, 0, 0, 0], [-2.0, 0, 0, 0], [0.0, 0, 0, 0]])
    bias = jnp.zeros((E,))
    idx, p = moe.route_sigmoid_topk(x, gate, bias, k)
    assert np.asarray(idx).tolist() == [[0, 1], [4, 5], [0, 1]]
    h = dict(top_k=k, norm_topk=True, scale=1.0, use_bias=True, first=0)
    comb = np.asarray(ref.route(x, gate, bias, h))
    assert [sorted(np.nonzero(r)[0]) for r in comb] == [[0, 1], [4, 5],
                                                        [0, 1]]
    np.testing.assert_allclose(
        np.take_along_axis(comb, np.asarray(idx), -1), np.asarray(p),
        atol=1e-7, rtol=0)
    s = 1 / (1 + np.exp(-2.0))
    np.testing.assert_allclose(np.asarray(p)[0], [s / (2 * s + 1e-6)] * 2,
                               atol=1e-7)
    assert abs(float(np.asarray(p, np.float64)[2].sum())
               - 1.0 / (1.0 + 1e-6)) < 1e-7 and float(p[2].sum()) < 1.0
    # the bias selects (expert 3 wins with it) and does not weigh
    idx, p = moe.route_sigmoid_topk(x[2:], gate, bias.at[3].set(0.1), k)
    assert np.asarray(idx).tolist() == [[3, 0]]
    np.testing.assert_allclose(np.asarray(p), [[0.5 / (1 + 1e-6)] * 2],
                               atol=1e-7)
    comb = np.asarray(ref.route(x[2:], gate, bias.at[3].set(0.1), h))
    assert sorted(np.nonzero(comb[0])[0]) == [0, 3]


def test_an_expert_left_out_does_not_pass(tree, monkeypatch):
    """The control of the forward test: with every token's last expert
    left out the logits are off the reference by decades more than the
    tolerance."""
    route = moe.route_sigmoid_topk

    def dropping(*a, **kw):
        idx, p = route(*a, **kw)
        return idx, p.at[:, -1].set(0.0)
    monkeypatch.setattr(moe, "route_sigmoid_topk", dropping)
    seq = _seq(24, 30)
    got = np.asarray(lfm2.forward(tree, CFG, jnp.asarray(seq)[None])[0])
    assert np.abs(got - _ref_logits(tree, seq, 0, 24)).max() > 100 * ATOL
