"""Jamba (models/jamba.py) against the plain reference
(benchmarks/reference/jamba.py) on seeded weights, by LOGITS, at a tiny
size on the CPU: the plain forward; prefill then paged decode through
the functions ``serve_paged_greedy`` runs; the Mamba state at page ends
and behind a right-padded bucket; a radix hit cut back to a page that
holds a snapshot, a copy-on-write copy and a resume; the snapshot store
run dry; the serve loop against one-request-at-a-time decoding; and the
selective scan's two Pallas calls (interpret mode here) against plain
``lax.scan``. What LFM2's tests already say of the shared plane
(tests/test_lfm2.py) is said here of this family's two-leaf state and
of the snapshot store that is not sized by the page count.

Tolerances, each beside its reason: in float32 the program and the
reference compute the same sums in another order (a scan blocked by
tokens against a token-by-token ``lax.scan``, a different attention
formulation on a hit), which reads 1e-6..1e-5 on logits of size ~4:
``ATOL`` = 2e-4 leaves a decade and more of room, and the controls read
1e-2..5 (two decades above it). In bfloat16 weights and activations are
rounded to 8 bits of mantissa over 8 layers: a row of logits reads
0.01-0.05 relative RMS, held to 0.1.
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

from benchmarks import control_jamba, weights_jamba  # noqa: E402
from benchmarks.entries import serve_paged_greedy_jamba as entry  # noqa: E402
from benchmarks.reference import jamba as ref  # noqa: E402
from mpi_acx_tpu.models import jamba, kvpage, serving  # noqa: E402
from mpi_acx_tpu.ops import ssm  # noqa: E402

ATOL = 2e-4
PT, MAX_LEN = 16, 128

# The tiny preset as a configuration FILE's keys (what the benchmark's
# entry and reference read): two periods (mamba, attn, mamba, mamba),
# d = 64, 128 channels of 8 numbers, 4 query heads on one K/V head, a
# snapshot every second page. ``init_scale`` 1/sqrt(d): the layers, not
# the tied embedding's echo, decide the logits.
C = dict(vocab_size=96, hidden_size=64, num_hidden_layers=8,
         num_attention_heads=4, num_key_value_heads=1, intermediate_size=96,
         attn_layer_period=4, attn_layer_offset=1, mamba_expand=2,
         mamba_d_state=8, mamba_d_conv=4, mamba_dt_rank=8, rms_norm_eps=1e-6,
         max_position_embeddings=256, init_scale=0.125,
         serve={"snapshot_every": 2})
PLAN, HP = weights_jamba.plan(C), ref.hyper(C)
CFG = entry.program_config(C, "float32")


@pytest.fixture(scope="module")
def tree():
    return weights_jamba.make_jamba(C, 7, jnp.float32)


def _seq(n, seed):
    return np.random.default_rng(seed).integers(
        0, C["vocab_size"], n).astype(np.int32)


def _ref_logits(tree, seq, first, rows):
    return np.asarray(ref.logits_from(
        tree, jnp.asarray(seq), first, jnp.zeros((rows,), jnp.int8),
        plan=PLAN, hp=HP))


# -- the program's config and layout ------------------------------------------

def test_tiny_preset_and_the_file_mapping_agree():
    assert CFG == jamba.tiny_jamba(dtype=jnp.dtype("float32"))
    mine = jamba.init_params(jax.random.key(0), CFG)
    theirs = jax.eval_shape(lambda: weights_jamba.make_jamba(C, 0,
                                                             jnp.float32))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: a.shape, mine) == jax.tree.map(
        lambda a: a.shape, theirs)
    # what stays float32 when the tree is cast for inference
    cast = jamba.cast_params(mine)
    kept = {path[-1].key for path, leaf in
            jax.tree_util.tree_leaves_with_path(cast)
            if leaf.dtype == jnp.float32}
    assert kept == {"A_log", "D", "b_dt", "norm1", "norm2", "dt_norm",
                    "b_norm", "c_norm", "final_norm"}


@pytest.mark.parametrize("cfg,want,attention_at", [
    (jamba.jamba2_3b(), [(14, 2)], [7, 21]),
    (CFG, [(4, 2)], [1, 5]),
    (jamba.tiny_jamba(n_layers=7),
     [(1, 1), (1, 1), (1, 3), (1, 1), (1, 1)], [1, 5]),
], ids=["published_28", "tiny", "a_ragged_end"])
def test_layers_compress_into_whole_periods(cfg, want, attention_at):
    kinds = jamba.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds)
            if k.operator == "attention"] == attention_at
    segs = jamba.segments(cfg)
    assert [(len(s.period), s.repeats) for s in segs] == want
    assert [k for s in segs for _ in range(s.repeats)
            for k in s.period] == list(kinds)
    # the benchmark's weights find the same stretches by themselves
    c = dict(C, num_hidden_layers=cfg.n_layers,
             attn_layer_period=cfg.attn_layer_period,
             attn_layer_offset=cfg.attn_layer_offset)
    assert [(len(p), r) for _, p, r in weights_jamba.stretches(c)] == want


def test_the_spec_and_int8_pages_by_name():
    spec = kvpage.paged_spec(jamba, CFG)
    assert (spec.n_page_layers, spec.n_state_layers, spec.n_rep,
            spec.snapshot_every) == (2, 6, 4, 2)
    assert spec.built("operator") == "attention+mamba"
    assert spec.built("ffn") == "dense:_ffn"
    assert {k: (v.shape, v.dtype) for k, v in spec.state.items()} == {
        "conv": ((3 * 128,), jnp.float32), "ssm": ((8, 128), jnp.float32)}
    assert spec.state_bytes_slot == 6 * (3 * 128 + 8 * 128) * 4
    # the published widths: 9.3 MB a slot, the window in bfloat16
    big = kvpage.paged_spec(jamba, jamba.jamba2_3b())
    assert big.state_bytes_slot == 9_318_400
    assert big.state["conv"].dtype == jnp.bfloat16
    assert big.state["ssm"].dtype == jnp.float32
    with pytest.raises(NotImplementedError, match="kv_int8.*jamba"):
        kvpage.PagedKV(CFG, jamba, 2, MAX_LEN, PT, 8, kv_int8=True)
    with pytest.raises(NotImplementedError, match="kv_int8.*jamba"):
        serving.serve_paged_greedy({}, CFG, [_seq(5, 0)], 2, n_slots=1,
                                   max_len=MAX_LEN, family=jamba,
                                   page_tokens=PT, kv_int8=True)


# -- the selective scan's two calls against plain lax.scan --------------------

def _scan_args(S, C_, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(u=f(S, C_), dt=jax.nn.softplus(f(S, C_) - 2), z=f(S, C_),
                b=f(S, N), c=f(S, N), a=-jnp.exp(0.3 * f(N, C_)), d=f(C_),
                h0=f(N, C_))


@pytest.mark.parametrize("S,block,snapshot", [
    (64, 16, 32), (64, 16, 16), (48, 16, 32), (64, 16, None), (8, 16, 32),
    (80, 16, 64)])
def test_ssm_scan_kernel_is_the_plain_scan(S, block, snapshot):
    """The Pallas call (interpret mode) over blocks of tokens against
    ``lax.scan`` over tokens: y, every snapshot, the end state. 1e-5:
    the same float32 operations in the same order a channel."""
    a = _scan_args(S, 256, 8)
    want = ssm.ssm_scan_ref(*a.values(), snapshot=snapshot)
    got = ssm.ssm_scan(*a.values(), snapshot=snapshot, block=block)
    assert got[1].shape == ((S // snapshot if snapshot else 0), 8, 256)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=0)


def test_padding_with_dt_zero_leaves_the_state_of_the_last_real_token():
    a = _scan_args(32, 128, 8, seed=1)
    n = 21
    cut = {k: (v[:n] if k in ("u", "dt", "z", "b", "c") else v)
           for k, v in a.items()}
    padded = dict(a, dt=a["dt"].at[n:].set(0.0))
    for scan in (ssm.ssm_scan_ref, ssm.ssm_scan):
        end = scan(*padded.values())[2]
        # (1e-6: two programs' float32 roundings, not the padding)
        np.testing.assert_allclose(
            np.asarray(end), np.asarray(ssm.ssm_scan_ref(*cut.values())[2]),
            atol=1e-6, rtol=0)
    # and the state moves on where dt is not zero
    assert np.abs(np.asarray(ssm.ssm_scan_ref(*a.values())[2] - end)).max() \
        > 1e-2


@pytest.mark.parametrize("layer", [0, 2])
def test_ssm_update_kernel_is_the_plain_update_in_place(layer):
    """One token a slot through one layer of a stacked state: the call
    (interpret mode) against the plain update, and the other layers'
    rows untouched."""
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    L, B, N, C_ = 3, 8, 8, 256
    h = f(L, B, N, C_)
    args = (jax.nn.softplus(f(B, C_)), f(B, C_), f(B, C_), f(B, N), f(B, N),
            -jnp.exp(0.3 * f(N, C_)), f(C_))
    want_y, want_h = ssm.ssm_update_ref(h, layer, *args)
    got_y, got_h = ssm.ssm_update(h, jnp.int32(layer), *args)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               atol=1e-6, rtol=0)
    others = [l for l in range(L) if l != layer]
    np.testing.assert_array_equal(np.asarray(got_h)[others],
                                  np.asarray(h)[others])
    assert ssm.select_ssm(True) == (ssm.ssm_update, ssm.ssm_scan)
    assert ssm.select_ssm(None) == ssm.select_ssm(False) == (
        ssm.ssm_update_ref, ssm.ssm_scan_ref)        # off the chip


# -- forward ------------------------------------------------------------------

@pytest.mark.parametrize("dtype,kernel", [
    ("float32", False), ("float32", True), ("bfloat16", False)])
def test_forward_against_the_reference(tree, dtype, kernel):
    seq = _seq(40, 1)
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype), ssm_kernel=kernel)
    params = (jamba.cast_params(tree) if dtype == "bfloat16" else tree)
    got = np.asarray(jamba.forward(params, cfg, jnp.asarray(seq)[None])[0])
    # the reference reads the SAME (rounded) weights, in float32
    want = _ref_logits(params, seq, 0, 40)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    else:
        centre = lambda a: a - a.mean(-1, keepdims=True)
        rows = np.sqrt(np.square(centre(got - want)).sum(-1)
                       / np.square(centre(want)).sum(-1))
        assert np.isfinite(rows).all() and 1e-4 < rows.min()
        assert rows.max() < 0.1, np.sort(rows)


def test_a_batch_is_its_rows(tree):
    seqs = np.stack([_seq(24, 2), _seq(24, 3)])
    got = np.asarray(jamba.forward(tree, CFG, jnp.asarray(seqs)))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(tree, seqs[b], 0, 24),
                                   atol=ATOL, rtol=0)


@pytest.mark.parametrize("control", sorted(control_jamba.BROKEN))
def test_a_broken_scan_does_not_pass(tree, monkeypatch, control):
    """The controls of the forward test, as ``control_jamba.py`` breaks
    the scan on the chip: the state rounded to bfloat16 after every
    token, or the ``D u`` term left out, reads logits off the
    reference by far more than the tolerance."""
    monkeypatch.setattr(ssm, "_token", control_jamba.BROKEN[control])
    seq = _seq(40, 30)
    got = np.asarray(jamba.forward(tree, CFG, jnp.asarray(seq)[None])[0])
    least = {"bf16_h": 20 * ATOL, "no_D_u": 1.0}[control]
    assert np.abs(got - _ref_logits(tree, seq, 0, 40)).max() > least


@pytest.mark.parametrize("terms", [("D",), ("norms",)],
                         ids=["the_inner_norms", "the_D_u_term"])
def test_what_the_reference_can_leave_out_matters(tree, terms):
    """Jamba's three inner norms and the ``D u`` term each move a logit
    by ~1: a program without either cannot pass for the reference."""
    seq = _seq(40, 31)
    got = np.asarray(ref.logits_from(
        tree, jnp.asarray(seq), 0, jnp.zeros((40,), jnp.int8), plan=PLAN,
        hp=HP, terms=terms))
    assert np.abs(got - _ref_logits(tree, seq, 0, 40)).max() > 0.5


# -- prefill + paged decode, through the functions the serve loop runs --------

def _refill(pkv, params, b, prompt, reserve, cfg=CFG):
    """``serve_paged_greedy``'s refill, call for call: match (cut back
    to a page that holds a snapshot), prefill (the suffix alone on a
    hit, from that snapshot), scatter, seat, insert. Returns the
    prefill's logits [vocab] and the pages hit."""
    kw = dict(cfg=cfg, family=jamba, kv_int8=False, on_tpu=False,
              page_tokens=PT)
    hit = pkv.prefix.match(prompt) if pkv.prefix is not None else []
    fresh = pkv.alloc_evicting(
        kvpage.pages_needed(len(prompt) + reserve, PT) - len(hit))
    P = len(hit) * PT
    if hit:
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
    pkv.scatter_prompt(one, fresh, whole=(len(prompt) - P) // PT)
    pkv.seat(b, hit, fresh, len(prompt), state=end)
    if pkv.prefix is not None:
        pkv.prefix.insert(prompt, pkv.pages[b])
    return np.asarray(logits[0, 0]), len(hit)


_STEP = jax.jit(lambda p, s, t: kvpage.paged_decode_step(p, CFG, s, t, PT,
                                                         jamba))


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


def _pkv(n_slots=2, prefix_cache=True, n_snapshots=None, n_pages=None):
    return kvpage.PagedKV(CFG, jamba, n_slots, MAX_LEN, PT,
                          n_pages or 8 * n_slots, prefix_cache=prefix_cache,
                          n_snapshots=n_snapshots)


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
    assert pkv.tail_restores == 0 and not pkv.moe_chunks


def test_pages_and_state_are_the_references_and_padding_leaves_no_mark(tree):
    """A prompt of 70 tokens right-padded to its bucket of 128, then
    seven decode steps. The multi-query pages equal the reference's
    keys and values; the snapshots taken at the ends of pages 1 and 3
    (tokens 31, 63) equal the reference's scan state and conv inputs
    there; the slot's state after the prefill is the reference's at
    token 69, whatever the 58 padded positions held, and after the
    steps at token 76."""
    seq, n, steps = _seq(90, 4), 70, 7
    pkv = _pkv(n_slots=1, n_pages=16)
    _refill(pkv, tree, 0, seq[:n], steps)
    assert sorted(pkv.snaps.row_of) == [pkv.pages[0][1], pkv.pages[0][3]]
    T = n + steps
    at = (31, 63, n - 1, T - 1)
    k, v, u, h = (np.asarray(a) for a in ref.states(
        tree, jnp.asarray(seq[:T]), plan=PLAN, hp=HP, h_at=at))
    window = lambda t: u[:, t - 2:t + 1].reshape(u.shape[0], -1)
    for j, page in enumerate((1, 3)):
        snap = pkv.restore_tail(pkv.pages[0][page])
        np.testing.assert_allclose(np.asarray(snap["ssm"]),
                                   h[:, j].transpose(0, 2, 1), atol=1e-5)
        np.testing.assert_allclose(np.asarray(snap["conv"]), window(at[j]),
                                   atol=1e-5)
    held = lambda name: np.asarray(pkv.held[name])[:, 0]
    np.testing.assert_allclose(held("ssm"), h[:, 2].transpose(0, 2, 1),
                               atol=1e-5)
    np.testing.assert_allclose(held("conv"), window(n - 1), atol=1e-5)
    _decode(pkv, tree, [seq], [n], steps)
    np.testing.assert_allclose(held("ssm"), h[:, 3].transpose(0, 2, 1),
                               atol=1e-5)
    np.testing.assert_allclose(held("conv"), window(T - 1), atol=1e-5)
    gk, gv = pkv.gather_history(pkv.pages[0])          # [L, Hkv, Dh, pages*PT]
    np.testing.assert_allclose(np.asarray(gk)[..., :T].transpose(0, 3, 1, 2),
                               k, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(gv)[..., :T].transpose(0, 3, 1, 2),
                               v, atol=1e-5, rtol=0)


@pytest.mark.parametrize("shared,hit_pages", [(64, 4), (57, 2), (40, 2),
                                              (31, 0)])
def test_a_radix_hit_is_cut_back_to_a_snapshot_and_reads_as_cold(
        tree, shared, hit_pages):
    """Two prompts sharing ``shared`` tokens: the trie keeps the first's
    pages up to its last snapshot (every second page), the second is
    seated from the deepest shared page that holds one, prefills the
    rest alone, and reads what a cold prefill of it reads."""
    a = _seq(80, 5)
    b = np.concatenate([a[:shared], _seq(30, 6)])
    la, lb, steps = 75, shared + 9, 5
    pkv = _pkv(n_pages=24)
    _refill(pkv, tree, 0, a[:la], steps)
    first, hits = _refill(pkv, tree, 1, b[:lb], steps)
    assert hits == hit_pages and pkv.tail_restores == (hits > 0)
    warm = _decode(pkv, tree, [a, b], [la, lb], steps)[:, 1]
    want = _ref_logits(tree, b, lb - 1, steps + 1)
    np.testing.assert_allclose(np.concatenate([first[None], warm]), want,
                               atol=ATOL, rtol=0)


def test_a_request_preempted_and_resumed_reads_as_uninterrupted(tree):
    """Seat, decode, release (a preemption drops the slot's pages and
    its state), seat again: the resume hits the request's own pages up
    to the last snapshot and prefills the rest again; with the store
    emptied (no snapshot left) it prefills everything again. Either
    way every step reads what it read the first time."""
    seq, n, steps = _seq(70, 8), 45, 4
    pkv = _pkv(n_slots=1)
    first, _ = _refill(pkv, tree, 0, seq[:n], steps)
    before = _decode(pkv, tree, [seq], [n], steps)
    want = _ref_logits(tree, seq, n, steps)
    for take_away, hits_want in ((False, 2), (True, 0)):
        pkv.release(0)
        if take_away:
            for page in list(pkv.snaps.row_of):
                pkv.snaps.drop(page)
        again, hits = _refill(pkv, tree, 0, seq[:n], steps)
        assert hits == hits_want
        after = _decode(pkv, tree, [seq], [n], steps)
        np.testing.assert_allclose(again, first, atol=ATOL, rtol=0)
        np.testing.assert_allclose(after, before, atol=ATOL, rtol=0)
        np.testing.assert_allclose(after[:, 0], want, atol=ATOL, rtol=0)
    # the trie's node whose page had lost its snapshot was handed the
    # page that holds the new one
    assert pkv.prefix.match(seq[:n])[-1] == pkv.pages[0][1]


def test_a_copy_on_write_copy_continues_to_the_same_logits(tree):
    """A slot's shared page copied before a write (the defensive guard):
    the copy needs no snapshot, the slot's own state carries on."""
    seq, n, steps = _seq(70, 9), 40, 5
    pkv = _pkv(n_slots=1)
    _refill(pkv, tree, 0, seq[:n], steps)
    was, rows = pkv.pages[0][1], dict(pkv.snaps.row_of)
    assert pkv.alloc.refcount(was) == 2 and pkv.ensure_writable(0, 1)
    assert pkv.pages[0][1] != was and pkv.snaps.row_of == rows
    got = _decode(pkv, tree, [seq], [n], steps)
    np.testing.assert_allclose(got[:, 0], _ref_logits(tree, seq, n, steps),
                               atol=ATOL, rtol=0)


def test_a_wrong_snapshot_does_not_pass(tree, monkeypatch):
    """The control of the hit and resume tests: a hit that starts from
    zeros, or from a scan state in bfloat16, reads logits off the
    reference by far more than the tolerance."""
    seq, n = _seq(60, 9), 2 * PT + 9
    want = _ref_logits(tree, seq, n - 1, 1)[0]
    restore = kvpage.PagedKV.restore_tail
    zeros = lambda self, page: jax.tree.map(jnp.zeros_like,
                                            restore(self, page))

    def low(self, page):
        snap = restore(self, page)
        return dict(snap, ssm=snap["ssm"].astype(jnp.bfloat16).astype(
            jnp.float32))
    for wrong, least in ((zeros, 1e-1), (low, 5 * ATOL)):
        pkv = _pkv(n_slots=1)
        _refill(pkv, tree, 0, seq[:n], 0)
        pkv.release(0)
        monkeypatch.setattr(kvpage.PagedKV, "restore_tail", wrong)
        got, hits = _refill(pkv, tree, 0, seq[:n], 0)
        monkeypatch.setattr(kvpage.PagedKV, "restore_tail", restore)
        assert hits == 2 and np.abs(got - want).max() > least


# -- the snapshot store --------------------------------------------------------

def test_snapshot_rows_run_dry_and_the_least_recently_used_goes(tree):
    """Two rows for three prompts that each want one: the third takes
    the row of the prompt matched longest ago, whose pages then give no
    hit (never a wrong one), while the other two still resume."""
    prompts = [_seq(40, s) for s in (40, 41, 42)]
    pkv = _pkv(n_slots=1, n_pages=16, n_snapshots=2)
    assert pkv.snaps.n_rows == 2
    assert jax.tree.leaves(pkv.snaps.rows)[0].shape[:2] == (6, 3)  # + sink
    for p in prompts[:2]:
        _refill(pkv, tree, 0, p, 0)
        pkv.release(0)
    touched = pkv.prefix.match(prompts[0])             # prompt 0: used last
    assert len(touched) == 2
    for page in touched:
        pkv.alloc.decref(page)
    _refill(pkv, tree, 0, prompts[2], 0)
    pkv.release(0)
    s = pkv.snaps
    assert (s.taken, s.evictions, s.rows_hwm, len(s.row_of)) == (3, 1, 2, 2)
    for p, pages in zip(prompts, (2, 0, 2)):
        hit = pkv.prefix.match(p)
        assert len(hit) == pages
        for page in hit:
            pkv.alloc.decref(page)
    # every prompt, resumed or prefilled again, reads the reference's
    # logits; the evicted one takes a row anew (and the oldest goes)
    for p in (prompts[1], prompts[2]):
        got, hits = _refill(pkv, tree, 0, p, 0)
        assert hits == (0 if p is prompts[1] else 2)
        np.testing.assert_allclose(got, _ref_logits(tree, p, len(p) - 1, 1)[0],
                                   atol=ATOL, rtol=0)
        pkv.release(0)
    assert s.evictions == 2 and len(pkv.prefix.match(prompts[1])) == 2


def test_a_store_of_no_rows_keeps_nothing_and_serves(tree):
    pkv = _pkv(n_slots=1, n_snapshots=0)
    seq = _seq(60, 43)
    for _ in range(2):
        got, hits = _refill(pkv, tree, 0, seq[:45], 0)
        assert hits == 0 and not pkv.snaps.row_of
        pkv.release(0)
    np.testing.assert_allclose(got, _ref_logits(tree, seq, 44, 1)[0],
                               atol=ATOL, rtol=0)
    # pages past a prompt's last snapshot never enter the trie
    assert pkv.alloc.used_count == 0 and not pkv.prefix.root.children


def test_a_freed_page_frees_its_row_and_the_default_never_evicts():
    pkv = _pkv(n_pages=9)
    assert pkv.n_snapshots == 5            # ceil(9 pages / every 2nd)
    store = pkv.snaps
    pages = pkv.alloc.alloc(4)
    rows = [store.take(p) for p in pages]
    assert rows == [0, 1, 2, 3] and store.take(pages[0]) == 0
    pkv.alloc.decref(pages[1])
    assert not store.has(pages[1]) and store.has(pages[2])
    assert store.take(pkv.alloc.alloc(1)[0]) == 1      # lowest free row
    pkv.reset_pool()
    assert not pkv.snaps.row_of and pkv.snaps.taken == 6
    # GPT-2 keeps no store; LFM2's has a row a page
    from mpi_acx_tpu.models import lfm2
    from mpi_acx_tpu.models import transformer as tfm
    assert kvpage.PagedKV(tfm.tiny_config(), None, 1, 32, 8, 4).snaps is None
    assert kvpage.PagedKV(lfm2.tiny_lfm2(), lfm2, 1, 32, 8, 4,
                          ).snaps.n_rows == 4


# -- the serve loop itself ----------------------------------------------------

def _serve(tree, prompts, n_new, **kw):
    kw = dict(dict(n_slots=2, max_len=MAX_LEN, family=jamba, chunk=4,
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
    """Through ``serve_paged_greedy`` itself: seven requests into two
    slots, two of them sharing whole pages with an earlier one (64
    tokens: four pages hit; 40: cut back to two); every served token is
    the reference's choice to ATOL and the tokens are those of serving
    one request at a time; the metrics name what was built and count
    the snapshot store's book."""
    base = _seq(80, 10)
    prompts = [base[:70], _seq(9, 11), _seq(23, 12),
               np.concatenate([base[:64], _seq(6, 13)]),
               np.concatenate([base[:40], _seq(15, 14)]), _seq(35, 15),
               _seq(64, 16)]
    outs = _serve(tree, prompts, 7, n_snapshots=6)
    m = outs.metrics
    assert _gaps(tree, prompts, outs).max() <= ATOL
    assert (m.prefix_hits, m.conv_tail_restores,
            m.prefix_pages_reused) == (2, 2, 6)
    assert m.paged_operator == "attention+mamba"
    assert m.paged_ffn == "dense:_ffn" and m.moe_layer_steps == 0
    assert m.state_bytes_slot == 6 * (3 * 128 + 8 * 128) * 4
    # 70 tokens: two snapshots; 35, 64 + 6 and 40 + 15 one each (the
    # hits' suffixes none); 64 two
    assert m.state_snapshots_taken == 2 + 1 + 2
    assert m.state_snapshot_rows_hwm == 5 and m.state_snapshot_evictions == 0
    one_at_a_time = [_serve(tree, [p], 7, n_slots=1, prefix_cache=False)[0]
                     for p in prompts]
    assert all((a == b).all() for a, b in zip(outs, one_at_a_time))
    # fewer rows than the prompts want: evictions, and the same tokens
    tight = _serve(tree, prompts, 7, n_snapshots=1)
    assert tight.metrics.state_snapshot_evictions > 0
    assert tight.metrics.state_snapshot_rows_hwm == 1
    assert all((a == b).all() for a, b in zip(tight, outs))


def test_requests_that_end_mid_chunk_get_the_references_tokens(tree,
                                                                monkeypatch):
    """Outputs of 2 to 9 tokens against a chunk of 4, five requests into
    two slots: requests end mid-chunk and the last drains beside an
    empty slot. The loop tells each chunk what every slot still owes
    (``state['left']``) and the attend zeroes the rows of the slot-steps
    that can deliver nothing; this family's other operators run on as
    they did. Every served token is still the reference's choice to
    ATOL, and the tokens are, bit for bit, those of the same call with
    every slot said to be live throughout."""
    prompts = [_seq(41, 20), _seq(9, 21), _seq(23, 22), _seq(35, 23),
               _seq(17, 24)]
    n_new = [6, 3, 9, 2, 5]
    told = _serve(tree, prompts, n_new, n_snapshots=6)
    assert [len(o) - len(p) for o, p in zip(told, prompts)] == n_new
    assert _gaps(tree, prompts, told).max() <= ATOL
    assert 0 < told.metrics.attend_dead_share < 1
    monkeypatch.setattr(serving.RequestBook, "left",
                        lambda self: np.full(self.n_slots, self.chunk,
                                             np.int32))
    untold = _serve(tree, prompts, n_new, n_snapshots=6)
    assert untold.metrics.attend_pages_dead == 0
    assert untold.metrics.attend_pages_walked == (
        told.metrics.attend_pages_walked + told.metrics.attend_pages_dead)
    assert all((a == b).all() for a, b in zip(told, untold))


@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["prefix_cache_on", "prefix_cache_off"])
def test_serve_loop_preempts_and_resumes_to_the_same_tokens(tree,
                                                            prefix_cache):
    """A pool too small for both requests' growth: the later one is
    preempted (its pages and its state dropped) and resumed by a
    prefill, and every token it was served is still the reference's."""
    prompts = [_seq(30, 20), _seq(29, 21)]
    outs = _serve(tree, prompts, 40, n_pages=8, prefix_cache=prefix_cache)
    m = outs.metrics
    assert m.preemptions >= 1 and m.rejections == 0
    assert m.conv_tail_restores == m.prefix_hits
    assert _gaps(tree, prompts, outs).max() <= ATOL
    calm = _serve(tree, prompts, 40, prefix_cache=prefix_cache)
    assert calm.metrics.preemptions == 0
    assert all((a == b).all() for a, b in zip(outs, calm))
