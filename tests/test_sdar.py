"""SDAR-MoE (models/sdar.py): generation by diffusion over blocks through
the paged plane, against the plain reference (benchmarks/reference/
sdar.py) on seeded weights, by LOGITS, at a tiny size on the CPU: the
plain forward under the block-causal mask; prefill then block chunks
through the functions ``serve_paged_greedy`` runs (``serving.
paged_prefill`` / ``paged_suffix_prefill``, ``PagedKV``, ``kvpage.
paged_decode_chunk``'s block arm, ``kvpage.paged_block_forward``); the
pages a finished request left behind; every ``P mod W`` and ``n_new mod
W``; 4, 2 and 1 denoising steps a block; a radix hit; a preempted
request; ``eos`` inside a block; int8 pages; the softmax router; the
attend's and the prefill's kernel paths in interpret mode; the request
book's block rules without a model.

Tolerances, each beside its reason: in float32 the program and the
reference compute the same sums in another order (scans, grouped
matmuls, the folded attend, a merge by logsumexps), which reads
1e-6..1e-5 on logits of size ~3: ``ATOL`` = 2e-4 leaves a decade and
more of room, and what has to differ (K/V of a forward that still held a
mask, a causal mask inside a block) reads 1e-2..1.
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

from benchmarks import weights_sdar  # noqa: E402
from benchmarks.entries import serve_paged_greedy_sdar as entry  # noqa: E402
from benchmarks.reference import sdar as ref  # noqa: E402
from mpi_acx_tpu.models import kvpage, moe, sdar, serving  # noqa: E402
from mpi_acx_tpu.ops import attention  # noqa: E402

ATOL = 2e-4
PT, MAX_LEN, W = 16, 128, 4

# The tiny preset as a configuration FILE's keys (what the benchmark's
# entry and reference read): three layers, 8 experts top 2, d = 64, 4 / 2
# heads of 16. ``init_scale`` 1/sqrt(d): the layers decide the logits.
C = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
         num_attention_heads=4, num_key_value_heads=2, head_dim=16,
         moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
         norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1000000,
         max_position_embeddings=256, init_scale=0.125,
         generation=dict(block_length=W, denoising_steps=4,
                         mask_token_id=95))
HP = ref.hyper(C)
CFG = entry.program_config(C, "float32")
SERVE = dict(n_slots=3, max_len=MAX_LEN, family=sdar, chunk=8,
             page_tokens=PT, n_pages=24, prefix_cache=True,
             return_paged_state=True)


@pytest.fixture(scope="module")
def tree():
    return weights_sdar.make_sdar(C, 7, jnp.float32)


def _seq(n, seed, vocab=C["vocab_size"]):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _with(c, **generation):
    c = dict(c, generation=dict(c["generation"], **generation))
    return c, ref.hyper(c), entry.program_config(c, "float32")


# -- the program's config and layout ------------------------------------------

def test_tiny_preset_and_the_file_mapping_agree(tree):
    assert CFG == sdar.tiny_sdar(dtype=jnp.dtype("float32"))
    mine = sdar.init_params(jax.random.key(0), CFG)
    assert jax.tree.structure(mine) == jax.tree.structure(tree)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(mine),
                                                  jax.tree.leaves(tree)))
    spec = kvpage.paged_spec(sdar, CFG)
    assert (spec.block, spec.denoise_steps, spec.mask_token) == (4, 4, 95)
    assert spec.n_page_layers == 3 and spec.n_state_layers == 0
    assert spec.built("operator") == "attention"
    # the others generate a token a step: the plane reads their 0
    from mpi_acx_tpu.models import lfm2
    assert kvpage.paged_spec(None, CFG_GPT2()).block == 0
    assert kvpage.paged_spec(lfm2, lfm2.tiny_lfm2()).block == 0
    published = sdar.sdar_30b_a3b()
    assert (published.n_layers, published.n_experts, published.top_k,
            published.head_dim, published.vocab) == (48, 128, 8, 128, 151936)


def CFG_GPT2():
    from mpi_acx_tpu.models import transformer as tfm
    return tfm.tiny_config()


# -- the router ----------------------------------------------------------------

@pytest.mark.parametrize("normalise", [True, False])
def test_route_softmax_topk_against_a_plain_softmax_and_sort(normalise):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(37, 16)).astype(np.float32)
    gate = rng.normal(size=(16, 12)).astype(np.float32)
    gate[:, 5] = gate[:, 2]                 # equal scores: the lower index
    idx, p = moe.route_softmax_topk(jnp.asarray(x), jnp.asarray(gate), 3,
                                    normalise)
    z = x.astype(np.float64) @ gate.astype(np.float64)
    g = np.exp(z - z.max(-1, keepdims=True))
    g /= g.sum(-1, keepdims=True)
    want = np.argsort(-g, axis=-1, kind="stable")[:, :3]
    assert idx.dtype == jnp.int32 and p.dtype == jnp.float32
    assert (np.asarray(idx) == want).all()
    assert not ((np.asarray(idx) == 5).any(-1)
                & ~(np.asarray(idx) == 2).any(-1)).any()
    w = np.take_along_axis(g, want, -1)
    if normalise:
        w = w / w.sum(-1, keepdims=True)
        assert np.allclose(np.asarray(p).sum(-1), 1.0, atol=1e-6)
    # f32 softmax against f64: 1e-7 relative
    assert np.allclose(np.asarray(p), w, atol=1e-6)


# -- the whole-sequence pass -----------------------------------------------------

def test_forward_against_the_reference(tree):
    seq = _seq(48, 1)
    got = np.asarray(sdar.forward(tree, CFG, jnp.asarray(seq)[None]))[0]
    want = np.asarray(ref.forward(tree, seq, hp=HP))
    assert np.abs(want).max() > 1.0         # the layers decide the logits
    assert np.abs(got - want).max() < ATOL
    # and it IS block-causal: a token changed at the END of a block moves
    # the logits of the block's first position, and of none before it
    other = seq.copy()
    other[11] = (other[11] + 1) % 90
    moved = np.abs(np.asarray(sdar.forward(
        tree, CFG, jnp.asarray(other)[None]))[0] - got).max(-1)
    assert (moved[:8] == 0).all() and (moved[8:12] > 1e-3).all()


@pytest.mark.parametrize("S", [32, 128])
def test_block_causal_flash_against_the_dense_mask(S):
    """``ops.attention.block_causal_flash`` (the causal kernel's result
    and logsumexp merged with the columns after a row inside its block;
    interpret mode here) against the dense block-causal mask. f32: the
    two softmaxes differ by summation order, 1e-6."""
    rng = np.random.default_rng(S)
    q, k, v = (jnp.asarray(rng.normal(size=(2, S, 4, 16)), jnp.float32)
               for _ in range(3))
    want = attention.block_causal_reference(q, k, v, W)
    got = attention.block_causal_flash(q, k, v, W)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    causal = attention.attention_reference(q, k, v, causal=True)
    assert np.abs(np.asarray(causal) - np.asarray(want)).max() > 1e-2
    assert attention.select_block_attention(False, W)(q, k, v).shape \
        == q.shape
    on = attention.select_block_attention(True, W)(q, k, v)
    assert np.abs(np.asarray(on) - np.asarray(want)).max() < 2e-5


# -- prefill, then blocks through the pages ---------------------------------------

def _seated(tree, cfg, prompt, n_slots=2, kv_int8=False):
    """A PagedKV with ``prompt`` prefilled into slot 0 as the serve loop
    seats it: its whole blocks stored, the rest handed to the first
    block."""
    pkv = kvpage.PagedKV(cfg, sdar, n_slots, MAX_LEN, PT, 16,
                         kv_int8=kv_int8)
    body = len(prompt) - len(prompt) % W
    pages = pkv.alloc_evicting(kvpage.pages_needed(len(prompt), PT))
    padded = serving._padded(prompt[:body], MAX_LEN, cfg.max_seq)
    logits, one = serving.paged_prefill(
        tree, jnp.asarray(padded), body - 1, cfg=cfg, family=sdar,
        kv_int8=kv_int8, on_tpu=False, page_tokens=None)
    assert logits is None                   # no head, no token
    pkv.scatter_prompt(one, pages)
    pkv.seat(0, [], pages, body)
    tok = np.full((n_slots, W), -1, np.int32)
    tok[0, :len(prompt) - body] = prompt[body:]
    return pkv, tok


@pytest.mark.parametrize("decode_flash", [False, True])
def test_a_blocks_forward_through_the_pages_against_the_references_denoise(
        tree, decode_flash):
    """``paged_block_forward`` behind a prefilled prompt, a block in the
    middle of its denoising (two positions committed), its logits
    against ``reference.denoise`` on the same block; with
    ``decode_flash`` the attend is the Pallas walk in interpret mode, a
    block's rows folded into one position's heads."""
    from mpi_acx_tpu.ops import flash_decode
    cfg = dataclasses.replace(CFG, decode_flash=decode_flash)
    prompt = _seq(24, 2)
    pkv, _ = _seated(tree, cfg, prompt)
    state = pkv.device_state(np.asarray([8, 0], np.int32))
    state["stage"] = (flash_decode.new_kv_stage(
        [state["k"], state["v"]], 2, 8), jnp.int32(0))
    block = np.asarray([[7, 95, 31, 95], [95, 95, 95, 95]], np.int32)
    x, out = kvpage.paged_block_forward(tree, cfg, state,
                                        jnp.asarray(block), PT, sdar)
    got = np.asarray(kvpage.paged_spec(sdar, cfg).head(tree, cfg, x))
    want = np.asarray(ref.denoise(tree, prompt, block[0],
                                  [True, False, True, False], hp=HP))
    assert got.shape == (2, W, C["vocab_size"])
    assert np.abs(got[0] - want).max() < ATOL
    # a slot with nothing left (left 0) is dead to the attend and the
    # expert layers: finite whatever, and no pair of it was routed
    assert np.isfinite(got).all()
    pairs, _, _, layer_steps, dead = (int(n) for n in out["moe"])
    assert (pairs, layer_steps, dead) == (3 * W * 2, 3, 3 * W * 2)
    # no page was written: the pool is what the prefill left
    assert (np.asarray(out["k"]) == np.asarray(pkv.pool["k"])).all()


def _serve(tree, cfg, prompts, n_new, **over):
    return serving.serve_paged_greedy(tree, cfg, prompts, n_new,
                                      **dict(SERVE, **over))


@pytest.fixture(scope="module")
def served(tree):
    """One serve call over every ``P mod W`` x ``n_new mod W``, prompts
    shorter than a block and longer than a page among them."""
    lens = [(p, n) for p in (20, 21, 22, 23) for n in (8, 9, 10, 11)]
    lens += [(3, 5), (1, 4), (34, 6), (4, 1)]
    prompts = [_seq(p, 100 + i) for i, (p, _) in enumerate(lens)]
    n_new = [n for _, n in lens]
    stream = []
    outs = _serve(tree, CFG, prompts, n_new,
                  on_token=lambda rid, tok: stream.append((rid, tok)))
    return prompts, n_new, outs, stream


@pytest.mark.parametrize("rid", range(20))
def test_serve_is_the_references_generate(tree, served, rid):
    """Tokens AND the step that committed each position: in float32
    nothing but a near-tie could part them (none at these seeds), and
    under them lie the logits: the served token's reference logit is the
    reference's best at the block as it stood."""
    prompts, n_new, outs, _ = served
    want, log = ref.generate(tree, prompts[rid], n_new[rid], hp=HP)
    assert (np.asarray(outs[rid]) == want).all()
    per = {r.rid: r for r in outs.metrics.per_request}[rid]
    assert per.block_log == log
    assert per.new_tokens == n_new[rid]
    P = len(prompts[rid])
    assert len(log) == -(-(P + n_new[rid]) // W) * W - (P - P % W)
    assert [a for _, a in log[:P % W]] == [-1] * (P % W)
    assert sorted(a for _, a in log[P % W:W]) == list(range(W - P % W))


def test_the_stream_the_counters_and_the_first_token(served):
    prompts, n_new, outs, stream = served
    m = outs.metrics
    # on_token fires in position order, every token once
    for rid, p in enumerate(prompts):
        mine = [t for r, t in stream if r == rid]
        assert mine == list(np.asarray(outs[rid])[len(p):])
    assert m.block_length == W and m.denoise_steps == 4
    chunks, blocks = m.steps, m.steps * (SERVE["chunk"] // W)
    assert (m.forwards_denoise, m.forwards_store) == (4 * blocks, blocks)
    assert m.decode_tokens == sum(n_new) == m.new_tokens
    assert m.decode_slot_steps == chunks * SERVE["chunk"] * SERVE["n_slots"]
    assert m.decode_slot_steps == (m.decode_tokens + m.block_positions_kept
                                   + m.block_positions_dead)
    kept = sum(len(p) % W + (-(len(p) + n)) % W
               for p, n in zip(prompts, n_new))
    assert m.block_positions_kept == kept
    assert len(m.block_by_chunk) == chunks == len(m.moe_by_chunk)
    assert all(c[:3] == (8, 2, 24) and c[2] == sum(c[3:6])
               for c in m.block_by_chunk)
    # every forward of every layer tallied: (layers x forwards) a chunk
    assert m.moe_layer_steps == 3 * 5 * blocks
    assert m.moe_assignments + m.moe_pairs_dead \
        == 2 * SERVE["n_slots"] * W * m.moe_layer_steps
    # TTFT ends at the first DELIVERED token: a chunk, not the prefill
    # (two clocks: ``ttft_s`` counts from behind the set-up, the spans'
    # fields from the call's entry)
    setup = m.phase_s["serve.setup"]
    assert all(r.ttft_s + setup > r.queue_wait_s + r.prefill_s > 0
               for r in m.per_request)
    assert m.prefills == len(prompts) and m.programs_traced > 0
    steps = [sp for sp in m.spans if sp.name == "chunk.step"]
    assert all(sp.ids["blocks"] == 2 for sp in steps)
    names = {sp.name for sp in m.spans}
    assert {"refill.match", "refill.prefill", "refill.scatter",
            "refill.seat", "chunk.step", "chunk.deliver"} <= names
    assert all(r.chunks >= 1 and r.decode_s > 0 for r in m.per_request)


def test_stored_pages_are_the_finished_sequences(tree, served):
    """What a finished request left in the pool against the reference's
    K/V of the FINISHED sequence (prompt and generated blocks alike),
    every layer; and NOT what the last denoising forward, which still
    held a mask, would have left."""
    prompts, n_new, outs, _ = served
    pkv = outs.paged_state
    per = {r.rid: r for r in outs.metrics.per_request}
    checked = 0
    for rid in range(len(prompts)):
        kept = pkv.left_behind(rid)
        if kept is None:
            continue
        pages, pos = kept
        P = len(prompts[rid])
        seq = np.concatenate([prompts[rid][:P - P % W],
                              [t for t, _ in per[rid].block_log]])
        assert len(seq) <= pos
        k, v = (np.asarray(a)[..., :len(seq)]
                for a in pkv.gather_history(pages))
        T = len(seq)
        _, rk, rv = ref.forward_with(
            tree, jnp.asarray(seq.astype(np.int32)), jnp.arange(T),
            jnp.asarray(ref.block_mask(T, W)), jnp.arange(1), hp=HP)
        for got, want in ((k, rk), (v, rv)):
            assert np.abs(got.transpose(0, 3, 1, 2)
                          - np.asarray(want)).max() < ATOL
        # the last block as its last denoising forward saw it
        at = np.asarray([a for _, a in per[rid].block_log][-W:])
        fed = seq.copy()
        fed[T - W:][at == at.max()] = 95
        _, mk, _ = ref.forward_with(
            tree, jnp.asarray(fed.astype(np.int32)), jnp.arange(T),
            jnp.asarray(ref.block_mask(T, W)), jnp.arange(1), hp=HP)
        assert np.abs(k.transpose(0, 3, 1, 2)[1:, T - W:]
                      - np.asarray(mk)[1:, T - W:]).max() > 1e-2
        checked += 1
    assert checked >= 3         # the last requests to finish, at least


@pytest.mark.parametrize("steps", [2, 1])
def test_fewer_denoising_steps_a_block(tree, steps):
    """W = 4 at 2 and at 1 steps: 2 and 4 positions committed a step."""
    c, hp, cfg = _with(C, denoising_steps=steps)
    prompts = [_seq(p, 40 + p) for p in (9, 18, 7)]
    n_new = [7, 6, 9]
    outs = _serve(tree, cfg, prompts, n_new)
    per = {r.rid: r for r in outs.metrics.per_request}
    for rid, (p, n) in enumerate(zip(prompts, n_new)):
        want, log = ref.generate(tree, p, n, hp=hp)
        assert (np.asarray(outs[rid]) == want).all()
        assert per[rid].block_log == log
        assert max(a for _, a in log) == steps - 1
    m = outs.metrics
    assert m.forwards_denoise == steps * m.forwards_store > 0


def test_a_prefix_hit_prefills_the_suffix_block_causally(tree):
    """Three prompts share two whole pages: the second and third seat
    the cached pages and prefill only the suffix (``_block_attend_
    behind``: the suffix's rows against history + suffix under the
    block-causal mask); one of them has NO whole block left to prefill.
    Tokens and commit order are the reference's."""
    head = _seq(2 * PT, 9)
    prompts = [np.concatenate([head, _seq(n, 60 + n)]) for n in (7, 10, 2)]
    n_new = [6, 5, 7]
    outs = _serve(tree, CFG, prompts, n_new, n_slots=1)
    assert outs.metrics.prefix_hits == 2
    assert outs.metrics.prefix_pages_reused == 4
    per = {r.rid: r for r in outs.metrics.per_request}
    for rid, (p, n) in enumerate(zip(prompts, n_new)):
        want, log = ref.generate(tree, p, n, hp=HP)
        assert (np.asarray(outs[rid]) == want).all()
        assert per[rid].block_log == log
    hits = [sp for sp in outs.metrics.spans if sp.name == "refill.prefill"]
    assert [sp.ids["hit_pages"] for sp in hits] == [0, 2, 2]


def test_a_page_holds_whole_blocks():
    with pytest.raises(AssertionError, match="multiple of the family's"):
        kvpage.PagedKV(CFG, sdar, 2, 60, 6, 8)
    with pytest.raises(AssertionError, match="multiple of the family's"):
        serving.serve_paged_greedy(
            sdar.init_params(jax.random.key(0), CFG), CFG, [_seq(5, 0)], 4,
            n_slots=1, max_len=64, family=sdar, chunk=6, page_tokens=16)


def test_a_preempted_request_replays_the_same_tokens(tree):
    """A pool too small for both requests' growth: the later arrival is
    preempted, requeued uncharged and served again from its prompt; its
    stream restarts and its tokens are the ones it would have had."""
    prompts = [_seq(14, 70), _seq(13, 71)]
    n_new = [30, 28]
    alone = _serve(tree, CFG, prompts, n_new)
    stream = {0: [], 1: []}
    tight = _serve(tree, CFG, prompts, n_new, n_slots=2, n_pages=5,
                   prefix_cache=False,
                   on_token=lambda rid, tok: stream[rid].append(tok))
    assert tight.metrics.preemptions >= 1 and tight.metrics.requeues == 0
    for rid in range(2):
        assert (np.asarray(tight[rid]) == np.asarray(alone[rid])).all()
    new = list(np.asarray(tight[1])[len(prompts[1]):])
    assert len(stream[1]) > len(new) and stream[1][-len(new):] == new


def test_eos_drops_what_follows_it_in_its_block(tree):
    prompts = [_seq(10, 80)]
    full = _serve(tree, CFG, prompts, [12])
    new = list(np.asarray(full[0])[10:])
    eos = new[5]
    first = new.index(eos)
    cut = _serve(tree, CFG, prompts, [12], eos=eos)
    assert list(np.asarray(cut[0])[10:]) == new[:first + 1]
    log = cut.metrics.per_request[0].block_log
    assert len(log) % W == 0 and len(log) >= 10 % W + first + 1


def test_int8_pages_serve_and_stay_close(tree):
    """The program's own ``kv_int8=True``: codes and scales in the pages
    and in the stage. Tokens may part from the float32 serve's at a
    near-tie; the pages read back within 8 bits' step (1 / 254 of a
    head's largest value), far above ATOL: what the benchmark's
    ``kv_int8`` control has to fail on."""
    prompts = [_seq(21, 90), _seq(9, 91)]
    outs = _serve(tree, CFG, prompts, [9, 7], kv_int8=True)
    assert all(np.asarray(o).shape == (len(p) + n,)
               for o, p, n in zip(outs, prompts, (9, 7)))
    pkv = outs.paged_state
    assert pkv.pool["k"].dtype == jnp.int8 and "ks" in pkv.pool
    per = {r.rid: r for r in outs.metrics.per_request}
    pages, _ = pkv.left_behind(0)
    seq = np.concatenate([prompts[0][:20],
                          [t for t, _ in per[0].block_log]]).astype(np.int32)
    T = len(seq)
    k = np.asarray(pkv.gather_history(pages)[0])[..., :T]
    _, rk, _ = ref.forward_with(
        tree, jnp.asarray(seq), jnp.arange(T),
        jnp.asarray(ref.block_mask(T, W)), jnp.arange(1), hp=HP)
    err = np.abs(k.transpose(0, 3, 1, 2)[0] - np.asarray(rk)[0])
    assert ATOL < err.max() < np.abs(np.asarray(rk)[0]).max() / 100


# -- the request book's block rules, without a model ---------------------------

def _book(n_new, **kw):
    return serving.RequestBook([np.arange(7), np.arange(5)], n_new, 2, None,
                               8, 0, block=4, **kw)


def test_book_seats_a_prompts_tail_and_counts_positions():
    got = []
    book = _book([6, 3], on_token=lambda rid, t: got.append((rid, t)))
    assert book.last_tok.shape == (2, 4) and (book.last_tok == -1).all()
    book.queue.clear()
    book.seat(0, 0, np.asarray([50, 51, 52]))       # P mod W = 3
    book.seat(1, 1, np.asarray([60]))               # P mod W = 1
    assert book.prefills == 2 and book.ttft == [None, None] and not got
    assert list(book.last_tok[0]) == [50, 51, 52, -1]
    # positions owed: tokens + the prompt's tokens in the first block
    assert list(book.left()) == [6 + 3, 3 + 1]
    block = np.asarray([[50, 51, 52, 10, 11, 12, 13, 14],
                        [60, 20, 21, 22, 23, 24, 25, 26]]).T
    at = np.asarray([[-1, -1, -1, 0, 2, 0, 3, 1],
                     [-1, 1, 0, 2, 3, 2, 1, 0]]).T
    book.deliver(block, 0.1, at)
    assert got == [(0, 10), (0, 11), (0, 12), (0, 13), (0, 14),
                   (1, 20), (1, 21), (1, 22)]
    assert book.ttft[0] is not None and book.ttft[1] is not None
    assert (book.last_tok == -1).all()
    assert list(book.left()) == [1, 0] and book.slot_finished(1)
    # request 1 ended inside its first block: nothing of the second logged
    assert book.block_log[1] == [(60, -1), (20, 1), (21, 0), (22, 2)]
    assert len(book.block_log[0]) == 8
    assert book.block_chunks == [(8, 4)]            # delivered, kept back
    assert book.decode_slot_steps == 16 and book.decode_tokens == 8
    book.finish_request(1)
    nxt = np.full((8, 2), 7)
    book.deliver(nxt, 0.1, np.zeros((8, 2), int))
    assert got[-1] == (0, 7) and book.slot_finished(0)
    # its last block's excess is logged, not delivered; the idle slot's
    # positions are dead
    assert len(book.block_log[0]) == 12 and book.block_chunks[-1] == (1, 3)
    assert list(np.asarray(book.emitted[0])) == [10, 11, 12, 13, 14, 7]


def test_book_restart_drops_the_block_log():
    book = _book([6, 3])
    book.queue.clear()
    book.seat(0, 0, np.asarray([], np.int32))
    book.deliver(np.full((8, 2), 5), 0.1, np.zeros((8, 2), int))
    assert len(book.block_log[0]) == 8 and book.ttft[0] is not None
    book.owner[0] = -1
    book.restart(0)
    assert book.block_log[0] == [] and book.emitted[0] == []
    assert book.ttft[0] is None and list(book.queue) == [0]


def test_a_token_familys_book_is_what_it_was():
    book = serving.RequestBook([np.arange(4)], [3], 1, None, 2, 0)
    assert book.block == 0 and book.last_tok.shape == (1,)
    book.queue.clear()
    book.seat(0, 0, 9)
    assert book.emitted[0] == [9] and book.ttft[0] is not None
    assert list(book.left()) == [2]
    book.deliver(np.asarray([[4], [5]]), 0.1)
    assert book.emitted[0] == [9, 4, 5] and book.last_tok[0] == 5
    assert book.block_chunks == [] and book.block_log[0] == []
