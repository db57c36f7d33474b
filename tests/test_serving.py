"""Continuous-batching serving (models/serving.py): per-slot positions
must make every slot's math identical to its solo run, so the whole
server is pinned by bit-equality against per-request generate().

The reference has no serving stack (SURVEY.md §0); this is
framework-goal surface. The throughput claim (no drain bubble at mixed
output lengths) is structural — covered here by the refill bookkeeping
test; the wall clock is the benchmark's (benchmarks/run.py, on the chip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_acx_tpu.models import llama as lm
from mpi_acx_tpu.models import moe_transformer as moe
from mpi_acx_tpu.models import serving
from mpi_acx_tpu.models import transformer as tfm


def _gpt2():
    cfg = tfm.tiny_config(vocab=61, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_seq=96)
    return cfg, tfm.init_params(jax.random.key(0), cfg), tfm


def _llama():
    cfg = lm.tiny_llama(vocab=61, d_model=48, n_heads=4, n_kv_heads=2,
                        n_layers=2, d_ff=96, max_seq=96)
    return cfg, lm.init_params(jax.random.key(1), cfg), lm


def _moe():
    cfg = moe.tiny_moe_config(vocab=61, d_model=48, n_heads=4, n_layers=2,
                              d_ff=96, max_seq=96, n_experts=4)
    return cfg, moe.init_params(jax.random.key(2), cfg), moe


def _prompts(key, n, vocab, lens):
    ks = jax.random.split(key, n)
    return [np.asarray(jax.random.randint(ks[i], (lens[i % len(lens)],),
                                          0, vocab), np.int32)
            for i in range(n)]


@pytest.mark.parametrize("fam", [_gpt2, _llama, _moe],
                         ids=["gpt2", "llama", "moe"])
def test_continuous_batching_equals_solo_runs(fam):
    """7 requests with staggered lengths through 3 slots: every output
    equals that request's solo greedy generate, bit for bit — including
    the requests that entered mid-stream through a refill."""
    cfg, params, mod = fam()
    n_new, max_len = 6, 32
    prompts = _prompts(jax.random.key(3), 7, cfg.vocab,
                       lens=[5, 9, 3, 12, 7])
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=3,
                               max_len=max_len, family=mod)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(want)[0], err_msg=str(p))


def test_more_requests_than_slots_and_single_slot():
    """Queue pressure: 5 requests through ONE slot — pure sequential
    refills — still bit-equal to solo runs."""
    cfg, params, mod = _gpt2()
    prompts = _prompts(jax.random.key(4), 5, cfg.vocab, lens=[4, 6])
    got = serving.serve_greedy(params, cfg, prompts, 4, n_slots=1,
                               max_len=24, family=mod)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], 4,
                            max_len=24)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_eos_retires_early_and_refills():
    """An ``eos`` hit retires the request at the eos token; outputs are
    the solo output truncated at the first eos in the generated part,
    and later requests still complete correctly after the early
    refill."""
    cfg, params, mod = _gpt2()
    n_new, max_len = 8, 32
    prompts = _prompts(jax.random.key(5), 6, cfg.vocab, lens=[5, 8, 11])
    solo = [np.asarray(mod.generate(params, cfg, jnp.asarray(p)[None],
                                    n_new, max_len=max_len))[0]
            for p in prompts]
    # Pick an eos that actually occurs mid-generation somewhere so the
    # early-retire path runs (fall back to an unused id otherwise).
    eos = None
    for s, p in zip(solo, prompts):
        gen = s[len(p):]
        if len(np.unique(gen)) > 1:
            eos = int(gen[0])
            break
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, eos=eos)
    for p, g, s in zip(prompts, got, solo):
        gen = s[len(p):]
        if eos is not None and eos in gen.tolist():
            stop = gen.tolist().index(eos) + 1
            want = np.concatenate([p, gen[:stop]])
        else:
            want = s
        np.testing.assert_array_equal(np.asarray(g), want)


def test_vector_pos_matches_scalar_pos_decode():
    """decode_step with pos [B] (all equal) must equal scalar pos
    exactly — the serving mode is the generate path's math."""
    cfg, params, mod = _gpt2()
    B, S, max_len = 3, 6, 16
    tok = jax.random.randint(jax.random.key(6), (B, S), 0, cfg.vocab)
    _, cache_s = mod.prefill(params, cfg, tok, max_len, last_only=True)
    cache_v = dict(cache_s)
    cache_v["pos"] = jnp.full((B,), S, jnp.int32)
    nxt = jax.random.randint(jax.random.key(7), (B,), 0, cfg.vocab)
    ls, cs = mod.decode_step(params, cfg, cache_s, nxt)
    lv, cv = mod.decode_step(params, cfg, cache_v, nxt)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lv))
    np.testing.assert_array_equal(np.asarray(cs["k"]), np.asarray(cv["k"]))
    assert cv["pos"].shape == (B,) and int(cv["pos"][0]) == S + 1


@pytest.mark.parametrize("chunk", [4, 5])
def test_chunked_serving_equals_solo_runs(chunk):
    """chunk>1 amortizes host dispatch without changing a single
    output token (including n_new not divisible by chunk, mid-chunk
    finishes, and refills at chunk boundaries)."""
    cfg, params, mod = _gpt2()
    n_new, max_len = 6, 40
    prompts = _prompts(jax.random.key(8), 6, cfg.vocab, lens=[5, 9, 3])
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=chunk)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_per_request_n_new():
    """Mixed output lengths — the workload continuous batching exists
    for: each request stops at ITS OWN n_new, refills backfill the
    freed slots, outputs equal per-request solo runs."""
    cfg, params, mod = _gpt2()
    max_len = 48
    prompts = _prompts(jax.random.key(9), 6, cfg.vocab, lens=[5, 8])
    n_new = [2, 9, 4, 7, 1, 6]
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=3)
    for p, g, n in zip(prompts, got, n_new):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


@pytest.mark.parametrize("fam", [_gpt2, _llama, _moe],
                         ids=["gpt2", "llama", "moe"])
def test_int8_slots_equal_int8_solo(fam):
    """Continuous batching over int8 slot caches: same codes, same
    scales, same scale-on-scores read as the solo kv_int8 run — so
    outputs must be bit-equal to generate(..., kv_int8=True)."""
    cfg, params, mod = fam()
    n_new, max_len = 5, 32
    prompts = _prompts(jax.random.key(10), 5, cfg.vocab, lens=[4, 9, 6])
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=2,
                               kv_int8=True)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len, kv_int8=True)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_int8_weight_checkpoint_serves():
    """Weight-only int8 checkpoints (ops/wquant.py) flow through the
    serving tier transparently — every slot op reads weights via
    wread — and outputs equal the solo quantized runs."""
    from mpi_acx_tpu.ops.wquant import GPT2_WEIGHTS, quantize_weights_int8
    cfg, params, mod = _gpt2()
    qparams = quantize_weights_int8(params, GPT2_WEIGHTS)
    prompts = _prompts(jax.random.key(11), 4, cfg.vocab, lens=[5, 8])
    got = serving.serve_greedy(qparams, cfg, prompts, 4, n_slots=2,
                               max_len=24, family=mod, chunk=2)
    for p, g in zip(prompts, got):
        want = mod.generate(qparams, cfg, jnp.asarray(p)[None], 4,
                            max_len=24)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_serve_sample_equals_solo_sampled_runs():
    """Stochastic serving: request rid's key stream is
    fold_in(key, rid) with sample_generate's split discipline, so each
    output must equal the solo generate_sample run under that key —
    regardless of slot assignment, refill order, or chunking."""
    cfg, params, mod = _gpt2()
    n_new, max_len = 5, 40
    base = jax.random.key(42)
    prompts = _prompts(jax.random.key(12), 6, cfg.vocab, lens=[4, 7, 10])
    got = serving.serve_sample(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, key=base, family=mod,
                               temperature=0.9, top_k=17, chunk=3)
    for rid, (p, g) in enumerate(zip(prompts, got)):
        want = mod.generate_sample(params, cfg, jnp.asarray(p)[None],
                                   n_new, jax.random.fold_in(base, rid),
                                   temperature=0.9, top_k=17,
                                   max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0],
                                      err_msg=f"request {rid}")


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_continuous_batching_equals_solo(tp):
    """Continuous batching composed with tensor parallelism: the same
    host scheduler drives shard_map programs (make_tp_server_fns) whose
    KV slots shard by attention head — outputs must equal the solo
    single-device generate runs bit for bit at any tp (f32, the
    test_tp_inference convention: the matmul split reorders summation,
    and bf16 near-ties on a random-init model would flip argmaxes)."""
    import dataclasses
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns

    cfg, params, mod = _gpt2()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    mesh = mesh_from_devices({"tp": tp}, jax.devices()[:tp])
    n_new, max_len, chunk = 5, 32, 3
    prompts = _prompts(jax.random.key(13), 5, cfg.vocab, lens=[4, 9, 6])
    fns = make_tp_server_fns(params, cfg, mesh, chunk=chunk)
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=chunk,
                               server_fns=fns)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_tp_serving_int8_weight_checkpoint():
    """The full composition: continuous batching x tensor parallelism x
    int8 weight-only checkpoint (scale-keyed TP program cache + wread)
    — outputs equal the solo single-device quantized runs (f32 per the
    test_tp_inference convention)."""
    import dataclasses
    from mpi_acx_tpu.ops.wquant import GPT2_WEIGHTS, quantize_weights_int8
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns

    cfg, params, mod = _gpt2()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    qparams = quantize_weights_int8(params, GPT2_WEIGHTS)
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    prompts = _prompts(jax.random.key(14), 4, cfg.vocab, lens=[5, 8])
    fns = make_tp_server_fns(qparams, cfg, mesh, chunk=2)
    got = serving.serve_greedy(qparams, cfg, prompts, 4, n_slots=2,
                               max_len=24, family=mod, chunk=2,
                               server_fns=fns)
    for p, g in zip(prompts, got):
        want = mod.generate(qparams, cfg, jnp.asarray(p)[None], 4,
                            max_len=24)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_tp_llama_continuous_batching_equals_solo():
    """Llama TP serving: GQA slot caches shard by KV-head group,
    per-slot RoPE positions — outputs equal the solo runs at tp=2
    (f32 per the test_tp_inference convention)."""
    import dataclasses
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns

    cfg, params, mod = _llama()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    n_new, max_len, chunk = 5, 32, 3
    prompts = _prompts(jax.random.key(15), 5, cfg.vocab, lens=[4, 9, 6])
    fns = make_tp_server_fns(params, cfg, mesh, chunk=chunk,
                             family="llama")
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=chunk,
                               server_fns=fns)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_tp_moe_continuous_batching_equals_solo():
    """MoE TP serving: routed expert FFN through the ffn hook, experts
    sharded n_experts/tp per rank, auto EP dispatch — outputs equal
    the solo runs at tp=2 (f32 per the test_tp_inference convention;
    drop-free capacity so routing is batch-invariant)."""
    import dataclasses
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns

    cfg, params, mod = _moe()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                              capacity_factor=float(cfg.n_experts))
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    n_new, max_len, chunk = 5, 32, 3
    prompts = _prompts(jax.random.key(16), 5, cfg.vocab, lens=[4, 9, 6])
    fns = make_tp_server_fns(params, cfg, mesh, chunk=chunk,
                             family="moe")
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=chunk,
                               server_fns=fns)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


@pytest.mark.parametrize("fam,name", [(_gpt2, "gpt2"), (_llama, "llama")])
def test_tp_int8_kv_slots_equal_solo_int8(fam, name):
    """The last serving composition: continuous batching x tensor
    parallelism x int8 KV slot caches. Each rank quantizes its own
    head slice; outputs equal the solo single-device kv_int8 runs
    (f32 compute per the TP convention — the int8 codes/scales are
    identical per head regardless of the split, so quantization adds
    no TP-specific divergence)."""
    import dataclasses
    from mpi_acx_tpu.parallel.mesh import mesh_from_devices
    from mpi_acx_tpu.parallel.tp_inference import make_tp_server_fns

    cfg, params, mod = fam()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    mesh = mesh_from_devices({"tp": 2}, jax.devices()[:2])
    n_new, max_len, chunk = 5, 32, 3
    prompts = _prompts(jax.random.key(17), 5, cfg.vocab, lens=[4, 9, 6])
    fns = make_tp_server_fns(params, cfg, mesh, chunk=chunk,
                             family=name, kv_int8=True)
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=max_len, family=mod, chunk=chunk,
                               server_fns=fns, kv_int8=True)
    for p, g in zip(prompts, got):
        want = mod.generate(params, cfg, jnp.asarray(p)[None], n_new,
                            max_len=max_len, kv_int8=True)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_serve_sample_int8_kv_equals_solo():
    """Sampling and the int8 KV cache are orthogonal serving axes —
    together they must still equal the solo sampled int8 runs."""
    cfg, params, mod = _gpt2()
    base = jax.random.key(21)
    prompts = _prompts(jax.random.key(20), 4, cfg.vocab, lens=[5, 8])
    got = serving.serve_sample(params, cfg, prompts, 4, n_slots=2,
                               max_len=24, key=base, family=mod,
                               temperature=0.8, top_k=13, chunk=2,
                               kv_int8=True)
    for rid, (p, g) in enumerate(zip(prompts, got)):
        want = mod.generate_sample(params, cfg, jnp.asarray(p)[None], 4,
                                   jax.random.fold_in(base, rid),
                                   temperature=0.8, top_k=13,
                                   max_len=24, kv_int8=True)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want)[0])


def test_serving_telemetry():
    """serve_greedy returns a ServedBatch: the outputs behave as the
    plain list they always were, and .metrics carries the batch
    telemetry — per-request TTFT/latency/tokens-per-sec, queue depth,
    slot occupancy, requeue counts."""
    cfg, params, mod = _gpt2()
    n_new = 4
    prompts = _prompts(jax.random.key(22), 5, cfg.vocab, lens=[4, 7, 5])
    got = serving.serve_greedy(params, cfg, prompts, n_new, n_slots=2,
                               max_len=24, family=mod)
    assert isinstance(got, list) and len(got) == 5   # list face intact
    m = got.metrics
    assert isinstance(m, serving.ServingMetrics)
    assert m.requests == 5
    assert m.new_tokens == sum(len(g) - len(p)
                               for p, g in zip(prompts, got)) == 5 * n_new
    assert m.wall_s > 0 and m.tokens_per_s > 0
    assert m.steps > 0 and m.prefills == 5 and m.requeues == 0
    # 5 requests into 2 slots: 3 must have queued behind the seed.
    assert m.queue_depth_max >= 3
    assert 0 < m.slot_occupancy_mean <= 1.0
    assert 0 < m.ttft_p50_s <= m.ttft_p99_s
    assert 0 < m.itl_p50_s <= m.itl_p99_s
    assert len(m.per_request) == 5
    for r in m.per_request:
        assert r.new_tokens == n_new and r.retries == 0
        assert 0 < r.ttft_s <= r.latency_s <= m.wall_s
        assert r.tokens_per_s > 0


# -- RequestBook: the rules every serve loop shares, without a model --------


def _book(n_req=4, n_slots=3, n_new=3, eos=None, chunk=2, retries=1, **kw):
    prompts = [np.arange(1, 3 + rid, dtype=np.int32) for rid in range(n_req)]
    return serving.RequestBook(prompts, [n_new] * n_req, n_slots, eos,
                               chunk, retries, **kw)


def _seat_heads(book, n, first=50):
    for b in range(n):
        book.seat(b, book.queue.popleft(), first + b)


def test_book_charged_requeue_past_budget_raises():
    """A charged requeue spends the request's budget and, past
    max_request_retries, raises naming the request; the stream and the
    TTFT are reset and the request is back at the END of the queue."""
    book = _book(retries=1)
    _seat_heads(book, 1)
    boom = RuntimeError("boom")
    book.owner[0] = -1
    book.requeue(0, boom)
    assert book.attempts[0] == 1 and book.requeues == 1
    assert book.emitted[0] == [] and book.ttft[0] is None
    assert list(book.queue) == [1, 2, 3, 0]
    with pytest.raises(RuntimeError, match="request 0 failed 2 time"):
        book.requeue(0, boom)
    assert book.requeues == 1           # the raising attempt queued nothing


def test_book_uncharged_requeue_does_not_count():
    """Peer loss is not the request's fault: with a budget of ZERO any
    number of uncharged requeues pass, none moves ``attempts``."""
    book = _book(retries=0)
    for _ in range(5):
        book.queue.remove(2)
        book.requeue(2, RuntimeError("peer dead"), charge=False)
    assert book.attempts[2] == 0
    assert (book.requeues, book.peer_requeues) == (5, 5)
    assert list(book.queue) == [0, 1, 3, 2]


@pytest.mark.parametrize("lost_peer,shed", [(False, True), (True, True),
                                            (True, False)],
                         ids=["charged", "peer-sheds", "peer-keeps-width"])
def test_book_step_failed_requeues_every_active_slot(lost_peer, shed):
    """After a failed step every active slot's request goes back on the
    queue IN SLOT ORDER with its stream cleared and the step's input
    zeroed; a peer-loss failure charges nobody and sheds the highest
    idle slot, unless the caller keeps its width (the loopback)."""
    book = _book(n_req=5, n_slots=4)
    book.seat(2, 0, 7)                  # slots 0 and 2 active, 1 and 3 idle
    book.seat(0, 1, 8)
    del book.queue[0], book.queue[0]
    book.deliver(np.array([[9, 0, 9, 0]], np.int32), 0.01)
    exc = RuntimeError("tpu-acx: peer dead (error=20)" if lost_peer
                       else "wedged device")
    book.step_failed(exc, shed=shed)
    assert list(book.queue) == [2, 3, 4, 1, 0]      # slot 0's rid first
    assert book.emitted[0] == [] and book.emitted[1] == []
    assert not book.active() and not book.last_tok.any()
    assert book.requeues == 2
    assert book.peer_requeues == (2 if lost_peer else 0)
    assert [book.attempts[r] for r in (0, 1)] == ([0, 0] if lost_peer
                                                   else [1, 1])
    assert book.owner == ([-1, -1, -1, -2] if lost_peer and shed
                          else [-1] * 4)
    assert book.slots_shed == int(lost_peer and shed)


def test_book_shed_takes_highest_idle_and_never_the_last():
    book = _book(n_req=4, n_slots=3)
    book.seat(1, book.queue.popleft(), 5)   # slot 1 busy: not sheddable
    book.shed_slot()
    assert book.owner == [-1, 0, -2]        # highest IDLE slot
    book.shed_slot()
    assert book.owner == [-2, 0, -2]
    book.shed_slot()                        # one alive, and it is busy
    assert book.owner == [-2, 0, -2] and book.slots_shed == 2
    lone = _book(n_slots=1)
    lone.shed_slot()                        # a server of one slot keeps it
    assert lone.owner == [-1] and lone.slots_shed == 0
    assert lone.free_slot() == 0 and book.free_slot() is None


def test_book_revives_on_a_rise_of_the_fleet_only(monkeypatch):
    """Shed slots come back when ``_fleet_active()`` RISES: not on the
    fall that preceded it, not while it is level, and not at all when
    no native runtime answered at the book's birth."""
    fleet = {"active": 4}
    monkeypatch.setattr(serving, "_fleet_active", lambda: fleet["active"])
    book = _book(n_slots=3)
    book.shed_slot()
    book.shed_slot()
    assert book.owner == [-1, -2, -2]
    assert book.revive() == []              # level
    fleet["active"] = 3
    assert book.revive() == []              # the fall lowers the watermark
    assert book.revive() == []
    fleet["active"] = 4                     # back to where it STARTED: a rise
    assert book.revive() == [1, 2]
    assert book.owner == [-1, -1, -1] and book.slots_revived == 2
    fleet["active"] = 5
    assert book.revive() == [] and book.slots_revived == 2  # nothing shed
    monkeypatch.setattr(serving, "_fleet_active", lambda: None)
    dormant = _book(n_slots=2)
    dormant.shed_slot()
    monkeypatch.setattr(serving, "_fleet_active", lambda: 9)
    assert dormant.revive() == [] and dormant.owner == [-1, -2]


@pytest.mark.parametrize("eos,block,want,finished", [
    (None, [[4], [5], [6]], [50, 4, 5], True),      # length: n_new = 3
    (5, [[4], [5], [6]], [50, 4, 5], True),         # eos as the last token
    (4, [[4], [5], [6]], [50, 4], True),            # eos before the length
    (50, [[4], [5], [6]], [50], True),              # the FIRST token is eos
    (9, [[4]], [50, 4], False),                     # neither, yet
], ids=["length", "eos-at-length", "eos-early", "eos-first", "running"])
def test_book_slot_finished_and_deliver_stop_at_the_end(eos, block, want,
                                                        finished):
    """``slot_finished`` on length and on eos; ``deliver`` stops a slot
    at its end MID-CHUNK (later tokens of the block are dropped) and
    still feeds the block's last row to the next step."""
    book = _book(n_req=1, n_slots=2, n_new=3, eos=eos, chunk=len(block))
    _seat_heads(book, 1)
    block = np.asarray([[t[0], 77] for t in block], np.int32)
    book.deliver(block, 0.03)
    assert book.emitted[0] == want
    assert book.slot_finished(0) is finished
    assert list(book.last_tok) == [block[-1, 0], 77]    # idle slot too
    assert book.decode_slot_steps == len(block) * 2
    assert book.decode_tokens == len(want) - 1
    assert len(book.itl_samples) == len(want) - 1
    assert book.steps == 1


@pytest.mark.parametrize("n_new,blocks,want", [
    ([5, 2, 9], [], [4, 1, 0]),               # seated: one token each had
    ([5, 2, 9], [[[4, 5, 0]], [[6, 7, 0]]], [2, 0, 0]),  # slot 1 done, kept
    ([1, 3, 9], [], [0, 2, 0]),               # the prefill's token was all
], ids=["seated", "after-two-steps", "one-token-request"])
def test_book_left_is_what_each_slot_still_owes(n_new, blocks, want):
    """``left``: per slot the owner's ``n_new`` less what it has emitted,
    0 for a slot that owns no request (idle or shed), ``[n_slots]``
    int32: the steps of the next chunk in which the slot can deliver a
    token. A request that has its tokens and is not yet retired owes 0."""
    prompts = [np.arange(1, 4, dtype=np.int32)] * 2
    book = serving.RequestBook(prompts, n_new[:2], 3, None, 1, 0)
    _seat_heads(book, 2)
    for block in blocks:
        book.deliver(np.asarray(block, np.int32), 0.01)
    got = book.left()
    assert got.dtype == np.int32 and got.shape == (3,)
    assert list(got) == want
    book.owner[2] = -2                                  # shed: owes nothing
    assert list(book.left()) == want


def test_book_streams_finish_and_metrics_over_its_own_rids():
    """on_token sees the first token and every delivered one; a rejected
    request keeps its marker and has no telemetry row; ``rids`` narrows
    queue, rows and ``requests`` to a rank's share; finish_request
    returns prompt + emitted and idles the slot."""
    seen = []
    rej = {1: serving.RequestRejected(1, "exceeds_max_len", "x")}
    book = _book(n_req=6, n_slots=2, n_new=2, chunk=1, rejected=rej,
                 rids=[1, 3, 5], on_token=lambda rid, t: seen.append((rid, t)))
    assert list(book.queue) == [3, 5] and book.done[1] is rej[1]
    _seat_heads(book, 2, first=40)
    book.sample_gauges()
    book.deliver(np.array([[8, 9]], np.int32), 0.02)
    assert seen == [(3, 40), (5, 41), (3, 8), (5, 9)]
    assert book.finish_request(1) == 5 and book.finish_request(0) == 3
    np.testing.assert_array_equal(book.done[5], [1, 2, 3, 4, 5, 6, 7, 41, 9])
    assert book.owner == [-1, -1] and book.done[0] is None
    m = book.metrics(preemptions=3)
    assert [r.rid for r in m.per_request] == [3, 5]
    assert (m.requests, m.rejections, m.new_tokens) == (3, 1, 4)
    assert m.rejection_reasons == {"exceeds_max_len": 1}
    assert (m.prefills, m.steps, m.preemptions) == (2, 1, 3)
    assert m.decode_tokens == 2 and m.step_utilization == 1.0
    assert m.slot_occupancy_mean == 1.0 and m.queue_depth_max == 0
    assert all(0 < r.ttft_s <= r.latency_s <= m.wall_s
               for r in m.per_request)


# -- one injected failure through each single-process entry point -----------


@pytest.fixture(scope="module")
def loopback_rt():
    from mpi_acx_tpu import runtime
    r = runtime.Runtime()
    yield r
    r.finalize()


def _fail_nth(fn, nth, exc):
    """``fn`` that raises ``exc`` on its nth call, once."""
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == nth:
            raise exc
        return fn(*a, **kw)
    flaky.calls = calls
    return flaky


def _serve_with_failure(entry, failure, exc, monkeypatch, request):
    """Serve 5 requests through 2 slots, clean and then with one
    injected failure: ``refill`` = the 3rd prefill / handoff raises,
    ``step`` = the 2nd decode step raises."""
    cfg = tfm.tiny_config(vocab=61, d_model=48, n_heads=4, n_layers=2,
                          d_ff=96, max_seq=64)
    params = tfm.init_params(jax.random.key(5), cfg)
    prompts = _prompts(jax.random.key(29), 5, cfg.vocab, lens=[4, 9, 6])
    kw = dict(n_new=[5, 3, 6, 4, 5], n_slots=2, max_len=32)
    if entry == "greedy":
        fns = serving.make_server_fns(params, cfg, tfm)

        def run(fail):
            prefill_fn, step_fn = fns[0], fns[1]
            if fail == "refill":
                prefill_fn = _fail_nth(prefill_fn, 3, exc)
            elif fail == "step":
                step_fn = _fail_nth(step_fn, 2, exc)
            return serving.serve_greedy(
                params, cfg, prompts, family=tfm,
                server_fns=(prefill_fn, step_fn) + fns[2:], **kw)
    elif entry == "paged":
        from mpi_acx_tpu.models import kvpage

        def run(fail):
            with monkeypatch.context() as m:
                if fail == "refill":
                    m.setattr(serving, "paged_prefill",
                              _fail_nth(serving.paged_prefill, 3, exc))
                elif fail == "step":
                    make = kvpage.make_paged_step_fn
                    m.setattr(kvpage, "make_paged_step_fn",
                              lambda *a, **k: _fail_nth(make(*a, **k), 2,
                                                        exc))
                return serving.serve_paged_greedy(
                    params, cfg, prompts, family=tfm, page_tokens=8, **kw)
    else:
        from mpi_acx_tpu.models.disagg import serve_disagg_greedy
        rt = request.getfixturevalue("loopback_rt")
        fns = serving.make_server_fns(params, cfg, tfm, kv_int8=True)

        def run(fail):
            step_fn, handoffs = fns[1], {"n": 0}

            def ship_fault(rid, layer):
                if layer == 1:
                    handoffs["n"] += 1
                    if handoffs["n"] == 3:
                        raise exc
            if fail == "step":
                step_fn = _fail_nth(step_fn, 2, exc)
            return serve_disagg_greedy(
                params, cfg, prompts, rt=rt,
                server_fns=(fns[0], step_fn) + fns[2:],
                ship_fault=ship_fault if fail == "refill" else None, **kw)
    return run(None), run(failure)


@pytest.mark.parametrize("failure", ["refill", "step", "step-peer-loss"])
@pytest.mark.parametrize("entry", ["greedy", "paged", "disagg"])
def test_one_failure_costs_a_replay_through_every_entry_point(
        entry, failure, monkeypatch, request):
    """The same injected failure through serve_greedy,
    serve_paged_greedy and the disagg loopback: outputs equal the clean
    run's bit for bit, and the accounting is the book's through each. A
    refill that fails once is charged to its request alone; a failed
    step requeues the two active requests, charged, or — peer-loss
    shaped — uncharged with one slot shed, except in the loopback,
    which has no peer whose loss shrinks it."""
    from mpi_acx_tpu import runtime
    lost_peer = failure == "step-peer-loss"
    exc = (runtime.AcxPeerDeadError(
        "tpu-acx: peer dead (error=20, source=1, tag=0)",
        runtime.ERR_PEER_DEAD, 1, 0) if lost_peer
        else RuntimeError("injected failure"))
    clean, got = _serve_with_failure(entry, failure.split("-")[0], exc,
                                     monkeypatch, request)
    for i, (w, g) in enumerate(zip(clean, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    m, retries = got.metrics, [r.retries for r in got.metrics.per_request]
    assert clean.metrics.requeues == 0 and clean.metrics.prefills == 5
    if failure == "refill":
        assert (m.requeues, m.peer_requeues, m.slots_shed) == (1, 0, 0)
        assert retries == [0, 0, 1, 0, 0]   # the 3rd prefill was rid 2's
        assert m.prefills == 5              # successful refills only
    else:
        assert m.requeues == 2, m           # both slots were active
        assert m.peer_requeues == (2 if lost_peer else 0)
        assert sum(retries) == (0 if lost_peer else 2)
        assert m.slots_shed == int(lost_peer and entry != "disagg")
        assert m.prefills == 7              # the two victims refilled twice
    assert m.slots_revived == 0 and m.new_tokens == clean.metrics.new_tokens


# -- RollingSLO window semantics (docs/DESIGN.md §13/§20) -------------------


def test_rolling_slo_empty_window():
    """A fresh (or fully-expired) window reports zeroed percentiles and
    empty lifecycle counters — never a crash on the empty deque."""
    s = serving.RollingSLO(window_s=30.0)
    d = s.live_slos()
    assert d["ttft_n"] == 0 and d["itl_n"] == 0
    assert d["ttft_p50_s"] == 0.0 and d["ttft_p99_s"] == 0.0
    assert d["itl_p50_s"] == 0.0 and d["itl_p99_s"] == 0.0
    assert d["rejections"] == 0 and d["rejects"] == {}
    assert d["preemptions"] == 0 and d["resumes"] == 0


def test_rolling_slo_single_sample():
    """With one sample every percentile IS that sample (nearest-rank,
    no interpolation against phantom neighbors)."""
    s = serving.RollingSLO()
    s.note_ttft(0.25)
    s.note_itl(0.01)
    d = s.live_slos()
    assert d["ttft_n"] == 1
    assert d["ttft_p50_s"] == d["ttft_p99_s"] == 0.25
    assert d["itl_p50_s"] == d["itl_p99_s"] == 0.01


def test_rolling_slo_window_expiry(monkeypatch):
    """Samples older than window_s fall out of the percentiles — the
    30 s default window forgets a slow start once it is 30 s in the
    past, unlike ServingMetrics' whole-batch aggregates."""
    now = {"t": 100.0}
    monkeypatch.setattr(serving.time, "monotonic", lambda: now["t"])
    s = serving.RollingSLO(window_s=30.0)
    s.note_ttft(1.0)
    now["t"] = 110.0
    s.note_ttft(2.0)
    now["t"] = 131.0  # first sample now 31 s old, second only 21 s
    d = s.live_slos()
    assert d["ttft_n"] == 1 and d["ttft_p50_s"] == 2.0
    now["t"] = 200.0  # everything expired
    d = s.live_slos()
    assert d["ttft_n"] == 0 and d["ttft_p50_s"] == 0.0


def test_rolling_slo_lifecycle_counters_cumulative(monkeypatch):
    """Rejections/preemptions/resumes are cumulative, NOT windowed: a
    rejection burst 40 s ago still matters to an operator triaging
    goodput, so expiry must not erase it."""
    now = {"t": 0.0}
    monkeypatch.setattr(serving.time, "monotonic", lambda: now["t"])
    s = serving.RollingSLO(window_s=30.0)
    s.note_reject("queue_full")
    s.note_reject("queue_full")
    s.note_reject("ttft_budget")
    s.note_preempt()
    s.note_resume()
    now["t"] = 1000.0  # far past any window
    d = s.live_slos()
    assert d["rejections"] == 3
    assert d["rejects"] == {"queue_full": 2, "ttft_budget": 1}
    assert d["preemptions"] == 1 and d["resumes"] == 1
