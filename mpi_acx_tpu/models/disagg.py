"""Disaggregated prefill/decode serving: per-layer KV handoff.

Monolithic continuous batching (models/serving.py) runs prefill and
decode on the same rank, so every prompt pass stalls the decode batch
behind it — the interference disaggregation exists to remove. Here the
fleet splits by role (``ACX_ROLE``): prefill ranks run the prompt pass
and ship each layer's KV block THE MOMENT that layer finishes — one
partitioned send per request, one partition per layer, MPIX_Pready
fired from inside the layer loop while later layers still run — and
decode ranks poll MPIX_Parrived, splice arriving pages into their slot
caches through the same ``scatter_fn`` the monolithic server uses, and
own token generation. The wire mechanics (packing, persistent
channels, tags) live in parallel/kv_ship.py.

Wire form (the EQuARX rule): int8 codes + f32 scales are the ONLY form
KV takes on the wire, so decode slot caches are always the int8
variant and a disagg serve is bit-equal to the monolithic
``_serve(kv_int8=True)`` — for BOTH prefill-side cache variants
(``prefill_kv_int8``): quantize-at-compute and quantize-at-wire
produce identical bytes because prefill attention runs on the exact
bf16 K/V either way and ops/kvquant.py is deterministic. Pinned by
tests/test_disagg.py. The loopback mode does not copy that server: it
calls ``_serve``, the one fixed-slot loop, and hands it the wire handoff
as its prefill; the decode worker keeps its intake-driven loop and
shares the RequestBook.

Handoff protocol, per request (descriptor + one partitioned round):

  prefill                           decode
  -------                           ------
  HDR isend {rid, prompt_len,
             bucket}         ---->  irecv HDR; pick channel(peer,
                                    bucket); MPIX_Start recv round
  MPIX_Start send round
  layer 0 compute; quant;
  pack; Pready(0)            ---->  Parrived(0) -> splice layer 0
  layer 1 ...                ---->  ... (arrival overlaps prefill
  ...                               compute of later layers)
  head -> first token
  FIN isend {rid, first,
             prefill_us}     ---->  irecv FIN; all layers arrived;
  wait round                        wait round; scatter_fn -> slot
                                    armed; decode takes over

Failure semantics: a handoff that dies mid-round (peer loss, injected
fault) is REQUEUED — the decode side discards the partial splice and
re-arms for the re-shipped handoff; peer loss does not charge the
request's retry budget (serving.py's ``_peer_dead`` rule). A respawned
prefill rank re-ships every handoff it owns from scratch; the decode
side discards duplicates of already-completed requests by rid, which
makes the re-ship idempotent. The send side completes an aborted round
by publishing its remaining partitions with stale staging bytes
(``abort_fill``) so the persistent channel stays restartable.

Telemetry: every handoff records the TTFT split — prefill-compute vs
ship (publish -> last arrival) vs decode-pickup (unpack + scatter) —
as ``HandoffTelemetry`` rows on ``DisaggMetrics.handoffs``;
``overlap=False`` (ship only after the full prompt pass) is the
baseline an overlap A/B compares against.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpi_acx_tpu import reqlog
from mpi_acx_tpu.models.serving import (
    RequestBook, ServedBatch, ServingMetrics, _padded,
    _flight_dump_best_effort, _pct, _peer_dead, _per_request_n_new,
    _serve, _span_app_begin_best_effort, _span_app_end_best_effort,
    make_server_fns)
from mpi_acx_tpu.parallel.kv_ship import (
    DESC_FIN_TAG, DESC_HDR_TAG, KvReceiver, KvShipper)

# Descriptor magics ("ACXH"/"ACXF"): a handoff stream that desyncs
# (protocol bug, stale message from a dead incarnation) fails loudly at
# the magic check instead of splicing garbage into a slot cache.
_HDR_MAGIC = 0x41435848
_FIN_MAGIC = 0x41435846


def _hdr_wire(rid: int, prompt_len: int, bucket: int) -> np.ndarray:
    return np.array([_HDR_MAGIC, rid, prompt_len, bucket], np.int64)


def _fin_wire(rid: int, first_token: int, prefill_us: int,
              expose_us: int) -> np.ndarray:
    return np.array([_FIN_MAGIC, rid, first_token, prefill_us,
                     expose_us], np.int64)


def fleet_roles(size: int) -> List[str]:
    """Role of every rank, from $ACX_ROLE.

    Accepted forms (README knob table): a comma list mapping every rank
    (``prefill,decode,decode`` — the form acxrun propagates, since all
    ranks share one environment), a single role token (this rank's
    role; the fleet map defaults to rank 0 = prefill, rest = decode and
    the token must agree with it), or unset (loopback single-process
    serving — no fleet)."""
    spec = os.environ.get("ACX_ROLE", "").strip()
    default = ["prefill"] + ["decode"] * max(size - 1, 0)
    if not spec:
        return default
    if "," in spec:
        roles = [t.strip() for t in spec.split(",") if t.strip()]
        if len(roles) != size or any(r not in ("prefill", "decode")
                                     for r in roles):
            raise ValueError(
                f"ACX_ROLE={spec!r}: need one prefill|decode per rank "
                f"({size})")
        if "prefill" not in roles or "decode" not in roles:
            raise ValueError(
                f"ACX_ROLE={spec!r}: need at least one prefill and one "
                "decode rank")
        return roles
    if spec not in ("prefill", "decode"):
        raise ValueError(f"ACX_ROLE={spec!r}: prefill|decode|comma-list")
    return default


@dataclass
class HandoffTelemetry:
    """One handoff's TTFT split (DisaggMetrics.handoffs row)."""

    rid: int
    layers: int
    wire_bytes: int      # partitioned payload (codes + scales)
    prefill_s: float     # embed -> first token (incl. per-layer publish)
    ship_s: float        # FIN observed -> last partition arrived + round
    pickup_s: float      # unpack/assemble -> scatter -> slot armed
    overlap: bool        # per-layer Pready (True) vs ship-after-prefill
    expose_s: float = 0.0  # publish time EXPOSED after the head — the
    #                        wire cost overlap hides under compute (~0
    #                        with per-layer Pready; the full serialized
    #                        pack+publish without it)


@dataclass
class DisaggMetrics(ServingMetrics):
    """ServingMetrics grown by the handoff rows of a disagg serve."""

    handoffs: List[HandoffTelemetry] = field(default_factory=list)
    handoff_prefill_p50_s: float = 0.0
    handoff_ship_p50_s: float = 0.0
    handoff_pickup_p50_s: float = 0.0


def _finish_handoff_metrics(m: DisaggMetrics) -> DisaggMetrics:
    m.handoff_prefill_p50_s = _pct([h.prefill_s for h in m.handoffs], 0.5)
    m.handoff_ship_p50_s = _pct([h.ship_s for h in m.handoffs], 0.5)
    m.handoff_pickup_p50_s = _pct([h.pickup_s for h in m.handoffs], 0.5)
    return m


def make_layerwise_prefill_fns(params, cfg, family=None):
    """Per-layer prefill closures: (embed_fn, layer_fn, head_fn,
    quant_fn). The layer loop is hoisted to the host so the caller can
    publish layer l's KV the moment ``layer_fn`` returns — the
    per-layer Pready the monolithic scan prefill structurally cannot
    express. Each closure reuses the dense family's exact block pieces
    (_qkv/_attend/_mlp, same primitive sequence as the scan body), so
    the hoisted loop is bit-identical to ``family.prefill`` — logits,
    codes, and scales (pinned by tests/test_disagg.py).

    Only the dense transformer scaffold is supported (the layer
    internals are family-specific; llama/MoE would need their own
    block closures)."""
    from mpi_acx_tpu.backend import jit_bound
    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.models.decoding import pack_kv
    from mpi_acx_tpu.ops.wquant import wread
    if family is not None and family is not tfm:
        raise NotImplementedError(
            "layerwise prefill: dense transformer family only")

    def embed(params, tokens):
        S = tokens.shape[1]
        return (params["embed"][tokens]
                + params["pos"][:S]).astype(cfg.dtype)

    def layer_step(params, x, layer):
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, layer, 0,
                                               keepdims=False),
            params["layers"])
        q, k, v = tfm._qkv(cfg, lp, x)
        x = x + tfm._attend(cfg, q, k, v) @ wread(lp, "wo", x.dtype)
        return tfm._mlp(cfg, lp, x), k, v

    def head(params, x, last_index):
        x = tfm.layernorm(x, params["lnf_g"], params["lnf_b"])
        x = lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        return jnp.einsum("bsd,vd->bsv", x,
                          params["embed"].astype(x.dtype),
                          preferred_element_type=jnp.float32)

    @jax.jit
    def quant_fn(k, v):
        # The wire carries what lands in the cache: codes + scales in
        # cache layout ([1, H, *, bucket]; decoding.pack_kv).
        one = pack_kv(k, v, True)
        return one["k"], one["ks"], one["v"], one["vs"]

    # The weights are arguments of the compiled programs, not constants
    # baked into each of them (backend.jit_bound).
    return (jit_bound(embed, params), jit_bound(layer_step, params),
            jit_bound(head, params), quant_fn)


def _prefill_ship(ch, pfns, cfg, padded, last_index, overlap,
                  prefill_kv_int8, ship_fault=None, rid=0):
    """Run the layerwise prompt pass, publishing layer l's partition as
    it completes (``overlap``) or all partitions after the head
    (the ship-after-full-prefill baseline). Returns (first_token,
    prefill_s). The caller has already begun the channel round.

    ``prefill_kv_int8`` picks the prefill-side cache variant:
    quantize-at-compute (the prefill holds int8 codes, as a
    kv_int8-serving prefill rank would) vs quantize-at-wire (bf16
    staging, codes produced at pack time). Same wire bytes either way
    — prefill attention uses the exact bf16 K/V in both, and the
    quantizer is the single ops/kvquant.py definition.

    ``ship_fault(rid, layer)`` is a test hook called before layer
    ``layer``'s publish — raising from it models a prefill rank dying
    mid-handoff (tests/test_disagg.py).

    Returns (first_token, prefill_s, expose_s) — ``expose_s`` is the
    publish time left EXPOSED after the head finished: ~0 with per-layer
    overlap (everything already shipped under compute), the full
    serialized pack+publish cost without it. It rides the FIN
    descriptor."""
    embed_fn, layer_fn, head_fn, quant_fn = pfns
    t0 = time.perf_counter()
    x = embed_fn(padded)
    staged = []
    for layer in range(cfg.n_layers):
        x, k, v = layer_fn(x, layer)
        reqlog.emit("prefill_layer", rid, layer=layer)
        if prefill_kv_int8:
            # quantize-at-compute: codes are the prefill's cache form.
            kq, ks, vq, vs = (np.asarray(a) for a in quant_fn(k, v))
        else:
            # quantize-at-wire: bf16 staging until the pack.
            kq = ks = vq = vs = None
        if ship_fault is not None:
            ship_fault(rid, layer)
        if overlap:
            if kq is None:
                kq, ks, vq, vs = (np.asarray(a) for a in quant_fn(k, v))
            ch.publish(layer, kq[0], ks[0], vq[0], vs[0])
            reqlog.emit("ship_pready", rid, part=layer, overlap=True)
        else:
            staged.append((kq, ks, vq, vs) if kq is not None else (k, v))
    logits = head_fn(x, last_index)
    first = int(jnp.argmax(logits[0, 0]))
    t_head = time.perf_counter()
    if not overlap:
        for layer, st in enumerate(staged):
            if len(st) == 2:
                kq, ks, vq, vs = (np.asarray(a) for a in quant_fn(*st))
            else:
                kq, ks, vq, vs = st
            ch.publish(layer, kq[0], ks[0], vq[0], vs[0])
            reqlog.emit("ship_pready", rid, part=layer, overlap=False)
    t1 = time.perf_counter()
    return first, t1 - t0, t1 - t_head


def _splice_poll(ch, bucket, heads, head_dim, timeout_s=30.0):
    """Poll every layer partition, splicing arrivals into the assembled
    [L, 1, H, *, bucket] host cache as they land (arrival order, not
    layer order). Raises AcxTimeoutError past ``timeout_s`` — the
    bound that keeps a decode rank from spinning forever on a prefill
    rank that died before heartbeat detection."""
    from mpi_acx_tpu.runtime import ERR_TIMEOUT, AcxTimeoutError
    L = ch.geom.n_layers
    kq = np.zeros((L, 1, heads, head_dim, bucket), np.int8)
    vq = np.zeros_like(kq)
    ks = np.zeros((L, 1, heads, 1, bucket), np.float32)
    vs = np.zeros_like(ks)
    pending = set(range(L))
    deadline = time.monotonic() + timeout_s
    while pending:
        for layer in sorted(pending):
            if ch.poll(layer):
                lkq, lks, lvq, lvs = ch.take(layer)
                kq[layer, 0] = lkq
                ks[layer, 0] = lks
                vq[layer, 0] = lvq
                vs[layer, 0] = lvs
                pending.discard(layer)
        if pending and time.monotonic() > deadline:
            raise AcxTimeoutError(
                f"handoff: {len(pending)} layer partition(s) never "
                f"arrived within {timeout_s}s", ERR_TIMEOUT, ch.geom.peer,
                -1)
    return {"k": kq, "ks": ks, "v": vq, "vs": vs}


def _abort_rounds(send_ch, recv_ch, drain_s: float = 5.0) -> None:
    """Close both ends of a failed handoff round so the persistent
    channels stay restartable: the send side publishes its remaining
    partitions with stale staging bytes, the recv side drains arrivals
    (error-completed partitions read arrived too) and closes. Never
    raises — this runs on the requeue path, where the original
    exception is the one that matters."""
    try:
        if send_ch is not None and send_ch.open_round:
            send_ch.abort_fill()
            send_ch.finish()
    except Exception:  # noqa: BLE001 — cleanup must not mask the cause
        send_ch.open_round = False
    try:
        if recv_ch is not None and recv_ch.open_round:
            deadline = time.monotonic() + drain_s
            while (not all(recv_ch.poll(p)
                           for p in range(recv_ch.geom.n_layers))
                   and time.monotonic() < deadline):
                pass
            recv_ch.finish()
    except Exception:  # noqa: BLE001 — cleanup must not mask the cause
        recv_ch.open_round = False


_loopback_runtime = None


def _loopback_rt():
    """Process-singleton loopback Runtime (rank 0 of 1) for the
    single-process disagg mode; finalized at interpreter exit. A
    caller running under acxrun passes its own Runtime instead."""
    global _loopback_runtime
    if _loopback_runtime is None:
        import atexit

        from mpi_acx_tpu.runtime import Runtime
        _loopback_runtime = Runtime()
        atexit.register(_loopback_runtime.finalize)
    return _loopback_runtime


def serve_disagg_greedy(params, cfg, prompts: Sequence[np.ndarray], n_new,
                        n_slots: int, max_len: int, family=None,
                        eos: Optional[int] = None, chunk: int = 1,
                        server_fns=None, prefill_kv_int8: bool = False,
                        max_request_retries: int = 2, rt=None,
                        overlap: bool = True,
                        ship_fault: Optional[Callable] = None,
                        poll_timeout_s: float = 30.0) -> ServedBatch:
    """Disaggregated greedy serve. With $ACX_ROLE unset: loopback mode
    — this process plays both roles against a self-channel, so the
    full wire path (descriptors, partitioned round, per-layer Pready /
    Parrived, splice) runs single-process, as the prefill handed to
    the monolithic server's own loop (``serving._serve``); outputs are
    bit-equal to ``serve_greedy(..., kv_int8=True)``. With $ACX_ROLE
    set (under acxrun): dispatches to this rank's role worker —
    prefill ranks return an empty batch, decode ranks return their
    requests' outputs (None rows elsewhere).

    ``server_fns`` must be a ``make_server_fns(..., kv_int8=True)``
    tuple — decode slots are always int8, the wire form.
    ``prefill_kv_int8`` picks the prefill-side variant (see
    ``_prefill_ship``); ``overlap=False`` ships only after the full
    prompt pass (the A/B baseline). ``ship_fault(rid, layer)`` is a
    failure-injection hook (see ``_prefill_ship``)."""
    roles = None
    if os.environ.get("ACX_ROLE", "").strip():
        if rt is None:
            raise ValueError("fleet mode needs an explicit Runtime")
        roles = fleet_roles(rt.size)
        if roles[rt.rank] == "prefill":
            run_prefill_worker(rt, params, cfg, prompts, max_len,
                               family=family, overlap=overlap,
                               prefill_kv_int8=prefill_kv_int8)
            return ServedBatch([None] * len(prompts),
                               _finish_handoff_metrics(DisaggMetrics()))
        return run_decode_worker(
            rt, params, cfg, prompts, n_new, n_slots, max_len,
            family=family, eos=eos, chunk=chunk, server_fns=server_fns,
            max_request_retries=max_request_retries,
            poll_timeout_s=poll_timeout_s)
    return _serve_disagg_loopback(
        params, cfg, prompts, n_new, n_slots, max_len, family, eos,
        chunk, server_fns, prefill_kv_int8, max_request_retries,
        rt if rt is not None else _loopback_rt(), overlap, ship_fault,
        poll_timeout_s)


def _serve_disagg_loopback(params, cfg, prompts, n_new, n_slots, max_len,
                           family, eos, chunk, server_fns,
                           prefill_kv_int8, max_request_retries, rt,
                           overlap, ship_fault, poll_timeout_s):
    """Single-process disagg serve: models/serving.py's ``_serve`` — the
    one fixed-slot loop — with a real wire handoff (descriptor exchange
    + partitioned round against the loopback transport) as the prefill
    it is given. The decode loop IS the monolithic one; that, plus the
    wire carrying the exact int8 codes the monolithic fill would have
    produced, is the bit-equality argument.

    What a caller can tell from the monolithic server, kept: an
    oversized request is an AssertionError here, not a RequestRejected
    row; and a peer-loss shaped step failure sheds no slot (one process
    has no peer whose loss shrinks it)."""
    n_new = _per_request_n_new(prompts, n_new)
    assert all(len(p) + n + chunk <= max_len
               for p, n in zip(prompts, n_new)), "request exceeds max_len"
    assert all(len(p) + n + chunk <= cfg.max_seq
               for p, n in zip(prompts, n_new)), "request exceeds max_seq"

    pfns = make_layerwise_prefill_fns(params, cfg, family)
    shipper = KvShipper(rt, cfg.n_layers, cfg.n_heads, cfg.head_dim)
    receiver = KvReceiver(rt, cfg.n_layers, cfg.n_heads, cfg.head_dim)
    handoffs = {}                # rid -> the handoff that seated it

    def handoff(_prefill_fn, rid, padded, S):
        """The prefill-side layer loop publishes into the loopback
        self-channel, the decode side splices: the wire path the
        role-split fleet runs, serialized in one process. Returns the
        first token off the FIN descriptor and the spliced cache, on
        the device."""
        bucket = padded.shape[1]
        send_ch = shipper.channel(rt.rank, bucket)
        recv_ch = receiver.channel(rt.rank, bucket)
        try:
            # Descriptor header: recv posted first, both waited — the
            # exchange is atomic, so a later handoff failure can never
            # leave a dangling descriptor in the loopback stream.
            hdr = np.zeros(4, np.int64)
            hr = rt.irecv_enqueue(hdr, source=rt.rank, tag=DESC_HDR_TAG)
            rt.wait(rt.isend_enqueue(_hdr_wire(rid, S, bucket),
                                     dest=rt.rank, tag=DESC_HDR_TAG))
            rt.wait(hr)
            assert int(hdr[0]) == _HDR_MAGIC and int(hdr[1]) == rid, hdr
            reqlog.emit("ship_hdr", rid, side="loopback", bucket=bucket)
            recv_ch.begin()
            send_ch.begin()
            first, prefill_s, expose_s = _prefill_ship(
                send_ch, pfns, cfg, jnp.asarray(padded), S - 1, overlap,
                prefill_kv_int8, ship_fault=ship_fault, rid=rid)
            reqlog.emit("prefill_end", rid, first_token=first,
                        prefill_s=prefill_s)
            fin = np.zeros(5, np.int64)
            fr = rt.irecv_enqueue(fin, source=rt.rank, tag=DESC_FIN_TAG)
            rt.wait(rt.isend_enqueue(
                _fin_wire(rid, first, int(prefill_s * 1e6),
                          int(expose_s * 1e6)),
                dest=rt.rank, tag=DESC_FIN_TAG))
            rt.wait(fr)
            assert int(fin[0]) == _FIN_MAGIC and int(fin[1]) == rid, fin
            reqlog.emit("ship_fin", rid, side="loopback")
            t_ship = time.perf_counter()
            one = _splice_poll(recv_ch, bucket, cfg.n_heads,
                               cfg.head_dim, timeout_s=poll_timeout_s)
            send_ch.finish()
            recv_ch.finish()
            t_pick = time.perf_counter()
            one = {k: jnp.asarray(v) for k, v in one.items()}
        except Exception:  # noqa: BLE001 — the loop requeues the request
            _abort_rounds(send_ch, recv_ch)
            raise
        handoffs.pop(rid, None)
        handoffs[rid] = HandoffTelemetry(
            rid=rid, layers=cfg.n_layers,
            wire_bytes=cfg.n_layers * send_ch.geom.part_bytes,
            prefill_s=prefill_s, ship_s=t_pick - t_ship,
            pickup_s=time.perf_counter() - t_pick,
            overlap=overlap, expose_s=expose_s)
        return int(fin[2]), one, None

    batch = _serve(params, cfg, prompts, n_new, n_slots, max_len, family,
                   eos, chunk, server_fns, True, None, None, handoff,
                   max_request_retries=max_request_retries,
                   shed_on_peer_loss=False)
    shipper.close()
    receiver.close()
    batch.metrics = _finish_handoff_metrics(DisaggMetrics(
        **vars(batch.metrics), handoffs=list(handoffs.values())))
    return batch


# -- fleet-mode role workers (under acxrun, $ACX_ROLE set) -----------------


def run_prefill_worker(rt, params, cfg, prompts, max_len, family=None,
                       overlap: bool = True,
                       prefill_kv_int8: bool = False) -> int:
    """Prefill rank's loop: for every owned request (static map: rid ->
    prefill rank ``rid % n_prefill``, decode rank ``rid % n_decode``),
    run the layerwise prompt pass and ship it. A respawned incarnation
    of this rank simply reruns the loop from rid 0 — re-shipping is
    idempotent because the decode side discards duplicates by rid.
    Returns the number of handoffs shipped."""
    roles = fleet_roles(rt.size)
    prefill_ranks = [r for r, ro in enumerate(roles) if ro == "prefill"]
    decode_ranks = [r for r, ro in enumerate(roles) if ro == "decode"]
    me = prefill_ranks.index(rt.rank)
    pfns = make_layerwise_prefill_fns(params, cfg, family)
    shipper = KvShipper(rt, cfg.n_layers, cfg.n_heads, cfg.head_dim)
    my_rids = [rid for rid in range(len(prompts))
               if rid % len(prefill_ranks) == me]
    for depth, rid in enumerate(my_rids):
        # The prefill rank is the fleet's request entry point: its
        # admit/queue events open every journey the decode rank's
        # finish will close (tools/acx_request.py joins them by rid).
        reqlog.emit("admit", rid, prompt_len=len(prompts[rid]))
        reqlog.emit("queue", rid, depth=depth)
    shipped = 0
    for rid in my_rids:
        dst = decode_ranks[rid % len(decode_ranks)]
        prompt = np.asarray(prompts[rid], np.int32)
        S = len(prompt)
        padded = _padded(prompt, max_len, cfg.max_seq)
        bucket = padded.shape[1]
        ch = shipper.channel(dst, bucket)
        spanned = _span_app_begin_best_effort(rid)
        reqlog.emit("prefill_start", rid, prompt_len=S, bucket=bucket)
        try:
            rt.wait(rt.isend_enqueue(_hdr_wire(rid, S, bucket), dest=dst,
                                     tag=DESC_HDR_TAG))
            reqlog.emit("ship_hdr", rid, side="send", bucket=bucket,
                        dst=dst)
            ch.begin()
            first, prefill_s, expose_s = _prefill_ship(
                ch, pfns, cfg, jnp.asarray(padded), S - 1, overlap,
                prefill_kv_int8, rid=rid)
            reqlog.emit("prefill_end", rid, first_token=first,
                        prefill_s=prefill_s)
            rt.wait(rt.isend_enqueue(
                _fin_wire(rid, first, int(prefill_s * 1e6),
                          int(expose_s * 1e6)), dest=dst,
                tag=DESC_FIN_TAG))
            reqlog.emit("ship_fin", rid, side="send", dst=dst)
            ch.finish()
            shipped += 1
        finally:
            if spanned:
                _span_app_end_best_effort()
    shipper.close()
    return shipped


def run_decode_worker(rt, params, cfg, prompts, n_new, n_slots, max_len,
                      family=None, eos=None, chunk: int = 1,
                      server_fns=None, max_request_retries: int = 2,
                      poll_timeout_s: float = 30.0,
                      page_tokens: int = None,
                      n_pages: int = None) -> ServedBatch:
    """Decode rank's loop: consume handoffs from the prefill rank,
    splice them into slot caches, and generate. Returns a ServedBatch
    with this rank's requests filled in (None rows elsewhere).

    ``page_tokens`` switches the decode cache from fixed per-slot rows
    to the paged pool (models/kvpage.py): an inbound handoff's bucket
    rows land in freshly allocated pages (the wire already carries
    int8 codes + f32 scales — exactly the page-resident form, so the
    splice is a page scatter, no re-quantization) and the request's
    FULL page budget (prompt + n_new + chunk) is reserved at seat
    time — a seated request can never be starved mid-decode by a later
    arrival. Outputs stay bit-equal to the fixed-slot worker's.

    Failure semantics: a handoff that dies mid-flight (prefill rank
    killed) raises out of the intake; the request is requeued —
    UNCHARGED when the failure is peer-loss shaped — and satisfied by
    the respawned prefill rank's re-ship. Handoffs for already-retired
    rids (the re-ship's duplicates) are drained and discarded."""
    if family is None:
        from mpi_acx_tpu.models import transformer as family  # noqa: N813
    roles = fleet_roles(rt.size)
    prefill_ranks = [r for r, ro in enumerate(roles) if ro == "prefill"]
    decode_ranks = [r for r, ro in enumerate(roles) if ro == "decode"]
    assert len(prefill_ranks) == 1, \
        "decode worker handles a single prefill rank for now"
    src = prefill_ranks[0]
    n_new = _per_request_n_new(prompts, n_new)
    my_rids = [rid for rid in range(len(prompts))
               if decode_ranks[rid % len(decode_ranks)] == rt.rank]

    paged = page_tokens is not None
    if paged:
        from mpi_acx_tpu.models import kvpage
        pt = int(page_tokens)
        assert max_len % pt == 0, (max_len, pt)
        if n_pages is None:
            n_pages = n_slots * (max_len // pt)
        pkv = kvpage.PagedKV(cfg, family, n_slots, max_len, pt, n_pages,
                             kv_int8=True)
        step_fn = kvpage.make_paged_step_fn(params, cfg, family, chunk,
                                            pt)
        scatter_fn = None
    else:
        if server_fns is None:
            server_fns = make_server_fns(params, cfg, family, chunk=chunk,
                                         kv_int8=True)
        (_, step_fn, scatter_fn, fns_chunk, fns_int8,
         fns_sample) = server_fns
        assert fns_chunk == chunk and fns_int8 and fns_sample is None

    receiver = KvReceiver(rt, cfg.n_layers, cfg.n_heads, cfg.head_dim)
    if not paged:
        slots = family.init_kv_cache(cfg, n_slots, max_len, kv_int8=True)
        slots["pos"] = jnp.zeros((n_slots,), jnp.int32)
    keys = jax.random.split(jax.random.key(0), n_slots)
    # The book over this rank's own rids. Its queue holds the requests
    # still awaited from the prefill rank: the wire, not the queue,
    # decides which one arrives next.
    book = RequestBook(prompts, n_new, n_slots, eos, chunk,
                       max_request_retries, rids=my_rids)
    handoffs: List[HandoffTelemetry] = []

    def intake(b) -> bool:
        """Consume the next inbound handoff. Seats it in slot ``b`` and
        returns True; returns False for a discarded duplicate or a
        failed handoff (requeued — the re-ship will satisfy it)."""
        nonlocal slots
        hdr = np.zeros(4, np.int64)
        recv_ch = None
        rid = -1
        try:
            rt.wait(rt.irecv_enqueue(hdr, source=src, tag=DESC_HDR_TAG))
            assert int(hdr[0]) == _HDR_MAGIC, hdr
            rid, S, bucket = int(hdr[1]), int(hdr[2]), int(hdr[3])
            reqlog.emit("ship_hdr", rid, side="recv", bucket=bucket,
                        src=src)
            recv_ch = receiver.channel(src, bucket)
            recv_ch.begin()
            one = _splice_poll(recv_ch, bucket, cfg.n_heads,
                               cfg.head_dim, timeout_s=poll_timeout_s)
            fin = np.zeros(5, np.int64)
            rt.wait(rt.irecv_enqueue(fin, source=src, tag=DESC_FIN_TAG))
            assert (int(fin[0]) == _FIN_MAGIC
                    and int(fin[1]) == rid), (fin, rid)
            reqlog.emit("ship_fin", rid, side="recv", src=src)
            recv_ch.finish()
            if rid not in book.queue:
                return False      # re-ship duplicate: drained, dropped
            t_pick = time.perf_counter()
            one = {k: jnp.asarray(v) for k, v in one.items()}
            if paged:
                # Reserve the request's FULL page budget up front (no
                # growth path in this loop) and splice the wire's
                # int8+scales bucket rows — already the page-resident
                # form — straight into the prompt pages.
                need = kvpage.pages_needed(S + n_new[rid] + chunk, pt)
                pages = pkv.alloc_evicting(need)
                if pages is None:
                    raise RuntimeError(
                        f"decode rank {rt.rank}: page pool dry seating "
                        f"rid={rid} (need {need} pages, "
                        f"{pkv.alloc.free_count} free) — size n_pages "
                        "to n_slots*max_len/page_tokens")
                try:
                    pkv.scatter_prompt(
                        {k: v for k, v in one.items() if k != "pos"},
                        pages[:kvpage.pages_needed(S, pt)])
                    pkv.seat(b, [], pages, S, rid=rid)
                except Exception:
                    for p in pages:
                        pkv.alloc.decref(p)
                    raise
            else:
                slots = scatter_fn(slots, one, b, S)
                reqlog.emit("seat", rid, slot=b, pos=S)
            pickup_s = time.perf_counter() - t_pick
        except Exception as exc:  # noqa: BLE001 — any handoff failure
            # Snapshot the comm plane before healing: the flight dump
            # is the evidence trail acx_doctor (and the chaos oracle's
            # doctor_verdict audit) attributes the dead link from.
            if book.hang_dumps == 0 and _flight_dump_best_effort():
                book.hang_dumps += 1
            _abort_rounds(None, recv_ch)
            if rid in book.queue:
                book.queue.remove(rid)    # requeue puts it at the back
                book.requeue(rid, exc, charge=not _peer_dead(exc))
            elif rid < 0 and not _peer_dead(exc):
                raise
            return False
        book.queue.remove(rid)
        book.seat(b, rid, int(fin[2]))
        handoffs.append(HandoffTelemetry(
            rid=rid, layers=cfg.n_layers,
            wire_bytes=cfg.n_layers * recv_ch.geom.part_bytes,
            prefill_s=int(fin[3]) / 1e6, ship_s=0.0, pickup_s=pickup_s,
            overlap=True, expose_s=int(fin[4]) / 1e6))
        return True

    def retire_finished(b):
        nonlocal slots
        if book.owner[b] < 0 or not book.slot_finished(b):
            return
        book.finish_request(b)
        if paged:
            pkv.release(b)        # pages back to the pool, slot parked
        else:
            slots["pos"] = slots["pos"].at[b].set(0)

    while book.queue or book.active():
        # Seat inbound handoffs on every free slot before stepping.
        while book.queue and (b := book.free_slot()) is not None:
            if intake(b):
                retire_finished(b)
        if not book.active():
            continue
        book.sample_gauges()
        step_t0 = time.perf_counter()
        if paged:
            state = pkv.device_state(book.left())
            state, toks, keys = step_fn(
                state, jnp.asarray(book.last_tok), keys)
            pkv.absorb(state)
            kvpage.publish_page_stats_best_effort(
                pkv.alloc.free_count, pkv.alloc.shared_count(), 0, 0, 0)
        else:
            slots, toks, keys = step_fn(
                slots, jnp.asarray(book.last_tok), keys)
        block = np.asarray(toks, np.int32)
        book.deliver(block, time.perf_counter() - step_t0)
        for b in range(n_slots):
            retire_finished(b)

    receiver.close()
    return ServedBatch(book.done, _finish_handoff_metrics(
        book.metrics(DisaggMetrics, handoffs=handoffs)))
