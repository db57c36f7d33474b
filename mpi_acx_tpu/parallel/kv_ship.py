"""Per-layer KV shipping over host-plane partitioned channels.

The disaggregated-serving handoff (models/disagg.py): a prefill rank
maps ONE request's quantized KV cache — [L, H, D, prompt_bucket] int8
codes plus their f32 scales — onto ONE partitioned send with L
partitions, one per transformer layer. The prefill publishes partition
l with MPIX_Pready the moment layer l's K/V leave the device, while
layers l+1..L-1 are still computing — the reference's
produce-partition/Pready overlap (partitioned.cu:36-231) applied to
the serving plane's prompt-cache transfer instead of a kernel's
fragment stream. The decode rank polls MPIX_Parrived per layer and
splices arrivals into its slot cache without waiting for the tail of
the prompt pass.

Wire form (the EQuARX rule, PAPERS.md): quantized codes + scales are
the ONLY form KV ever takes on the wire — a bf16-cached prefill
quantizes before packing, never after. Per layer the partition packs
``[k codes | v codes | k scales | v scales]`` contiguously; codes are
int8 [H, D, bucket], scales f32 [H, 1, bucket] (ops/kvquant.py's
per-(position, head) scheme in the cache layout of
models/decoding.to_cache_layout — the bytes land in the decode rank's
slot or page as they arrive), so every partition has identical size
and the partitioned channel's equal-partition contract holds for any
layer count.

Channels are persistent (MPIX_Psend_init once per (peer, bucket
geometry), restarted per request with MPIX_Start) — the compile-once
discipline of models/serving.py applied to the wire: the handoff of
request N+1 reuses request N's channel, staging buffer, and flag
slots. docs/MIGRATION.md records the layer-partition layout as a
contract: partition index == layer index, in-partition packing as
above.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

# Host-plane tag space for the disagg handoff. KV rounds take one tag
# per prompt bucket (log2-indexed: channels for different buckets are
# distinct persistent requests and must not share a (peer, tag)
# message stream); descriptor tags live beside them.
KV_TAG_BASE = 7100
DESC_HDR_TAG = 7001
DESC_FIN_TAG = 7002


def kv_tag(bucket: int) -> int:
    """Per-bucket wire tag of the KV partitioned channel."""
    assert bucket > 0 and (bucket & (bucket - 1)) == 0, bucket
    return KV_TAG_BASE + bucket.bit_length()


def layer_part_bytes(bucket: int, heads: int, head_dim: int) -> int:
    """Bytes of one layer partition: k+v int8 codes plus k+v f32
    per-(position, head) scales."""
    codes = bucket * heads * head_dim      # int8, 1 byte each
    scales = bucket * heads * 4            # f32 [H, 1, bucket]
    return 2 * codes + 2 * scales


def pack_layer(row: np.ndarray, kq, ks, vq, vs) -> None:
    """Pack one layer's quantized K/V into staging row ``row`` (uint8,
    layer_part_bytes long). Enforces the wire rule: codes must already
    be int8 and scales f32 — a bf16 tensor here is a bug upstream, not
    something to quantize quietly at the wire."""
    kq = np.ascontiguousarray(kq)
    vq = np.ascontiguousarray(vq)
    ks = np.ascontiguousarray(ks)
    vs = np.ascontiguousarray(vs)
    assert kq.dtype == np.int8 and vq.dtype == np.int8, \
        (kq.dtype, vq.dtype, "wire form is int8 codes (EQuARX rule)")
    assert ks.dtype == np.float32 and vs.dtype == np.float32, \
        (ks.dtype, vs.dtype, "wire form is f32 scales (EQuARX rule)")
    o = 0
    for arr in (kq, vq, ks, vs):
        b = arr.reshape(-1).view(np.uint8)
        row[o:o + b.size] = b
        o += b.size
    assert o == row.size, (o, row.size)


def unpack_layer(row: np.ndarray, bucket: int, heads: int,
                 head_dim: int) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray, np.ndarray]:
    """Inverse of :func:`pack_layer`: staging row -> (kq, ks, vq, vs)
    with the shapes scatter_fn's per-slot splice expects (B=1 axis
    added by the caller when assembling the [L, 1, H, *, bucket] cache).
    Returns copies — the staging row is reused by the next round."""
    nc = bucket * heads * head_dim
    ns = bucket * heads * 4
    o = 0
    kq = row[o:o + nc].view(np.int8).reshape(heads, head_dim,
                                             bucket).copy()
    o += nc
    vq = row[o:o + nc].view(np.int8).reshape(heads, head_dim,
                                             bucket).copy()
    o += nc
    ks = row[o:o + ns].view(np.float32).reshape(heads, 1, bucket).copy()
    o += ns
    vs = row[o:o + ns].view(np.float32).reshape(heads, 1, bucket).copy()
    return kq, ks, vq, vs


@dataclass(frozen=True)
class ChannelGeom:
    """One persistent channel's shape key: everything that fixes the
    partition size and count."""

    peer: int
    bucket: int
    n_layers: int
    heads: int
    head_dim: int

    @property
    def part_bytes(self) -> int:
        return layer_part_bytes(self.bucket, self.heads, self.head_dim)


class _SendChannel:
    """One persistent L-partition send channel to one decode peer."""

    def __init__(self, rt, geom: ChannelGeom):
        self.rt = rt
        self.geom = geom
        self.staging = np.zeros((geom.n_layers, geom.part_bytes),
                                np.uint8)
        self.req = rt.psend_init(self.staging.reshape(-1),
                                 geom.n_layers, dest=geom.peer,
                                 tag=kv_tag(geom.bucket))
        self.open_round = False
        self.published = 0

    def begin(self) -> None:
        assert not self.open_round, "previous handoff round still open"
        self.rt.start(self.req)
        self.open_round = True
        self.published = 0

    def publish(self, layer: int, kq, ks, vq, vs) -> None:
        """Stage layer ``layer``'s quantized K/V and Pready its
        partition — called the moment the layer's prefill compute is
        done, while later layers still run."""
        pack_layer(self.staging[layer], kq, ks, vq, vs)
        self.rt.pready(layer, self.req)
        self.published += 1

    def abort_fill(self) -> None:
        """Publish every not-yet-published partition with whatever the
        staging rows hold (stale bytes — the receiver discards the
        handoff). Completing the round is what keeps the persistent
        channel restartable after a mid-handoff failure: a round with
        unpublished partitions would wedge both ends' FinishRound."""
        for layer in range(self.published, self.geom.n_layers):
            self.rt.pready(layer, self.req)
        self.published = self.geom.n_layers

    def finish(self):
        st = None
        try:
            st = self.rt.wait_partitioned(self.req)
        finally:
            self.open_round = False
        return st

    def close(self) -> None:
        self.rt.request_free(self.req)


class _RecvChannel:
    """One persistent L-partition recv channel from the prefill peer."""

    def __init__(self, rt, geom: ChannelGeom):
        self.rt = rt
        self.geom = geom
        self.staging = np.zeros((geom.n_layers, geom.part_bytes),
                                np.uint8)
        self.req = rt.precv_init(self.staging.reshape(-1),
                                 geom.n_layers, source=geom.peer,
                                 tag=kv_tag(geom.bucket))
        self.open_round = False

    def begin(self) -> None:
        assert not self.open_round, "previous handoff round still open"
        self.rt.start(self.req)
        self.open_round = True

    def poll(self, layer: int) -> bool:
        """MPIX_Parrived on partition ``layer``; an error-completed
        partition (peer died mid-ship) also reads arrived — the error
        surfaces in :meth:`finish`, where the caller's requeue path
        picks it up."""
        return self.rt.parrived(self.req, layer)

    def take(self, layer: int):
        """Unpack an arrived layer into (kq, ks, vq, vs) host arrays."""
        g = self.geom
        return unpack_layer(self.staging[layer], g.bucket, g.heads,
                            g.head_dim)

    def finish(self):
        st = None
        try:
            st = self.rt.wait_partitioned(self.req)
        finally:
            self.open_round = False
        return st

    def close(self) -> None:
        self.rt.request_free(self.req)


class KvShipper:
    """Prefill side: persistent per-(peer, bucket) send channels."""

    def __init__(self, rt, n_layers: int, heads: int, head_dim: int):
        self.rt = rt
        self.n_layers = n_layers
        self.heads = heads
        self.head_dim = head_dim
        self._chans: Dict[Tuple[int, int], _SendChannel] = {}

    def channel(self, peer: int, bucket: int) -> _SendChannel:
        key = (peer, bucket)
        if key not in self._chans:
            self._chans[key] = _SendChannel(
                self.rt, ChannelGeom(peer, bucket, self.n_layers,
                                     self.heads, self.head_dim))
        return self._chans[key]

    def close(self) -> None:
        for ch in self._chans.values():
            ch.close()
        self._chans.clear()


class KvReceiver:
    """Decode side: persistent per-(peer, bucket) recv channels."""

    def __init__(self, rt, n_layers: int, heads: int, head_dim: int):
        self.rt = rt
        self.n_layers = n_layers
        self.heads = heads
        self.head_dim = head_dim
        self._chans: Dict[Tuple[int, int], _RecvChannel] = {}

    def channel(self, peer: int, bucket: int) -> _RecvChannel:
        key = (peer, bucket)
        if key not in self._chans:
            self._chans[key] = _RecvChannel(
                self.rt, ChannelGeom(peer, bucket, self.n_layers,
                                     self.heads, self.head_dim))
        return self._chans[key]

    def close(self) -> None:
        for ch in self._chans.values():
            ch.close()
        self._chans.clear()
