"""Worker for the disagg-check legs: one role-split serving rank.

Launched by acxrun (``acxrun -np 3 -transport socket python3
tests/disagg_worker.py`` with ``ACX_ROLE=prefill,decode,decode``):
every rank runs the same deterministic workload through
``serve_disagg_greedy``, which dispatches on this rank's role — the
prefill rank ships per-layer KV handoffs, the decode ranks splice,
generate, and then each VERIFIES its outputs bit-for-bit against a
local monolithic ``serve_greedy(..., kv_int8=True)`` of the same
requests. Prints ``DISAGG_OK`` / ``DISAGG_SHIPPED`` plus one
``DISAGG_ROW {json}`` line per rank.

Under the chaos leg the prefill rank is killed mid-handoff and
respawned by the acx_chaos supervisor; the respawn re-runs this script
from rid 0 — re-shipping is idempotent (decode discards duplicates by
rid) — and the decode ranks requeue the torn handoff UNCHARGED.

Knobs: ACX_DISAGG_OVERLAP=0 ships only after the full prompt pass (the
A/B baseline), ACX_DISAGG_PREFILL_INT8=1 uses the quantize-at-compute
prefill cache variant, ACX_DISAGG_REQS scales the request count, and
ACX_DISAGG_BIG=1 switches to a wider model + longer prompts so the
exposed-ship time (the wire cost per-layer overlap hides) is well above
clock noise for an overlap A/B.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402

from mpi_acx_tpu import runtime  # noqa: E402
from mpi_acx_tpu.models import transformer as tfm  # noqa: E402
from mpi_acx_tpu.models.disagg import fleet_roles, serve_disagg_greedy  # noqa: E402
from mpi_acx_tpu.models.serving import make_server_fns, serve_greedy  # noqa: E402


def main():
    overlap = os.environ.get("ACX_DISAGG_OVERLAP", "1") != "0"
    prefill_int8 = os.environ.get("ACX_DISAGG_PREFILL_INT8", "0") == "1"
    n_reqs = int(os.environ.get("ACX_DISAGG_REQS", "6"))
    big = os.environ.get("ACX_DISAGG_BIG", "0") == "1"

    if big:
        # Wider heads + near-bucket prompts: ~1 MiB of codes+scales per
        # handoff, so the exposed-ship time is milliseconds, not noise.
        cfg = tfm.tiny_config(d_model=256, n_heads=8, max_seq=1024)
        lens = [450, 380, 500, 410, 470, 360]
        max_len, n_slots, chunk = 576, 2, 1
    else:
        cfg = tfm.tiny_config()
        lens = [5, 11, 3, 17, 8, 13, 7, 21, 4, 9]
        max_len, n_slots, chunk = 64, 2, 1
    params = tfm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=lens[i % len(lens)])
               .astype(np.int32) for i in range(n_reqs)]
    n_new = [3 + (i % 5) for i in range(n_reqs)]

    rt = runtime.Runtime()
    # A torn fleet (killed peer before heartbeat detection) must surface
    # as a typed error, not an infinite block on a posted descriptor.
    rt.set_deadline(60_000)
    roles = fleet_roles(rt.size)
    role = roles[rt.rank]

    fns = None
    if role == "decode":
        fns = make_server_fns(params, cfg, tfm, chunk=chunk, kv_int8=True)

    t0 = time.perf_counter()
    batch = serve_disagg_greedy(
        params, cfg, prompts, n_new, n_slots=n_slots, max_len=max_len,
        chunk=chunk, server_fns=fns, rt=rt, overlap=overlap,
        prefill_kv_int8=prefill_int8)
    wall = time.perf_counter() - t0

    if role == "prefill":
        print(f"DISAGG_SHIPPED rank={rt.rank} n={len(prompts)}",
              flush=True)
        print("DISAGG_ROW " + json.dumps({
            "rank": rt.rank, "role": "prefill", "wall_s": round(wall, 4),
            "overlap": overlap}), flush=True)
    else:
        mono = serve_greedy(params, cfg, prompts, n_new, n_slots=n_slots,
                            max_len=max_len, chunk=chunk, kv_int8=True,
                            server_fns=fns)
        m = batch.metrics
        mine = [r.rid for r in m.per_request]
        assert mine, "decode rank owns no requests"
        for rid in mine:
            assert batch[rid] is not None, f"request {rid} unserved"
            np.testing.assert_array_equal(
                batch[rid], mono[rid],
                err_msg=f"rank {rt.rank} request {rid} disagg != mono")
        wire = sum(h.wire_bytes for h in m.handoffs)
        ship_wall = sum(h.pickup_s for h in m.handoffs) or 1e-9
        exposes = sorted(h.expose_s for h in m.handoffs)
        print(f"DISAGG_OK rank={rt.rank} rids={mine} "
              f"requeues={m.requeues} peer_requeues={m.peer_requeues}",
              flush=True)
        print("DISAGG_ROW " + json.dumps({
            "rank": rt.rank, "role": "decode", "wall_s": round(wall, 4),
            "overlap": overlap, "prefill_int8": prefill_int8,
            "requests": len(mine),
            "ttft_p50_s": round(m.ttft_p50_s, 6),
            "pickup_p50_s": round(m.handoff_pickup_p50_s, 6),
            "expose_p50_s": round(exposes[len(exposes) // 2], 6),
            "handoff_wire_bytes": wire,
            "handoff_gbps": round(wire / ship_wall / 1e9, 4),
            "requeues": m.requeues,
            "peer_requeues": m.peer_requeues}), flush=True)
    rt.barrier()
    rt.finalize()


if __name__ == "__main__":
    main()
