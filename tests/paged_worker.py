"""Worker for the paged-check fleet legs: one role-split serving rank
whose DECODE side seats shipped KV into the PAGED pool
(models/kvpage.py, docs/DESIGN.md §19) instead of fixed slot rows.

Launched by acxrun (``acxrun -np 3 -transport socket python3
tests/paged_worker.py`` with ``ACX_ROLE=prefill,decode,decode``): the
prefill rank runs the unchanged per-layer KV shipper — the wire format
(int8 codes + f32 scales, partition index == layer) is already the
page-resident form, so §17 needs no update to feed a paged decode —
and each decode rank runs ``run_decode_worker(page_tokens=...)``, then
VERIFIES its outputs bit-for-bit against a local monolithic
``serve_greedy(..., kv_int8=True)`` of the same requests. Prints
``DISAGG_OK`` / ``DISAGG_SHIPPED`` plus one ``PAGED_ROW {json}`` line
per rank.

Under the chaos leg the prefill rank is killed mid-handoff and
respawned by the acx_chaos supervisor; re-shipping is idempotent
(decode discards duplicates by rid) and a torn handoff requeues
UNCHARGED — same rules as tests/disagg_worker.py, now with the paged
intake's allocate/rollback path in the loop.

Knobs: ACX_DISAGG_REQS scales the request count; ACX_PAGED_PT
overrides the page size (default 8 — several pages per request on the
tiny config, so the allocator actually cycles); ACX_PAGED_CHUNK the
decode chunk (default 1; at 4 requests end mid-chunk, and the worker
tells each chunk so: every ``device_state`` call is checked to carry
the book's ``left``).
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
from mpi_acx_tpu.models import kvpage  # noqa: E402
from mpi_acx_tpu.models import transformer as tfm  # noqa: E402
from mpi_acx_tpu.models.disagg import (fleet_roles, run_decode_worker,  # noqa: E402
                                       run_prefill_worker)
from mpi_acx_tpu.models.serving import serve_greedy  # noqa: E402


def main():
    n_reqs = int(os.environ.get("ACX_DISAGG_REQS", "6"))
    pt = int(os.environ.get("ACX_PAGED_PT", "8"))

    cfg = tfm.tiny_config()
    lens = [5, 11, 3, 17, 8, 13, 7, 21, 4, 9]
    max_len, n_slots = 64, 2
    chunk = int(os.environ.get("ACX_PAGED_CHUNK", "1"))
    params = tfm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=lens[i % len(lens)])
               .astype(np.int32) for i in range(n_reqs)]
    n_new = [3 + (i % 5) for i in range(n_reqs)]

    rt = runtime.Runtime()
    rt.set_deadline(60_000)
    roles = fleet_roles(rt.size)
    role = roles[rt.rank]

    t0 = time.perf_counter()
    if role == "prefill":
        shipped = run_prefill_worker(rt, params, cfg, prompts, max_len,
                                     family=tfm)
        wall = time.perf_counter() - t0
        print(f"DISAGG_SHIPPED rank={rt.rank} n={shipped}", flush=True)
        print("PAGED_ROW " + json.dumps({
            "rank": rt.rank, "role": "prefill",
            "wall_s": round(wall, 4)}), flush=True)
    else:
        # every chunk is told how many tokens each slot still owes
        handed, device_state = [], kvpage.PagedKV.device_state

        def recording(self, left=None):
            handed.append(left)
            return device_state(self, left)
        kvpage.PagedKV.device_state = recording
        batch = run_decode_worker(
            rt, params, cfg, prompts, n_new, n_slots=n_slots,
            max_len=max_len, family=tfm, chunk=chunk,
            page_tokens=pt)
        wall = time.perf_counter() - t0
        mono = serve_greedy(params, cfg, prompts, n_new, n_slots=n_slots,
                            max_len=max_len, chunk=chunk, kv_int8=True)
        m = batch.metrics
        mine = [r.rid for r in m.per_request]
        assert mine, "decode rank owns no requests"
        assert handed and all(h is not None for h in handed), handed
        assert m.decode_tokens == sum(
            int(np.minimum(h, chunk).sum()) for h in handed), handed
        for rid in mine:
            assert batch[rid] is not None, f"request {rid} unserved"
            np.testing.assert_array_equal(
                batch[rid], mono[rid],
                err_msg=f"rank {rt.rank} request {rid} paged != mono")
        print(f"DISAGG_OK rank={rt.rank} rids={mine} "
              f"requeues={m.requeues} peer_requeues={m.peer_requeues}",
              flush=True)
        print("PAGED_ROW " + json.dumps({
            "rank": rt.rank, "role": "decode",
            "wall_s": round(wall, 4), "page_tokens": pt,
            "requests": len(mine),
            "ttft_p50_s": round(m.ttft_p50_s, 6),
            "requeues": m.requeues,
            "peer_requeues": m.peer_requeues}), flush=True)
    rt.barrier()
    rt.finalize()


if __name__ == "__main__":
    main()
