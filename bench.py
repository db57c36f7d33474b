"""tpu-acx benchmark — prints ONE JSON line for the driver.

Primary metric: enqueued Isend/Irecv ping-pong p50 latency (µs) through the
full native stack (host execution queue -> flag table -> proxy -> socket
wire), 2 processes under acxrun — BASELINE.md metric #2. Also reports
partitioned-exchange bandwidth (host plane) and flagship-model forward
throughput + MFU on the TPU chip.

The TPU measurement runs in SUBPROCESSES with retries (a chip belongs
to one process, so this parent stays off JAX): a child whose backend
init fails or hangs is killed by timeout and retried; after the last attempt the failure is reported
LOUDLY as a "tpu_error" field in the JSON line instead of being dropped.

Capture is INCREMENTAL (rounds 2-4 lost entire windows to all-or-nothing
600 s children): a cheap probe child gates the expensive ones, each
metric group runs in its OWN child with its own timeout, every child's
rows are banked to BENCH_BANK.json the moment they land, and in --full
mode BENCH_FULL.json is rewritten after EVERY child — a driver kill or
a chip lost mid-run keeps everything measured up to that point.

`python bench.py --full` additionally re-measures the secondary
BASELINE.md rows (flash-attention speedup @ S=4096, KV-cache decode
tok/s, AdamW train-step tok/s) and regression-checks all starred/TPU
rows against BASELINE.md with a 10% tolerance, writing BENCH_FULL.json
and exiting nonzero on any regression.

The reference (NVIDIA/mpi-acx) publishes no numbers (SURVEY.md §6);
BASELINE.md records our own measurements as the baseline, so
vs_baseline tracks regression/improvement across rounds.
"""

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Round-2 baseline measurements (this machine, recorded in BASELINE.md).
BASELINE_P50_US = 26.6
BASELINE_PART_BW_GBPS = 1.12
BASELINE_GPT2_FWD_TOKS = 221_900.0
BASELINE_GPT2_FWD_B16S512_TOKS = 377_600.0  # saturating shape (r3)
# Device-side-loop methodology (round 3); round-2's 5.3x was host-side
# per-call timing, which reports dispatch latency rather than kernel
# time (see BASELINE.md).
BASELINE_FLASH_SPEEDUP_4096 = 2.4
BASELINE_DECODE_TOKS = 2_700.0
BASELINE_TRAIN_TOKS = 78_000.0  # device-side scan-loop measurement (r3)
# Deterministic (CPU-compiled HLO) — measured 3.88x; gate below it.
BASELINE_QUANT_TRAFFIC_REDUCTION = 3.5

# v5e bf16 peak: 197 TFLOP/s per chip (public spec).
V5E_BF16_PEAK_FLOPS = 197e12
GPT2_SMALL_PARAMS = 124e6


def native_bench(msg_bytes: int | None = None):
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True)
    cmd = [os.path.join(REPO, "build", "acxrun"), "-np", "2", "-timeout",
           "300", os.path.join(REPO, "build", "bench_pingpong")]
    if msg_bytes is not None:
        cmd.append(str(msg_bytes))
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=400)
    m = re.search(r"pingpong_p50_us=([\d.]+) pingpong_p99_us=([\d.]+) "
                  r"part_bw_gbps=([\d.]+)", r.stdout)
    if not m:
        raise RuntimeError(f"bench_pingpong failed: {r.stdout} {r.stderr}")
    return float(m.group(1)), float(m.group(2)), float(m.group(3))


def native_stripe_sweep(lane_counts=(1, 2, 4)):
    """Striped-wire bandwidth rows (DESIGN.md §15). ACX_STRIPES is fixed
    at transport construction, so each lane count is its own acxrun on
    the socket plane; ACX_RV_THRESHOLD=0 forces the eager path so large
    messages actually stripe instead of taking rendezvous."""
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True)
    rows = []
    for s in lane_counts:
        env = dict(os.environ, ACX_BENCH_STRIPE_SWEEP="1",
                   ACX_RV_THRESHOLD="0", ACX_STRIPES=str(s))
        cmd = [os.path.join(REPO, "build", "acxrun"), "-np", "2",
               "-timeout", "300", "-transport", "socket",
               os.path.join(REPO, "build", "bench_pingpong"), "8"]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=400, env=env)
        got = re.findall(r"BENCH_STRIPE stripes=(\d+) msg_bytes=(\d+) "
                         r"bw_gbps=([\d.]+)", r.stdout)
        if not got:
            raise RuntimeError(
                f"stripe sweep stripes={s} produced no rows: "
                f"{r.stdout[-300:]} {r.stderr[-300:]}")
        for st, mb, g in got:
            rows.append({"stripes": int(st), "msg_bytes": int(mb),
                         "bw_gbps": float(g)})
    return rows


def _record_wire_rows(rows, part_bw):
    """Fold the striped-wire rows into the newest MULTICHIP_r*.json so
    the multichip artifact carries the wire-plane numbers alongside the
    mesh result. The artifact belongs to the driver: merge, never fail."""
    import glob
    files = sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
    if not files:
        return
    try:
        with open(files[-1]) as f:
            d = json.load(f)
        d["wire"] = {"partitioned_bw_gbps": part_bw, "stripe_sweep": rows}
        with open(files[-1], "w") as f:
            json.dump(d, f)
            f.write("\n")
    except Exception:  # noqa: BLE001
        pass


def disagg_fleet_rows(n_reqs: int = 6, timeout: int = 300):
    """TTFT A/B of the role-split disagg fleet (models/disagg.py): the
    same 3-rank (1 prefill + 2 decode) workload with per-layer Pready
    overlap ON vs OFF (ship only after the full prompt pass). Decode
    ranks print DISAGG_ROW lines with their observed TTFT p50 and the
    exposed-ship p50 (FIN-carried: publish time left after the head) —
    per-layer Pready hides the ship under compute, so its exposed time
    is ~0 while the baseline pays the full serialized pack+publish on
    the TTFT path. ACX_DISAGG_BIG makes each handoff ~1 MiB so that
    exposure is milliseconds, not clock noise."""
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True)
    rows = {}
    for key, overlap in (("overlap", "1"), ("noverlap", "0")):
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["ACX_ROLE"] = "prefill,decode,decode"
        env["ACX_DISAGG_OVERLAP"] = overlap
        env["ACX_DISAGG_REQS"] = str(n_reqs)
        env["ACX_DISAGG_BIG"] = "1"
        cmd = [os.path.join(REPO, "build", "acxrun"), "-np", "3",
               "-timeout", str(timeout), "-transport", "socket",
               sys.executable, os.path.join(REPO, "tests",
                                            "disagg_worker.py")]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout + 60, env=env)
        decoded = [json.loads(ln.split("DISAGG_ROW ", 1)[1])
                   for ln in r.stdout.splitlines()
                   if ln.startswith("DISAGG_ROW ")]
        decoded = [d for d in decoded if d.get("role") == "decode"]
        if r.returncode != 0 or not decoded:
            raise RuntimeError(
                f"disagg fleet ({key}) rc={r.returncode}: "
                f"{r.stdout[-300:]} {r.stderr[-300:]}")
        ttfts = sorted(d["ttft_p50_s"] for d in decoded)
        exposes = sorted(d["expose_p50_s"] for d in decoded)
        rows[f"disagg_fleet_ttft_{key}_p50_s"] = round(
            ttfts[len(ttfts) // 2], 4)
        rows[f"disagg_fleet_ship_exposed_{key}_p50_ms"] = round(
            exposes[len(exposes) // 2] * 1e3, 3)
    rows["disagg_fleet_overlap_ttft_speedup"] = round(
        rows["disagg_fleet_ttft_noverlap_p50_s"]
        / max(rows["disagg_fleet_ttft_overlap_p50_s"], 1e-9), 3)
    rows["disagg_fleet_ship_hidden_ms"] = round(
        rows["disagg_fleet_ship_exposed_noverlap_p50_ms"]
        - rows["disagg_fleet_ship_exposed_overlap_p50_ms"], 3)
    return rows


def _record_disagg_rows(rows):
    """Fold the disagg rows into the newest MULTICHIP_r*.json (same
    merge-never-fail contract as _record_wire_rows)."""
    import glob
    files = sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
    if not files:
        return
    try:
        with open(files[-1]) as f:
            d = json.load(f)
        d["disagg"] = rows
        with open(files[-1], "w") as f:
            json.dump(d, f)
            f.write("\n")
    except Exception:  # noqa: BLE001
        pass


def journey_phase_rows(n_reqs: int = 6, timeout: int = 300):
    """Per-phase serving-time budget from the request-journey plane
    (docs/DESIGN.md §20): run the 3-rank journaled fleet
    (tests/request_worker.py — mono warmup first, so the phases measure
    serving rather than XLA compiles), reconstruct the journeys offline
    with tools/acx_request.py, and bank the fleet queue/prefill/ship/
    decode p50/p99 so future PRs can regress against phase budgets, not
    just the aggregate TTFT the disagg rows already carry."""
    import glob
    import tempfile
    subprocess.run(["make", "-C", REPO, "lib", "tools"], check=True,
                   capture_output=True)
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["ACX_ROLE"] = "prefill,decode,decode"
        env["ACX_DISAGG_REQS"] = str(n_reqs)
        env["ACX_REQLOG"] = os.path.join(td, "run")
        env["ACX_TRACE"] = os.path.join(td, "run")
        env["ACX_TRACE_CAP"] = "2000000"
        cmd = [os.path.join(REPO, "build", "acxrun"), "-np", "3",
               "-timeout", str(timeout), "-transport", "socket",
               sys.executable, os.path.join(REPO, "tests",
                                            "request_worker.py")]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout + 60, env=env)
        if r.returncode != 0:
            raise RuntimeError(
                f"journey fleet rc={r.returncode}: "
                f"{r.stdout[-300:]} {r.stderr[-300:]}")
        inputs = (sorted(glob.glob(os.path.join(
                      td, "run.rank*.reqlog.jsonl")))
                  + sorted(glob.glob(os.path.join(
                      td, "run.rank*.trace.json"))))
        rep_path = os.path.join(td, "journey.json")
        rq = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "acx_request.py"),
             "--json", rep_path] + inputs,
            capture_output=True, text=True, timeout=120)
        if rq.returncode != 0:
            raise RuntimeError(
                f"acx_request rc={rq.returncode}: {rq.stderr[-300:]}")
        with open(rep_path) as f:
            rep = json.load(f)
    rows = {}
    for ph in ("queue", "prefill", "ship", "decode"):
        st = rep["phase_breakdown"][ph]
        rows[f"journey_{ph}_p50_s"] = round(st["p50_s"] or 0.0, 4)
        rows[f"journey_{ph}_p99_s"] = round(st["p99_s"] or 0.0, 4)
    rows["journey_reconstructed_rate"] = rep["reconstructed_rate"]
    rows["journey_dominant_phase"] = rep["dominant_phase"]
    return rows


def _record_journey_rows(rows):
    """Fold the journey phase-budget rows into the newest BENCH_r*.json
    (same merge-never-fail contract as _record_paged_rows)."""
    import glob
    files = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not files:
        return
    try:
        with open(files[-1]) as f:
            d = json.load(f)
        d["journey"] = rows
        with open(files[-1], "w") as f:
            json.dump(d, f)
            f.write("\n")
    except Exception:  # noqa: BLE001
        pass


def _code_rev():
    """Fingerprint of the MEASURED code: tree hashes of the source
    paths plus any uncommitted diff to them. Deliberately excludes the
    bench artifacts, so the banker's own artifact commits don't shift
    it — but ANY code change (committed or not) does, which is what
    lets _bank_reuse refuse rows measured on code that no longer
    exists (r05 review: the decode group's 0.73x int8-KV row predated
    the scale-on-scores fix and would otherwise have been reused as
    evidence for it)."""
    paths = ["mpi_acx_tpu", "src", "include", "bench.py"]
    try:
        import hashlib
        h = subprocess.run(
            ["git", "-C", REPO, "rev-parse"] +
            [f"HEAD:{p}" for p in paths],
            capture_output=True, text=True, timeout=30).stdout
        d = subprocess.run(
            ["git", "-C", REPO, "diff", "HEAD", "--"] + paths,
            capture_output=True, text=True, timeout=30).stdout
        # Untracked sources are invisible to both rev-parse and diff —
        # a brand-new module measured before its first commit would
        # otherwise share a fingerprint with the tree that lacks it.
        u = subprocess.run(
            ["git", "-C", REPO, "ls-files", "--others",
             "--exclude-standard", "--"] + paths,
            capture_output=True, text=True, timeout=30).stdout
        parts = [h.encode(), d.encode()]
        for name in sorted(u.split()):
            try:
                with open(os.path.join(REPO, name), "rb") as f:
                    parts.append(name.encode() + b"\0" + f.read())
            except OSError:  # racing delete: name alone still shifts it
                parts.append(name.encode() + b"\0?")
        return hashlib.sha1(b"".join(parts)).hexdigest()[:12]
    except Exception:  # noqa: BLE001 — no git: disable reuse, not bench
        return "unknown"


def _bench_cfg():
    """The bench model geometry: GPT-2 125M, or a seconds-scale toy
    under ACX_BENCH_TINY=1 — the smoke mode that lets every TPU child
    run end-to-end on CPU BEFORE chip time risks
    crashing on untested code (tiny numbers are meaningless and must
    never be banked: _bank refuses when the env is set)."""
    from mpi_acx_tpu.models import transformer as tfm
    if os.environ.get("ACX_BENCH_TINY") == "1":
        return tfm.tiny_config(vocab=128, d_model=32, n_heads=2,
                               n_layers=2, d_ff=64, max_seq=4096)
    return tfm.gpt2_small()


# A `*_speedup` row is a RATIO of two measured rows; it is only evidence
# when baseline and variant came from the same code. This maps each
# speedup row to its (baseline, variant) component rows so the artifact
# writer can refuse ratios whose parts were measured at different revs
# (or predate rev stamping — both sides silently defaulting to
# "unrecorded" used to count as a match).
_SPEEDUP_COMPONENTS = {
    "flash_speedup_s4096": ("dense_ms", "flash_ms"),
    "decode_int8w_speedup": ("decode_tokens_per_s",
                             "decode_int8w_tokens_per_s"),
    "decode_flash_speedup": ("decode_tokens_per_s",
                             "decode_flash_tokens_per_s"),
    "decode_longctx_int8kv_speedup": ("decode_longctx_tokens_per_s",
                                      "decode_longctx_int8kv_tokens_per_s"),
    "decode_longctx_flash_speedup": (
        "decode_longctx_dense_tokens_per_s",
        "decode_longctx_flash_tokens_per_s"),
    "decode_longctx_int8kv_flash_speedup": (
        "decode_longctx_int8kv_dense_tokens_per_s",
        "decode_longctx_int8kv_flash_tokens_per_s"),
    "spec_speedup": ("spec_plain_ms", "spec_ms"),
    "serve_speedup": ("serve_static_tokens_per_s",
                      "serve_cont_tokens_per_s"),
}


def _load_bank() -> dict:
    """BENCH_BANK.json as a dict; {} when absent or corrupt. The one
    read path for the bank (banking, reuse, outage fallback)."""
    try:
        with open(os.path.join(REPO, "BENCH_BANK.json")) as f:
            bank = json.load(f)
        return bank if isinstance(bank, dict) else {}
    except Exception:  # noqa: BLE001 — first run or corrupt file
        return {}


def _bank(rows: dict, group: str | None = None):
    """Merge measured rows into BENCH_BANK.json IMMEDIATELY (checked-in,
    append-only evidence: a few minutes of chip time must survive a
    later crash/outage — round-4 verdict item #1)."""
    if os.environ.get("ACX_BENCH_TINY") == "1":
        return      # smoke geometry: numbers are meaningless
    path = os.path.join(REPO, "BENCH_BANK.json")
    bank = _load_bank()
    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    rev = _code_rev()
    for k, v in rows.items():
        if k != "device":
            bank[k] = {"value": v, "ts": ts, "rev": rev,
                       "device": rows.get("device", "?")}
            if group is not None:
                bank[k]["group"] = group
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(bank, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _bank_reuse(group: str):
    """Return {metric: value} for GROUP from BENCH_BANK.json if every
    row is TPU-measured within ACX_BANK_REUSE_H hours, else None.

    Off by default (driver runs measure fresh); the banker loop sets
    the env so a RETRY pass skips straight to the groups the last
    window didn't reach instead of re-burning chip minutes
    on already-banked ones (r05: window died between decode and
    train)."""
    if os.environ.get("ACX_BENCH_TINY") == "1":
        return None   # the smoke exists to RUN the children, not skip
    hours = float(os.environ.get("ACX_BANK_REUSE_H", "0") or 0)
    if hours <= 0:
        return None
    bank = _load_bank()
    rows = {k: v for k, v in bank.items()
            if isinstance(v, dict) and v.get("group") == group}
    if not rows:
        return None
    import calendar
    cutoff = time.time() - hours * 3600
    rev = _code_rev()
    for v in rows.values():
        if v.get("device") != "tpu":
            return None
        # Only rows measured on EXACTLY this code may stand in for a
        # fresh measurement ("unknown" never matches itself safely).
        if rev == "unknown" or v.get("rev") != rev:
            return None
        try:
            # Bank timestamps are UTC ("...Z"); timegm parses as UTC.
            t = calendar.timegm(time.strptime(v.get("ts", ""),
                                              "%Y-%m-%dT%H:%M:%SZ"))
        except ValueError:
            return None     # malformed row: fall through to measuring
        if t < cutoff:
            return None
    return {k: v["value"] for k, v in rows.items()}


def _run_tpu_child(mode: str, attempts: int = 3, timeout: int = 420,
                   child_flag: str = "tpu-child", env: dict | None = None):
    """Run `bench.py --<child_flag>-<mode>` in a fresh process, retrying
    on failure/hang. Returns (parsed dict | None, last_error | None)."""
    if attempts < 1:
        return None, "skipped (previous TPU child exhausted its retries)"
    last = None
    for i in range(attempts):
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 f"--{child_flag}-{mode}"],
                env=env, capture_output=True, text=True, timeout=timeout)
            for line in r.stdout.splitlines():
                if line.startswith("{"):
                    return json.loads(line), None
            last = (f"rc={r.returncode} no JSON in output; "
                    f"stderr tail: {r.stderr[-300:]}")
        except subprocess.TimeoutExpired:
            last = f"timeout after {timeout}s (attempt {i + 1})"
        except Exception as e:  # noqa: BLE001 — report, don't crash bench
            last = f"{type(e).__name__}: {e}"
        if i + 1 < attempts:
            time.sleep(10 * (i + 1))   # chip unavailable: may be transient
    return None, last


def tpu_child_fwd():
    """Child process: flagship GPT-2 125M forward throughput (tokens/s).

    The repetition loop runs ON DEVICE (lax.scan of REPS forwards with an
    iteration-dependent input so XLA can't hoist the body) and the result
    is fetched as a scalar. Host-side loops measure the host<->device
    round-trip, not the TPU — this methodology reports device
    throughput."""
    import jax
    import jax.numpy as jnp
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(REPO, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, (params, tokens) = mod.entry()
    # The flagship entry has no tiny variant; the CPU smoke just cuts
    # the rep count so the 125M forwards finish in seconds.
    reps = 3 if os.environ.get("ACX_BENCH_TINY") == "1" else 50
    vocab = int(tokens.max()) + 1

    def measure(tokens, reps_n):
        @jax.jit
        def loop_n(params, tokens):
            def body(carry, i):
                acc, t = carry
                ti = (t + i) % vocab
                return (acc + fn(params, ti).sum(), t), None
            (acc, _), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), tokens),
                jnp.arange(reps_n))
            return acc

        float(loop_n(params, tokens))              # compile + warm
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(loop_n(params, tokens))          # device_get = sync
            best = min(best, (time.perf_counter() - t0) / reps_n)
        return tokens.size / best

    toks = measure(tokens, reps)
    # Forward-pass MFU: ~2 FLOPs per parameter per token on the matmuls.
    mfu = toks * 2 * GPT2_SMALL_PARAMS / V5E_BF16_PEAK_FLOPS
    # Saturating shape (B=16, S=512): the entry() row (B=2, S=256) is a
    # latency shape; this one shows the chip's throughput ceiling.
    big = jax.random.randint(jax.random.key(2), (16, 512), 0, vocab)
    toks_big = measure(big, 10)
    print(json.dumps({
        "gpt2_fwd_tokens_per_s": round(toks, 1),
        "gpt2_fwd_mfu": round(mfu, 4),
        "gpt2_fwd_b16s512_tokens_per_s": round(toks_big, 1),
        "gpt2_fwd_b16s512_mfu": round(
            toks_big * 2 * GPT2_SMALL_PARAMS / V5E_BF16_PEAK_FLOPS, 4),
        "device": str(jax.devices()[0].platform),
    }))


def tpu_child_probe():
    """Child process: cheap chip-health probe. Gates the expensive
    children — when the chip is unavailable this fails in ONE short timeout
    instead of burning 3x420 s per metric group (rounds 2-4 lost whole
    windows to exactly that)."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((128, 128), jnp.bfloat16)
    y = float(jax.jit(lambda a: (a @ a).sum())(x))   # real compile+run
    print(json.dumps({"tpu_probe_ok": y > 0,
                      "device": str(jax.devices()[0].platform)}))


def _timeit(f, *a, reps=1):
    """Best-of-3 wall time of one f(*a) call (fully synced)."""
    import jax
    jax.block_until_ready(f(*a))               # compile + warm
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(*a)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def tpu_child_flash():
    """Child process: flash-attention speedup vs dense at S=4096 (GPT-2
    head geometry), device-side rep loops."""
    import jax
    import jax.numpy as jnp
    from mpi_acx_tpu.ops.attention import attention_reference, flash_attention

    def timeit_device(fn, q, k, v, reps=20):
        """Device-side rep loop (lax.scan with an iteration-dependent
        input so XLA can't hoist the body): host-side per-call timing
        reports dispatch latency, not kernel time — sub-ms kernels need
        the loop ON the device."""
        @jax.jit
        def loop(q, k, v):
            def body(acc, i):
                qq = q + (i % 2).astype(q.dtype) * 1e-3
                return acc + fn(qq, k, v).astype(jnp.float32).sum(), None
            acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                  jnp.arange(reps))
            return acc
        float(loop(q, k, v))                       # compile + warm
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(loop(q, k, v))                   # scalar fetch = sync
            best = min(best, (time.perf_counter() - t0) / reps)
        return best

    B, S, H, D = 1, 4096, 12, 64
    if os.environ.get("ACX_BENCH_TINY") == "1":
        S, H = 512, 2                  # CPU smoke shape (_bench_cfg)
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
               for kk in ks)
    t_dense = timeit_device(attention_reference, q, k, v)
    t_flash = timeit_device(flash_attention, q, k, v)
    print(json.dumps({
        "flash_speedup_s4096": round(t_dense / t_flash, 2),
        "flash_ms": round(t_flash * 1e3, 3),
        "dense_ms": round(t_dense * 1e3, 3),
        "device": str(jax.devices()[0].platform),
    }))


def tpu_child_decode():
    """Child process: KV-cache greedy decode tok/s (B=8, bf16 125M) plus
    the HBM roofline bounding it. Decode is bandwidth-bound (see
    parallel/tp_inference.py:3-8): every step re-streams the full weight
    set (amortized over the batch) plus each row's padded KV cache, so
    the per-step floor is bytes_moved / HBM_BW and roofline tok/s =
    B / floor (round-4 verdict item #7)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from mpi_acx_tpu.models import transformer as tfm

    cfg = _bench_cfg()
    params = tfm.cast_params(tfm.init_params(jax.random.key(0), cfg),
                             jnp.bfloat16)
    B, S_p, n_new, max_len = 8, 32, 64, 256
    lc_max, lc_new = 2048, 32
    if os.environ.get("ACX_BENCH_TINY") == "1":
        # The flash A/B doubles the longctx compiles and the forced-flash
        # rows run the kernel INTERPRETED on CPU — shrink the smoke so
        # make decode-check stays seconds-scale.
        n_new, lc_max, lc_new = 8, 512, 4
    prompt = jax.random.randint(jax.random.key(1), (B, S_p), 0, cfg.vocab)
    gen = jax.jit(lambda p, t: tfm.generate(p, cfg, t, n_new,
                                            max_len=max_len))
    decode_toks = B * n_new / _timeit(gen, params, prompt)

    # Dense-vs-flash A/B at the short operating point. The auto policy
    # picks dense at max_len=256 (below the block-skip crossover), so
    # decode_tokens_per_s above IS the dense baseline; this row forces
    # the ops/flash_decode.py kernel on the identical workload.
    fcfg = dataclasses.replace(cfg, decode_flash=True)
    fgen = jax.jit(lambda p, t: tfm.generate(p, fcfg, t, n_new,
                                             max_len=max_len))
    decode_toks_f = B * n_new / _timeit(fgen, params, prompt)

    # Roofline: v5e HBM ~819 GB/s (public spec). Static shapes mean the
    # kernels stream the PADDED (max_len) cache each step.
    HBM_BW = 819e9
    from mpi_acx_tpu.ops.wquant import (GPT2_WEIGHTS,
                                        quantize_weights_int8,
                                        weight_bytes)
    wbytes = weight_bytes(params)
    kvbytes = 2 * cfg.n_layers * max_len * cfg.d_model * 2 * B
    roofline = B * HBM_BW / (wbytes + kvbytes)

    # The roofline optimization attempt (round-4 verdict item #7):
    # int8 weight-only quantization halves the dominant per-step
    # stream (weights ~40x the KV bytes at this shape), so its
    # roofline is ~2x — the row records how much of that the kernel
    # actually realizes on chip.
    qparams = quantize_weights_int8(params, GPT2_WEIGHTS)
    decode_toks_q = B * n_new / _timeit(gen, qparams, prompt)
    qbytes = weight_bytes(qparams)
    roofline_q = B * HBM_BW / (qbytes + kvbytes)

    # Long-context operating point (max_len=2048): the KV stream is
    # now ~2.4x the int8 weight stream — the regime ops/kvquant.py
    # targets. A/B bf16 vs int8 cache AND dense vs flash on the same
    # workload: the dcfg/fcfg pair forces the decode backend either way
    # (cfg's None would auto-pick flash here, max_len >= 1024).
    dcfg = dataclasses.replace(cfg, decode_flash=False)
    lprompt = jax.random.randint(jax.random.key(3), (B, 32), 0,
                                 cfg.vocab)

    def ltoks(c, int8):
        lgen = jax.jit(lambda p, t: tfm.generate(p, c, t, lc_new,
                                                 max_len=lc_max,
                                                 kv_int8=int8))
        return B * lc_new / _timeit(lgen, qparams, lprompt)

    lc_toks, lc_toks8 = ltoks(cfg, False), ltoks(cfg, True)
    lc_dense, lc_dense8 = ltoks(dcfg, False), ltoks(dcfg, True)
    lc_flash, lc_flash8 = ltoks(fcfg, False), ltoks(fcfg, True)
    lc_kv = 2 * cfg.n_layers * lc_max * cfg.d_model * 2 * B
    lc_kv8 = lc_kv // 2 + lc_kv // (2 * cfg.head_dim) * 4  # codes+scales
    # Length-aware roofline: the flash kernel reads O(live length), not
    # O(max_len) — over this run the mean live length is S_p + lc_new/2
    # cache rows, so the bandwidth floor shrinks by live/max. The dense
    # rooflines above keep charging the full padded cache.
    live_frac = (32 + lc_new / 2) / lc_max
    lc_kv_live = lc_kv * live_frac
    lc_kv8_live = lc_kv8 * live_frac
    print(json.dumps({
        "decode_tokens_per_s": round(decode_toks, 1),
        "decode_flash_tokens_per_s": round(decode_toks_f, 1),
        "decode_flash_speedup": round(decode_toks_f / decode_toks, 2),
        "decode_roofline_tokens_per_s": round(roofline, 1),
        "decode_roofline_frac": round(decode_toks / roofline, 3),
        "decode_weight_mb": round(wbytes / 1e6, 1),
        "decode_kv_mb": round(kvbytes / 1e6, 1),
        "decode_int8w_tokens_per_s": round(decode_toks_q, 1),
        "decode_int8w_speedup": round(decode_toks_q / decode_toks, 2),
        "decode_int8w_roofline_frac": round(decode_toks_q / roofline_q,
                                            3),
        "decode_int8w_weight_mb": round(qbytes / 1e6, 1),
        "decode_longctx_tokens_per_s": round(lc_toks, 1),
        "decode_longctx_int8kv_tokens_per_s": round(lc_toks8, 1),
        "decode_longctx_int8kv_speedup": round(lc_toks8 / lc_toks, 2),
        "decode_longctx_dense_tokens_per_s": round(lc_dense, 1),
        "decode_longctx_flash_tokens_per_s": round(lc_flash, 1),
        "decode_longctx_flash_speedup": round(lc_flash / lc_dense, 2),
        "decode_longctx_int8kv_dense_tokens_per_s": round(lc_dense8, 1),
        "decode_longctx_int8kv_flash_tokens_per_s": round(lc_flash8, 1),
        "decode_longctx_int8kv_flash_speedup": round(
            lc_flash8 / lc_dense8, 2),
        "decode_longctx_kv_mb": round(lc_kv / 1e6, 1),
        "decode_longctx_int8kv_mb": round(lc_kv8 / 1e6, 1),
        "decode_longctx_roofline_tokens_per_s": round(
            B * HBM_BW / (qbytes + lc_kv), 1),
        "decode_longctx_int8kv_roofline_tokens_per_s": round(
            B * HBM_BW / (qbytes + lc_kv8), 1),
        "decode_longctx_live_roofline_tokens_per_s": round(
            B * HBM_BW / (qbytes + lc_kv_live), 1),
        "decode_longctx_int8kv_live_roofline_tokens_per_s": round(
            B * HBM_BW / (qbytes + lc_kv8_live), 1),
        "decode_longctx_live_roofline_frac": round(
            lc_flash / (B * HBM_BW / (qbytes + lc_kv_live)), 3),
        "device": str(jax.devices()[0].platform),
    }))


def _train_setup():
    """Shared geometry for the two train children (split r05: the
    combined child's 4 full train-step compiles blew past a 480 s
    timeout — train compiles 2, trainseg 3 with its own 900 s
    budget; trainseg re-times step_full on purpose so the fwd/bwd/opt
    segments come from the SAME run — the chip's ±40% day swing makes
    cross-child deltas meaningless)."""
    import jax
    import jax.numpy as jnp
    import optax
    from mpi_acx_tpu.models import transformer as tfm

    cfg = _bench_cfg()
    params_f32 = tfm.init_params(jax.random.key(0), cfg)
    opt = optax.adamw(1e-4)
    ostate = opt.init(params_f32)
    tok = jax.random.randint(jax.random.key(2), (8, 512), 0, cfg.vocab)
    tgt = jnp.roll(tok, -1, axis=-1)
    treps = 5

    def scan_loop(body):
        @jax.jit
        def loop(p, s, tok, tgt):
            (_, _), losses = jax.lax.scan(
                lambda c, _: body(c, tok, tgt), (p, s), None,
                length=treps)
            return losses[-1]
        return loop

    def step_full(carry, tok, tgt, chunk=None):
        p, s = carry
        loss, g = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, cfg, tok, tgt, xent_chunk=chunk))(p)
        upd, s = opt.update(g, s, p)
        return (optax.apply_updates(p, upd), s), loss

    # Segment isolates: fwd-only and fwd+bwd steps whose carries stay
    # loss-dependent so the scan iterations remain sequential.
    def step_fwd(carry, tok, tgt):
        p, s = carry
        loss = tfm.loss_fn(p, cfg, tok, tgt)
        p = jax.tree.map(lambda x: x + (0 * loss).astype(x.dtype), p)
        return (p, s), loss

    def step_grad(carry, tok, tgt):
        p, s = carry
        loss, g = jax.value_and_grad(tfm.loss_fn)(p, cfg, tok, tgt)
        p = jax.tree.map(lambda a, b: a - 0.0 * b, p, g)
        return (p, s), loss

    from types import SimpleNamespace
    return SimpleNamespace(
        jax=jax, tok=tok, tgt=tgt, treps=treps, params=params_f32,
        ostate=ostate, scan_loop=scan_loop, step_full=step_full,
        step_fwd=step_fwd, step_grad=step_grad)


def tpu_child_train():
    """Child process: single-chip AdamW train step (B=8, S=512), plain vs
    chunked-vocab CE, plus train MFU at 6*N FLOPs per token (round-4
    verdict item #6). Rep loops are lax.scan ON DEVICE with
    params/opt-state as the carry so every iteration is a dependent
    update XLA can't elide; host per-call timing would fold the
    dispatch round trip in."""
    b = _train_setup()
    t_full = _timeit(b.scan_loop(b.step_full), b.params, b.ostate,
                     b.tok, b.tgt) / b.treps
    t_chunk = _timeit(b.scan_loop(
        lambda c, x, y: b.step_full(c, x, y, chunk=8192)),
        b.params, b.ostate, b.tok, b.tgt) / b.treps

    toks = b.tok.size / t_full
    # Train MFU: ~6 FLOPs per param per token (fwd 2 + bwd 4).
    mfu = toks * 6 * GPT2_SMALL_PARAMS / V5E_BF16_PEAK_FLOPS
    print(json.dumps({
        "train_step_tokens_per_s": round(toks, 1),
        "train_step_xentchunk_tokens_per_s": round(b.tok.size / t_chunk, 1),
        "train_step_mfu": round(mfu, 4),
        "train_seg_total_ms": round(t_full * 1e3, 2),
        "device": str(b.jax.devices()[0].platform),
    }))


def tpu_child_trainseg():
    """Child process: the fwd-only / fwd+bwd segment isolates that
    attribute the train step's time across fwd / bwd / optimizer
    (verdict item #6). Split from tpu_child_train so neither child
    exceeds ~2 train-step compiles per run."""
    b = _train_setup()
    t_full = _timeit(b.scan_loop(b.step_full), b.params, b.ostate,
                     b.tok, b.tgt) / b.treps
    t_fwd = _timeit(b.scan_loop(b.step_fwd), b.params, b.ostate,
                    b.tok, b.tgt) / b.treps
    t_grad = _timeit(b.scan_loop(b.step_grad), b.params, b.ostate,
                     b.tok, b.tgt) / b.treps
    print(json.dumps({
        "train_seg_fwd_ms": round(t_fwd * 1e3, 2),
        "train_seg_bwd_ms": round((t_grad - t_fwd) * 1e3, 2),
        "train_seg_opt_ms": round((t_full - t_grad) * 1e3, 2),
        # Distinct key from the train child's train_seg_total_ms: the
        # two children bank under different groups and a shared key
        # would flip-flop its group tag (breaking _bank_reuse).
        "trainseg_total_ms": round(t_full * 1e3, 2),
        "device": str(b.jax.devices()[0].platform),
    }))


def _spec_setup():
    """Shared geometry for the two speculative children (split r05: one
    child was 5 compiles — two 40-step trainings, plain decode,
    and the speculative while_loop at B=1 AND B=8 — far past its 600 s
    timeout). Trained params are cached in build/ (gitignored scratch)
    so the second child skips the training compiles when it runs in the
    same window; a cold cache just retrains."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from mpi_acx_tpu.models import transformer as tfm

    n_new, k = 128, 4
    cfg = _bench_cfg()
    dcfg = dataclasses.replace(cfg, n_layers=2)
    tok = jax.random.randint(jax.random.key(1), (8, 64), 0, cfg.vocab)
    cache = os.path.join(REPO, "build", "spec_params.npy")

    def train(c, key, steps=40):
        p = tfm.init_params(key, c)
        opt = optax.adam(3e-3)
        st = opt.init(p)

        @jax.jit
        def step(p, st):
            loss, g = jax.value_and_grad(tfm.loss_fn)(p, c, tok, tok)
            up, st = opt.update(g, st)
            return optax.apply_updates(p, up), st, loss
        for _ in range(steps):
            p, st, _ = step(p, st)
        return tfm.cast_params(p, jnp.bfloat16)

    params = dparams = None
    rev = _code_rev()
    try:
        blob = np.load(cache, allow_pickle=True).item()
        # The cache is only a stand-in for training on the CURRENT
        # code — a rev/geometry mismatch is a cold cache, not an error
        # (same staleness rule as _bank_reuse).
        if blob.get("rev") == rev != "unknown" and blob.get("cfg") == cfg:
            to_dev = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
            params = to_dev(blob["params"])
            dparams = to_dev(blob["dparams"])
    except Exception:  # noqa: BLE001 — cold cache: train fresh
        pass
    if params is None:
        params = train(cfg, jax.random.key(0))
        dparams = train(dcfg, jax.random.key(5))
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        to_host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
        # tmp + os.replace: the child runs under a hard timeout kill
        # and a truncated cache would cost the next child its warm
        # start (np.save appends .npy, hence the suffixed tmp name).
        tmp = cache + ".tmp.npy"
        np.save(tmp, {"params": to_host(params),
                      "dparams": to_host(dparams),
                      "rev": rev, "cfg": cfg},
                allow_pickle=True)
        os.replace(tmp, cache)
    from types import SimpleNamespace
    return SimpleNamespace(jax=jax, jnp=jnp, tfm=tfm, cfg=cfg, dcfg=dcfg,
                           tok=tok, n_new=n_new, k=k, params=params,
                           dparams=dparams)


def tpu_child_spec():
    """Child process: on-chip speculative-decoding wall-clock at B=1.
    Trains the GPT-2 125M target and a 2-layer draft on a repetition
    task (so the draft's proposals usually match), then times plain
    greedy decode vs the speculative loop at the same (B=1, n_new)
    workload. Informational row — never regression-gated (acceptance
    depends on the task)."""
    from mpi_acx_tpu.models.speculative import speculative_generate
    s = _spec_setup()
    jax, n_new, k = s.jax, s.n_new, s.k
    prompt = s.tok[:1, :32]

    gen = jax.jit(lambda p, t: s.tfm.generate(
        p, s.cfg, t, n_new, max_len=32 + n_new + k))
    jax.block_until_ready(gen(s.params, prompt))
    t0 = time.perf_counter()
    jax.block_until_ready(gen(s.params, prompt))
    t_plain = time.perf_counter() - t0

    out, stats = speculative_generate(s.dparams, s.dcfg, s.params, s.cfg,
                                      prompt, n_new, k=k)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out, stats = speculative_generate(s.dparams, s.dcfg, s.params, s.cfg,
                                      prompt, n_new, k=k)
    jax.block_until_ready(out)
    t_spec = time.perf_counter() - t0
    rounds = int(stats["rounds"])

    print(json.dumps({
        "spec_speedup": round(t_plain / t_spec, 2),
        "spec_plain_ms": round(t_plain * 1e3, 1),
        "spec_ms": round(t_spec * 1e3, 1),
        "spec_rounds": rounds,
        "spec_target_pass_reduction": round(n_new / rounds, 2),
        "spec_accepted": int(stats["drafted_accepted"]),
        "device": str(jax.devices()[0].platform),
    }))


def tpu_child_specb():
    """Child process: batched (B=8) speculation — the vmap-lifted loop,
    per-row rounds, wall-clock bounded by the slowest row. Separate
    from tpu_child_spec because the B=8 while_loop is its own heavy
    compile; reuses the cached trained params when warm."""
    from mpi_acx_tpu.models.speculative import speculative_generate
    s = _spec_setup()
    jax, jnp, n_new, k = s.jax, s.jnp, s.n_new, s.k
    B = 8
    prompts = jnp.tile(s.tok[:1, :32], (B, 1)).at[:, -1].set(
        jnp.arange(B) % s.cfg.vocab)
    outb, statsb = speculative_generate(s.dparams, s.dcfg, s.params,
                                        s.cfg, prompts, n_new, k=k)
    jax.block_until_ready(outb)
    t0 = time.perf_counter()
    outb, statsb = speculative_generate(s.dparams, s.dcfg, s.params,
                                        s.cfg, prompts, n_new, k=k)
    jax.block_until_ready(outb)
    t_spec_b = time.perf_counter() - t0
    rounds_b = [int(r) for r in statsb["rounds"]]

    print(json.dumps({
        "spec_batched_ms": round(t_spec_b * 1e3, 1),
        "spec_batched_tokens_per_s": round(B * n_new / t_spec_b, 1),
        "spec_batched_rounds_max": max(rounds_b),
        "spec_batched_target_pass_reduction": round(
            n_new / max(rounds_b), 2),
        "device": str(jax.devices()[0].platform),
    }))


def tpu_child_serve():
    """Child process: continuous batching (models/serving.py) vs static
    batches on a mixed-output-length workload — the scheduling win the
    serving tier exists for. 16 requests (prompt 32, n_new cycling
    16/96/32/128) through 8 slots with chunk=32, against the same
    requests run as two static B=8 generate() batches that each must
    decode to their LONGEST member. Throughput counts only REQUESTED
    tokens, so the static row pays for its padding honestly.
    Informational — never regression-gated (the ratio depends on the
    length mix)."""
    import jax
    import jax.numpy as jnp
    from mpi_acx_tpu.models import serving
    from mpi_acx_tpu.models import transformer as tfm

    cfg = _bench_cfg()
    params = tfm.cast_params(tfm.init_params(jax.random.key(0), cfg),
                             jnp.bfloat16)
    S, chunk, n_slots = 32, 32, 8
    lens = [16, 96, 32, 128] * 4                       # 16 requests
    max_len = S + max(lens) + chunk
    keys = jax.random.split(jax.random.key(1), len(lens))
    prompts = [jax.random.randint(k, (S,), 0, cfg.vocab) for k in keys]

    # Warm both compile caches outside the timed region — the serve
    # warmup must run through the SAME server_fns the timed call uses
    # (a bare serve_greedy call builds fresh jit closures every time).
    fns = serving.make_server_fns(params, cfg, tfm, chunk=chunk)
    serving.serve_greedy(params, cfg, prompts[:2], [chunk, chunk],
                         n_slots=n_slots, max_len=max_len, family=tfm,
                         chunk=chunk, server_fns=fns)
    gen = jax.jit(lambda p, t: tfm.generate(p, cfg, t, max(lens),
                                            max_len=max_len))
    batch = jnp.stack(prompts[:n_slots])
    jax.block_until_ready(gen(params, batch))

    t0 = time.perf_counter()
    serving.serve_greedy(params, cfg, prompts, lens, n_slots=n_slots,
                         max_len=max_len, family=tfm, chunk=chunk,
                         server_fns=fns)
    t_cont = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(0, len(prompts), n_slots):
        jax.block_until_ready(
            gen(params, jnp.stack(prompts[i:i + n_slots])))
    t_static = time.perf_counter() - t0

    requested = sum(lens)
    print(json.dumps({
        "serve_cont_tokens_per_s": round(requested / t_cont, 1),
        "serve_static_tokens_per_s": round(requested / t_static, 1),
        "serve_speedup": round(t_static / t_cont, 2),
        "serve_requests": len(lens),
        "device": str(jax.devices()[0].platform),
    }))


def cpu_child_quant():
    """Child process (forced CPU, 8 virtual devices): wire-byte ratio of
    the int8-quantized ring all-reduce vs an f32 ring with the identical
    schedule, counted from collective-permute payload types in the
    compiled HLO. Deterministic — no chip, no weather — so the driver's
    artifact carries a perf-design metric even when the chip is
    unavailable."""
    import re as _re
    import jax
    # This child is CPU by definition: pin unconditionally so a direct
    # `bench.py --cpu-child-quant` invocation cannot block in the pinned
    # accelerator plugin's init loop (the round-2 dryrun failure mode).
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mpi_acx_tpu.parallel import mesh_from_devices
    from mpi_acx_tpu.parallel.quantized import ring_psum

    n, SZ = 8, 131072
    mesh = mesh_from_devices({"x": n}, jax.devices()[:n])

    def wire_bytes(fn):
        f = shard_map(fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                      check_vma=False)
        txt = jax.jit(f).lower(
            jnp.zeros((n, SZ), jnp.float32)).compile().as_text()
        per = {"u8": 1, "s8": 1, "pred": 1, "bf16": 2, "f16": 2,
               "f32": 4, "s32": 4}
        total = 0
        for mm in _re.finditer(
                r"(u8|s8|pred|f32|s32|bf16|f16)\[([\d,]*)\]\S* "
                r"collective-permute", txt):
            cnt = 1
            for d in mm.group(2).split(","):
                if d:
                    cnt *= int(d)
            total += cnt * per[mm.group(1)]
        return total

    # Numerator and denominator share ONE ring skeleton
    # (quantized.ring_psum), so the comparison cannot silently drift.
    bq = wire_bytes(lambda v: ring_psum(v[0], "x", quantize=True)[None])
    be = wire_bytes(lambda v: ring_psum(v[0], "x", quantize=False)[None])
    print(json.dumps({
        "quant_allreduce_wire_bytes": bq,
        "exact_ring_wire_bytes": be,
        "quant_allreduce_traffic_reduction": round(be / max(bq, 1), 2),
    }))


def cpu_child_disagg():
    """Child process (forced CPU): loopback disagg serve (models/
    disagg.py) — the full wire handoff path in one process. Reports the
    TTFT handoff split (prefill vs ship vs pickup p50) for per-layer
    overlap and for the ship-after-full-prefill baseline, plus handoff
    wire throughput for the two prefill-side cache variants (int8
    quantize-at-compute vs bf16 quantize-at-wire — same wire bytes, the
    EQuARX rule, different pack cost). Deterministic in shape; no chip."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from mpi_acx_tpu.models import transformer as tfm
    from mpi_acx_tpu.models.disagg import serve_disagg_greedy
    from mpi_acx_tpu.models.serving import make_server_fns

    cfg = tfm.tiny_config()
    params = tfm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 11, 17, 8)]
    n_new = [4, 3, 5, 4]
    fns = make_server_fns(params, cfg, tfm, chunk=1, kv_int8=True)

    def one(**kw):
        b = serve_disagg_greedy(params, cfg, prompts, n_new, n_slots=2,
                                max_len=64, server_fns=fns, **kw)
        m = b.metrics
        wire = sum(h.wire_bytes for h in m.handoffs)
        wall = sum(h.ship_s + h.pickup_s for h in m.handoffs) or 1e-9
        return m, wire / wall / 1e9

    m_ov, gbps_bf16 = one()                      # warm compile caches
    m_ov, gbps_bf16 = one()
    m_no, _ = one(overlap=False)
    m_i8, gbps_int8 = one(prefill_kv_int8=True)
    print(json.dumps({
        "disagg_requests": m_ov.requests,
        "disagg_handoff_prefill_p50_ms": round(
            m_ov.handoff_prefill_p50_s * 1e3, 3),
        "disagg_handoff_ship_p50_ms": round(
            m_ov.handoff_ship_p50_s * 1e3, 3),
        "disagg_handoff_pickup_p50_ms": round(
            m_ov.handoff_pickup_p50_s * 1e3, 3),
        "disagg_noverlap_ship_p50_ms": round(
            m_no.handoff_ship_p50_s * 1e3, 3),
        "disagg_handoff_gbps_bf16": round(gbps_bf16, 4),
        "disagg_handoff_gbps_int8": round(gbps_int8, 4),
        "device": str(jax.devices()[0].platform),
    }))


def cpu_child_paged():
    """Child process (forced CPU): the serving_sweep rows for the paged
    KV plane (models/kvpage.py, DESIGN.md §19). Three claims, each a
    row family:

    1. HBM KV bytes scale with LIVE tokens, not n_slots*max_len — the
       same workload served at max_len 64 and 128 holds its paged
       high-water bytes while the fixed-slot reservation doubles.
    2. A prefix-cache hit skips the shared prefix's prefill: hit-path
       TTFT (seat -> first token, timed through on_token on a 1-slot
       strictly-sequential server) beats the cold path's.
    3. Max concurrent requests under a FIXED HBM budget: pages buy
       admission for every request whose live need fits, not only
       budget/max_len slots — verified by actually serving that
       concurrency with zero preemptions.

    Shape-deterministic; wall-clock rows are informational (CPU)."""
    import time as _t

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from mpi_acx_tpu.models import kvpage, serving
    from mpi_acx_tpu.models import transformer as tfm

    cfg = tfm.tiny_config()
    params = tfm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(5)
    pt, n_slots, chunk = 8, 2, 1

    # -- claim 1: bytes per live token vs max_len ------------------------
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 11, 17, 8)]
    n_new = [4, 3, 5, 4]
    rows = {}
    for ml in (64, 128):
        out = serving.serve_paged_greedy(
            params, cfg, prompts, n_new, n_slots=n_slots, max_len=ml,
            family=tfm, chunk=chunk, page_tokens=pt,
            return_paged_state=True)
        pkv = out.paged_state
        # Bytes of ONE page across every pool array ([L, P, pt, H, Dh]:
        # per-page = L * pt * H * Dh * itemsize, summed over k/v).
        page_bytes = sum(
            pkv.pool[k].shape[0] * pt
            * int(np.prod(pkv.pool[k].shape[3:]))
            * pkv.pool[k].dtype.itemsize for k in pkv.pool)
        # The fixed-slot server's bf16 k+v reservation at this max_len.
        fixed = (cfg.n_layers * 2 * n_slots * ml * cfg.n_heads
                 * cfg.head_dim * 2)
        rows[f"paged_kv_hwm_bytes_maxlen{ml}"] = \
            out.metrics.pages_hwm * page_bytes
        rows[f"fixed_kv_bytes_maxlen{ml}"] = fixed
    live = sum(len(p) + n for p, n in zip(prompts, n_new))
    rows["paged_kv_bytes_per_live_token"] = round(
        rows["paged_kv_hwm_bytes_maxlen64"] / live, 1)
    rows["fixed_kv_bytes_per_live_token_maxlen64"] = round(
        rows["fixed_kv_bytes_maxlen64"] / live, 1)
    # The scaling claim itself: fixed doubles with max_len, paged holds.
    rows["paged_hbm_maxlen_growth"] = round(
        rows["paged_kv_hwm_bytes_maxlen128"]
        / max(rows["paged_kv_hwm_bytes_maxlen64"], 1), 2)
    rows["fixed_hbm_maxlen_growth"] = round(
        rows["fixed_kv_bytes_maxlen128"]
        / rows["fixed_kv_bytes_maxlen64"], 2)

    # -- claim 2: prefix-hit vs cold TTFT (1 slot = sequential seats) ----
    system = rng.integers(0, cfg.vocab, 24).astype(np.int32)  # 3 pages
    shared = [np.concatenate([system,
                              rng.integers(0, cfg.vocab, 4 + i)
                              .astype(np.int32)]) for i in range(4)]

    def ttfts(prefix_cache):
        stamps = {}
        t0 = _t.perf_counter()

        def on_token(rid, tok):
            stamps.setdefault(rid, []).append(_t.perf_counter())

        out = serving.serve_paged_greedy(
            params, cfg, shared, 4, n_slots=1, max_len=40, family=tfm,
            page_tokens=pt, prefix_cache=prefix_cache, on_token=on_token)
        # Seat time for rid i on the 1-slot server is rid i-1's last
        # token (or serve start); TTFT = first token - seat.
        tt = []
        for rid in range(len(shared)):
            seat = t0 if rid == 0 else stamps[rid - 1][-1]
            tt.append(stamps[rid][0] - seat)
        return out, tt

    ttfts(False)                                  # warm compile caches
    ttfts(True)
    out_cold, tt_cold = ttfts(False)
    out_hit, tt_hit = ttfts(True)
    assert out_hit.metrics.prefix_hits >= 3, out_hit.metrics
    # p50 over the requests that CAN hit (rid >= 1).
    rows["paged_prefix_cold_ttft_p50_ms"] = round(
        sorted(tt_cold[1:])[len(tt_cold[1:]) // 2] * 1e3, 3)
    rows["paged_prefix_hit_ttft_p50_ms"] = round(
        sorted(tt_hit[1:])[len(tt_hit[1:]) // 2] * 1e3, 3)
    rows["paged_prefix_pages_reused"] = out_hit.metrics.prefix_pages_reused

    # -- claim 3: max concurrency at a fixed HBM budget ------------------
    # Budget: the fixed-slot server's 4-slot, max_len=64 reservation =
    # 32 pages of 8. Fixed admits 4 concurrent requests, period; paged
    # admits every request whose LIVE need fits the pool.
    budget_pages = 4 * (64 // pt)
    S, n = 8, 8
    need = kvpage.pages_needed(S + n + chunk, pt)
    max_conc = budget_pages // need
    many = [rng.integers(0, cfg.vocab, S).astype(np.int32)
            for _ in range(max_conc)]
    out = serving.serve_paged_greedy(
        params, cfg, many, n, n_slots=max_conc, max_len=64, family=tfm,
        chunk=chunk, page_tokens=pt, n_pages=budget_pages,
        return_paged_state=True)
    assert out.metrics.preemptions == 0, out.metrics
    assert all(not isinstance(o, serving.RequestRejected) for o in out)
    rows.update({
        "fixed_max_concurrent_at_budget": 4,
        "paged_max_concurrent_at_budget": max_conc,
        "paged_concurrency_gain": round(max_conc / 4, 2),
        "paged_budget_pages_hwm": out.metrics.pages_hwm,
        "device": str(jax.devices()[0].platform),
    })
    print(json.dumps(rows))


def _record_paged_rows(rows):
    """Fold the paged serving-sweep rows into the newest BENCH_r*.json
    (same merge-never-fail contract as _record_disagg_rows)."""
    import glob
    files = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    if not files:
        return
    try:
        with open(files[-1]) as f:
            d = json.load(f)
        d["paged"] = rows
        with open(files[-1], "w") as f:
            json.dump(d, f)
            f.write("\n")
    except Exception:  # noqa: BLE001
        pass


def _run_cpu_child(mode: str, timeout: int = 300):
    """_run_tpu_child with a forced 8-virtual-device CPU backend (a
    CPU child must never take the chip)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return _run_tpu_child(mode, attempts=1, timeout=timeout,
                          child_flag="cpu-child", env=env)


def main(full: bool = False):
    p50, p99, bw = native_bench()
    out = {
        "metric": "enqueued_pingpong_p50_latency",
        "value": p50,
        "unit": "us",
        # Latency: lower is better -> ratio >= 1 means at/above baseline.
        "vs_baseline": round(BASELINE_P50_US / p50, 3),
        "pingpong_p99_us": p99,
        "partitioned_bw_gbps": bw,
        "partitioned_bw_vs_baseline": round(bw / BASELINE_PART_BW_GBPS, 3),
    }
    # Provisional line FIRST: if a driver timeout kills us mid-TPU-retry,
    # the native metrics still reach the artifact (the driver parses the
    # last JSON line, so a completed run supersedes this one).
    provisional = dict(out)
    provisional["tpu_error"] = "provisional line: TPU measurement pending"
    print(json.dumps(provisional), flush=True)

    # Striped-wire lane sweep (socket plane). The stripes=1 no-regression
    # gate is the partitioned_bw_gbps check above: striping is off by
    # default, so native_bench IS the unstriped measurement.
    try:
        srows = native_stripe_sweep()
        out["stripe_sweep"] = srows
        _record_wire_rows(srows, bw)
    except Exception as e:  # noqa: BLE001 — report, don't crash
        out["stripe_sweep_error"] = str(e)

    # Disagg serving rows: loopback TTFT handoff split + wire GB/s for
    # the two prefill-side cache variants (CPU child), then the 3-rank
    # role-split fleet's overlap-vs-ship-after-prefill TTFT A/B — the
    # per-layer-Pready win only visible with the roles on separate
    # processes. Folded into the MULTICHIP artifact like the wire rows.
    db, derr = _run_cpu_child("disagg")
    if db is not None:
        out.update(db)
    else:
        out["disagg_error"] = derr
    try:
        drows = disagg_fleet_rows()
        out.update(drows)
        _record_disagg_rows({**(db or {}), **drows})
    except Exception as e:  # noqa: BLE001 — report, don't crash
        out["disagg_fleet_error"] = str(e)

    # Request-journey phase budget (DESIGN.md §20): where a request's
    # wall time goes — queue/prefill/ship/decode p50/p99 from the
    # journaled 3-rank fleet — so a regression in ONE leg is visible
    # even when the aggregate TTFT still passes.
    try:
        jrows = journey_phase_rows()
        out.update(jrows)
        _record_journey_rows(jrows)
    except Exception as e:  # noqa: BLE001 — report, don't crash
        out["journey_error"] = str(e)

    # Paged-KV serving sweep (CPU child): HBM-per-live-token scaling,
    # prefix-hit TTFT split, fixed-budget concurrency (DESIGN.md §19).
    pb, perr2 = _run_cpu_child("paged")
    if pb is not None:
        out.update(pb)
        _record_paged_rows(pb)
    else:
        out["paged_error"] = perr2

    # Deterministic, chip-independent design metric (CPU-compiled HLO).
    qb, qerr = _run_cpu_child("quant")
    if qb is not None:
        out.update(qb)
    else:
        out["quant_bytes_error"] = qerr

    # --- TPU capture: probe-first, per-row children, bank-as-you-go ---
    # An unavailable chip costs ONE ~150 s probe timeout (x2 attempts), not
    # 3x420 s per group; each group's rows land in BENCH_BANK.json (and,
    # in --full mode, a rewritten BENCH_FULL.json) the moment its child
    # exits, so a mid-run kill preserves everything measured so far.
    probe, perr = _run_tpu_child("probe", attempts=2, timeout=150)
    errs = {}
    results = {}
    chip_unavailable = probe is None

    def run_group(name, timeout, attempts=2):
        nonlocal chip_unavailable
        banked = _bank_reuse(name)
        if banked is not None:
            results[name] = banked
            out.update(banked)
            out[f"{name}_from_bank"] = True   # per-group provenance
            return banked
        if chip_unavailable:
            errs[name] = (f"probe failed: {perr}" if probe is None
                          else "chip unavailable mid-run (re-probe failed)")
            return None
        r, e = _run_tpu_child(name, attempts=attempts, timeout=timeout)
        if r is not None:
            results[name] = r
            out.update(r)
            _bank(r, group=name)
        else:
            errs[name] = e
            # A group that exhausted its retries usually means the
            # chip became unavailable mid-run. Re-probe CHEAPLY; if dead, later
            # groups fail fast instead of burning attempts x timeout
            # each (~1.5 h of guaranteed timeouts otherwise).
            rp, _ = _run_tpu_child("probe", attempts=1, timeout=150)
            chip_unavailable = rp is None
        return r

    fwd = run_group("fwd", timeout=420, attempts=3)
    if fwd is not None and "gpt2_fwd_tokens_per_s" in fwd:
        out["gpt2_fwd_vs_baseline"] = round(
            fwd["gpt2_fwd_tokens_per_s"] / BASELINE_GPT2_FWD_TOKS, 3)
    if probe is None:
        out["tpu_error"] = f"probe failed: {perr}"  # LOUD, never dropped
    elif fwd is None:
        out["tpu_error"] = errs["fwd"]
    def attach_banked_rows():
        """Outage fallback: attach the committed BENCH_BANK.json rows,
        clearly labeled with when and on what code they were measured.
        Rounds 2-4 each ended with a tpu_error-only artifact while
        chip-measured evidence existed in the repo — the artifact
        should carry it rather than pretend none exists. Called on ANY
        recorded outage (probe-dead OR chip unavailable mid---full).

        `*_speedup` rows are ratios and only attach when the speedup
        AND both its component rows (_SPEEDUP_COMPONENTS) carry the
        SAME recorded rev — a baseline and variant measured on
        different code (or before rev stamping, when both sides
        defaulted to "unrecorded") is refused and listed loudly under
        banked_speedups_dropped instead."""
        bank = {k: v for k, v in _load_bank().items()
                if isinstance(v, dict) and v.get("device") == "tpu"}

        def rev_of(key):
            r = bank.get(key, {}).get("rev")
            return r if r not in (None, "unrecorded", "unknown") else None

        rows, dropped = {}, {}
        for k, v in bank.items():
            if "_speedup" in k:
                parts = _SPEEDUP_COMPONENTS.get(k)
                if parts is None:
                    dropped[k] = "no component mapping for this ratio"
                    continue
                revs = {rev_of(k)} | {rev_of(p) for p in parts}
                if None in revs:
                    dropped[k] = "ratio or component rev unrecorded"
                    continue
                if len(revs) != 1:
                    dropped[k] = ("baseline and variant measured at "
                                  "different revs")
                    continue
            rows[k] = {"value": v.get("value"), "ts": v.get("ts"),
                       "rev": v.get("rev", "unrecorded")}
        if rows:
            out["banked_tpu_rows"] = rows
        if dropped:
            out["banked_speedups_dropped"] = dropped

    if "tpu_error" in out:
        attach_banked_rows()

    checks = []

    def write_full(partial: bool):
        """(Re)compute the gate over whatever has landed and write
        BENCH_FULL.json NOW — called after every child in --full mode.
        An UNMEASURED row is recorded as skipped — loudly, with the
        outage reason — NOT as a regression: a red gate must mean the
        code got slower, never that the chip was unavailable. The skip
        requires a recorded child failure for THAT row's source; a
        metric missing from a successful child (key drift), or a
        chip-INDEPENDENT child failing, still fails the gate."""
        if "tpu_error" in out or errs:
            # Keep the artifact self-contained on ANY outage shape:
            # the banked evidence must be in BENCH_FULL.json itself,
            # not only the stdout line (review r05).
            attach_banked_rows()
        checks.clear()

        def gate(name, value, baseline, higher_is_better=True,
                 unmeasured_reason=None):
            if value is None:
                if unmeasured_reason is not None:
                    checks.append({
                        "metric": name, "ok": None, "skipped": True,
                        "reason": f"not measured ({unmeasured_reason})"})
                else:
                    checks.append({
                        "metric": name, "ok": False,
                        "reason": "metric missing from a successful "
                                  "child (key drift?)"})
                return
            if higher_is_better:
                ok = value >= baseline * 0.9
            else:                  # latency: at most 10% above baseline
                ok = value <= baseline * 1.1
            checks.append({"metric": name, "value": value,
                           "baseline": baseline,
                           "ratio": round(value / baseline, 3), "ok": ok})

        def why(name):
            if name in errs:
                return f"TPU outage: {errs[name]}"
            if name not in results:
                return "child not yet run (partial write)" if partial \
                    else f"child not run: {errs.get(name, 'unknown')}"
            return None

        g = lambda n: results.get(n, {})  # noqa: E731
        gate("pingpong_p50_us", p50, BASELINE_P50_US,
             higher_is_better=False)
        gate("partitioned_bw_gbps", bw, BASELINE_PART_BW_GBPS)
        gate("gpt2_fwd_tokens_per_s",
             g("fwd").get("gpt2_fwd_tokens_per_s"),
             BASELINE_GPT2_FWD_TOKS, unmeasured_reason=why("fwd"))
        gate("gpt2_fwd_b16s512_tokens_per_s",
             g("fwd").get("gpt2_fwd_b16s512_tokens_per_s"),
             BASELINE_GPT2_FWD_B16S512_TOKS, unmeasured_reason=why("fwd"))
        gate("flash_speedup_s4096",
             g("flash").get("flash_speedup_s4096"),
             BASELINE_FLASH_SPEEDUP_4096, unmeasured_reason=why("flash"))
        gate("decode_tokens_per_s",
             g("decode").get("decode_tokens_per_s"), BASELINE_DECODE_TOKS,
             unmeasured_reason=why("decode"))
        gate("train_step_tokens_per_s",
             g("train").get("train_step_tokens_per_s"),
             BASELINE_TRAIN_TOKS, unmeasured_reason=why("train"))
        # Chip-independent row: a failure here is NEVER an outage skip.
        gate("quant_allreduce_traffic_reduction",
             (qb or {}).get("quant_allreduce_traffic_reduction"),
             BASELINE_QUANT_TRAFFIC_REDUCTION)
        out["regressions"] = [c["metric"] for c in checks
                              if c["ok"] is False]
        out["unmeasured"] = [c["metric"] for c in checks
                             if c.get("skipped")]
        doc = {"checks": checks, "result": out}
        if partial:
            doc["partial"] = True
        # Tiny smoke numbers must never overwrite the checked-in
        # artifact (same rule as _bank): they land in /tmp instead.
        dest = ("/tmp/BENCH_FULL.smoke.json"
                if os.environ.get("ACX_BENCH_TINY") == "1"
                else os.path.join(REPO, "BENCH_FULL.json"))
        tmp = dest + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, dest)

    if full:
        write_full(partial=True)
        # TPU groups FIRST and back-to-back: chip minutes are
        # the scarce resource — no host-only work may sit between them.
        # decode got 600 s when the flash A/B tripled its compile count
        # (short flash + forced dense/flash x bf16/int8 longctx).
        for name, timeout in (("flash", 420), ("decode", 600),
                              ("train", 600), ("trainseg", 900)):
            run_group(name, timeout=timeout)
            if name in errs:
                out[f"tpu_{name}_error"] = errs[name]
            write_full(partial=True)
        # Speculative decode wall-clock: informational, isolated in its
        # own children so a failure cannot cost the gated rows above
        # (spec = B=1 + the trainings; specb = the batched while_loop,
        # reusing spec's cached trained params when warm).
        for name in ("spec", "specb", "serve"):
            run_group(name, timeout=900)
            if name in errs:     # same convention as the gated groups
                out[f"tpu_{name}_error"] = errs[name]
            write_full(partial=True)
        # Host-plane message-size sweep (p50/p99 per size) — native, no
        # chip needed (round-4 verdict item #8); runs after the chip
        # work on purpose.
        sweep = []
        for msg in (1, 1024, 65536, 1048576):
            try:
                sp50, sp99, _ = native_bench(msg_bytes=msg)
                sweep.append({"msg_bytes": msg, "p50_us": sp50,
                              "p99_us": sp99})
            except Exception as e:  # noqa: BLE001 — report, don't crash
                sweep.append({"msg_bytes": msg, "error": str(e)})
        out["pingpong_sweep"] = sweep
        write_full(partial=False)

    print(json.dumps(out))
    if (full and any(c["ok"] is False for c in checks)
            and os.environ.get("ACX_BENCH_TINY") != "1"):
        # Tiny smoke: toy numbers red-flag every gate by construction;
        # the smoke's pass/fail signal is "did every child run".
        sys.exit(1)


def dryrun_decode():
    """`make decode-check` hook: run the decode child in-process on the
    tiny CPU geometry and assert the dense-vs-flash A/B rows actually
    land — the flash rows exercise the ops/flash_decode.py kernel in
    interpret mode, so this catches kernel breakage AND row-name drift
    before chip time is spent on it."""
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        tpu_child_decode()
    rows = json.loads(buf.getvalue().strip().splitlines()[-1])
    need = ["decode_flash_tokens_per_s", "decode_flash_speedup",
            "decode_longctx_dense_tokens_per_s",
            "decode_longctx_flash_tokens_per_s",
            "decode_longctx_flash_speedup",
            "decode_longctx_int8kv_dense_tokens_per_s",
            "decode_longctx_int8kv_flash_tokens_per_s",
            "decode_longctx_int8kv_flash_speedup",
            "decode_longctx_live_roofline_tokens_per_s"]
    missing = [k for k in need if k not in rows]
    assert not missing, f"decode dryrun: rows missing {missing}"
    assert all(rows[k] > 0 for k in need), rows
    print(json.dumps({"dryrun_decode_ok": True,
                      "rows": {k: rows[k] for k in need}}))


def dryrun_disagg():
    """`make disagg-check` hook: run the disagg loopback child
    in-process on the tiny CPU geometry and assert the TTFT-split and
    wire-throughput rows actually land — catches wire-path breakage and
    row-name drift before a bench window burns minutes on it. The fleet
    A/B runs in the same make target as its own acxrun legs, so this
    dryrun stays single-process."""
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cpu_child_disagg()
    rows = json.loads(buf.getvalue().strip().splitlines()[-1])
    need = ["disagg_handoff_prefill_p50_ms", "disagg_handoff_ship_p50_ms",
            "disagg_handoff_pickup_p50_ms", "disagg_noverlap_ship_p50_ms",
            "disagg_handoff_gbps_bf16", "disagg_handoff_gbps_int8"]
    missing = [k for k in need if k not in rows]
    assert not missing, f"disagg dryrun: rows missing {missing}"
    assert all(rows[k] > 0 for k in need), rows
    print(json.dumps({"dryrun_disagg_ok": True,
                      "rows": {k: rows[k] for k in need}}))


def dryrun_paged():
    """`make paged-check` hook: run the paged serving child in-process
    on the tiny CPU geometry and assert the three §19 row families
    actually land — the HBM-scaling rows, the prefix-hit TTFT split,
    and the fixed-budget concurrency rows — catching scheduler
    breakage and row-name drift before a bench window burns minutes on
    it. The 3-rank paged fleet runs in the same make target as its own
    acxrun legs, so this dryrun stays single-process."""
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cpu_child_paged()
    rows = json.loads(buf.getvalue().strip().splitlines()[-1])
    need = ["paged_kv_hwm_bytes_maxlen64", "paged_kv_hwm_bytes_maxlen128",
            "fixed_kv_bytes_maxlen64", "fixed_kv_bytes_maxlen128",
            "paged_kv_bytes_per_live_token", "paged_hbm_maxlen_growth",
            "fixed_hbm_maxlen_growth", "paged_prefix_cold_ttft_p50_ms",
            "paged_prefix_hit_ttft_p50_ms", "paged_prefix_pages_reused",
            "paged_max_concurrent_at_budget", "paged_concurrency_gain"]
    missing = [k for k in need if k not in rows]
    assert missing == [], f"paged dryrun: rows missing {missing}"
    # The acceptance shape: the fixed reservation doubles with max_len,
    # the paged high-water does not move (live tokens are unchanged);
    # pages buy strictly more concurrency than slots at equal HBM.
    assert rows["fixed_hbm_maxlen_growth"] == 2.0, rows
    assert rows["paged_hbm_maxlen_growth"] == 1.0, rows
    assert rows["paged_concurrency_gain"] > 1, rows
    assert rows["paged_prefix_pages_reused"] >= 9, rows  # 3 hits * 3 pages
    _record_paged_rows(rows)
    print(json.dumps({"dryrun_paged_ok": True,
                      "rows": {k: rows[k] for k in need}}))


if __name__ == "__main__":
    if ("--dryrun-decode" in sys.argv or "--dryrun-disagg" in sys.argv
            or "--dryrun-paged" in sys.argv):
        # The dryrun is a correctness smoke, never a measurement: force
        # the tiny CPU geometry no matter how it was invoked.
        os.environ["ACX_BENCH_TINY"] = "1"
    if os.environ.get("ACX_BENCH_TINY") == "1":
        # Smoke mode runs on CPU by definition: pin through the config,
        # which wins over the environment.
        import jax
        jax.config.update("jax_platforms", "cpu")
    if any(a.startswith(("--tpu-child-", "--cpu-child-"))
           for a in sys.argv):
        # Children compile; this parent never touches JAX.
        from mpi_acx_tpu import backend
        backend.enable_compile_cache()
    if "--cpu-child-quant" in sys.argv:
        cpu_child_quant()
    elif "--cpu-child-disagg" in sys.argv:
        cpu_child_disagg()
    elif "--cpu-child-paged" in sys.argv:
        cpu_child_paged()
    elif "--dryrun-disagg" in sys.argv:
        dryrun_disagg()
    elif "--dryrun-paged" in sys.argv:
        dryrun_paged()
    elif "--tpu-child-probe" in sys.argv:
        tpu_child_probe()
    elif "--tpu-child-fwd" in sys.argv:
        tpu_child_fwd()
    elif "--tpu-child-flash" in sys.argv:
        tpu_child_flash()
    elif "--dryrun-decode" in sys.argv:
        dryrun_decode()
    elif "--tpu-child-decode" in sys.argv:
        tpu_child_decode()
    elif "--tpu-child-trainseg" in sys.argv:
        tpu_child_trainseg()
    elif "--tpu-child-train" in sys.argv:
        tpu_child_train()
    elif "--tpu-child-serve" in sys.argv:
        tpu_child_serve()
    elif "--tpu-child-specb" in sys.argv:
        tpu_child_specb()
    elif "--tpu-child-spec" in sys.argv:
        tpu_child_spec()
    else:
        main(full="--full" in sys.argv)
