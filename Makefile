# tpu-acx native runtime build.
# Counterpart of the reference's nvcc Makefile (reference Makefile:1-49), but
# plain g++: the device compiler on TPU is XLA/Pallas, reached from Python;
# everything here is host-side runtime (proxy, transport, stream/graph queue,
# public MPIX API, launcher).
#
# Knobs (mirroring reference Makefile:1-6):
#   CXX              host compiler (default g++)
#   ACX_DEBUG=1      compile in debug logging (reference: -DDEBUG)

CXX      ?= g++
CXXFLAGS ?= -O2 -g -Wall -Wextra -std=c++17 -fPIC -pthread -MMD -MP
INCLUDES  = -Iinclude -Iinclude/compat -I.
LDFLAGS   = -pthread

ifeq ($(ACX_DEBUG), 1)
CXXFLAGS += -DACX_DEBUG
endif

BUILD := build

# Sources are wildcarded: every directory below is part of the library the
# moment its files exist, and `make all` never references a file that does not.
LIB_SRCS := $(wildcard src/core/*.cc) \
            $(wildcard src/net/*.cc) \
            $(wildcard src/runtime/*.cc) \
            $(wildcard src/shim/*.cc) \
            $(wildcard src/api/*.cc)
LIB_OBJS := $(LIB_SRCS:%.cc=$(BUILD)/%.o)

LIB       = $(BUILD)/libtpuacx.so
STATICLIB = $(BUILD)/libtpuacx.a

.PHONY: all lib tools ctest itest check reftests clean

all: lib tools ctest itest

lib: $(LIB) $(STATICLIB)

$(BUILD)/%.o: %.cc
	@mkdir -p $(dir $@)
	$(CXX) $(CXXFLAGS) $(INCLUDES) -c $< -o $@

$(LIB): $(LIB_OBJS)
	$(CXX) -shared $(LIB_OBJS) -o $@ $(LDFLAGS)

$(STATICLIB): $(LIB_OBJS)
	ar rcs $@ $(LIB_OBJS)

# --- launcher (reference: mpiexec; ours: acxrun) ---
TOOL_SRCS := $(wildcard tools/*.cc)
TOOL_BINS := $(TOOL_SRCS:tools/%.cc=$(BUILD)/%)

tools: $(TOOL_BINS)

$(BUILD)/%: tools/%.cc $(STATICLIB)
	@mkdir -p $(BUILD)
	$(CXX) $(CXXFLAGS) $(INCLUDES) $< $(STATICLIB) -o $@ $(LDFLAGS)

# --- unit tests (single process, fake transport) ---
CTEST_SRCS := $(wildcard ctests/*.cc)
CTEST_BINS := $(CTEST_SRCS:ctests/%.cc=$(BUILD)/ctests/%)

ctest: $(CTEST_BINS)

$(BUILD)/ctests/%: ctests/%.cc $(STATICLIB)
	@mkdir -p $(BUILD)/ctests
	$(CXX) $(CXXFLAGS) $(INCLUDES) $< $(STATICLIB) -o $@ $(LDFLAGS)

# --- integration tests (multi-process, run under acxrun) ---
# Ports of the reference's six ring programs (reference test/src/*); built
# against the same compat headers (include/compat) the reference tests use.
ITEST_SRCS := $(wildcard itests/*.c) $(wildcard itests/*.cc)
ITEST_BINS := $(patsubst itests/%.c,$(BUILD)/itests/%,$(filter %.c,$(ITEST_SRCS))) \
              $(patsubst itests/%.cc,$(BUILD)/itests/%,$(filter %.cc,$(ITEST_SRCS)))

itest: $(ITEST_BINS)

$(BUILD)/itests/%: itests/%.c $(STATICLIB)
	@mkdir -p $(BUILD)/itests
	$(CXX) $(CXXFLAGS) $(INCLUDES) -x c++ $< -x none $(STATICLIB) -o $@ $(LDFLAGS)

$(BUILD)/itests/%: itests/%.cc $(STATICLIB)
	@mkdir -p $(BUILD)/itests
	$(CXX) $(CXXFLAGS) $(INCLUDES) $< $(STATICLIB) -o $@ $(LDFLAGS)

# --- reference-test source compatibility ---
# Compiles NVIDIA/mpi-acx's own C test programs UNCHANGED from
# /root/reference/test/src against our compat headers (mpi.h, cuda_runtime.h,
# mpi-acx.h) and runs them under acxrun. This is the north-star check:
# "test/ builds unchanged". (ring-partitioned.cu needs nvcc and is covered by
# our itests/ring-partitioned port instead.)
REF          ?= /root/reference
REF_TEST_DIR ?= $(REF)/test/src
REF_TESTS := ring ring-all ring-all-device ring-all-graph ring-all-graph-construction
REF_BINS  := $(REF_TESTS:%=$(BUILD)/reftests/%)

reftests: $(REF_BINS) tools
	@for t in $(REF_BINS); do echo "== acxrun -np 2 $$t"; $(BUILD)/acxrun -np 2 $$t || exit 1; done
	@echo "ALL REFERENCE TESTS PASSED"

$(BUILD)/reftests/%: $(REF_TEST_DIR)/%.c $(STATICLIB)
	@mkdir -p $(BUILD)/reftests
	$(CXX) $(CXXFLAGS) -Wno-unused-parameter $(INCLUDES) -x c++ $< -x none $(STATICLIB) -o $@ $(LDFLAGS)

# --- run everything ---
# Integration tests run on both data planes: shm (default, SPSC rings in a
# memfd) and socket (AF_UNIX, the cross-host-shaped wire).
check: ctest itest tools
	@for t in $(CTEST_BINS); do echo "== $$t"; $$t || exit 1; done
	@for t in $(ITEST_BINS); do echo "== acxrun -np 2 $$t (shm)"; $(BUILD)/acxrun -np 2 $$t || exit 1; done
	@for t in $(ITEST_BINS); do echo "== acxrun -np 2 $$t (socket)"; $(BUILD)/acxrun -np 2 -transport socket $$t || exit 1; done
	@for t in $(ITEST_BINS); do echo "== acxrun -np 2 $$t (rendezvous-all)"; ACX_RV_THRESHOLD=1 $(BUILD)/acxrun -np 2 $$t || exit 1; done
	@for t in $(ITEST_BINS); do echo "== acxrun -np 2 $$t (rendezvous-nack)"; ACX_RV_THRESHOLD=1 ACX_RV_FORCE_FALLBACK=1 $(BUILD)/acxrun -np 2 $$t || exit 1; done
	@for t in $(ITEST_BINS); do echo "== acxrun -np 2 $$t (rendezvous-socket)"; ACX_RV_THRESHOLD=1 $(BUILD)/acxrun -np 2 -transport socket $$t || exit 1; done
	@for t in $(ITEST_BINS); do echo "== acxrun -np 4 $$t (shm, 4 ranks)"; $(BUILD)/acxrun -np 4 $$t || exit 1; done
	@echo "== acxrun -np 2 fuzz (canary: corruption must be DETECTED)"
	@ACX_FUZZ_CANARY=1 $(BUILD)/acxrun -np 2 $(BUILD)/itests/fuzz || exit 1
	@echo "== acxrun -np 2 fuzz (second seed)"
	@ACX_FUZZ_SEED=98761 $(BUILD)/acxrun -np 2 $(BUILD)/itests/fuzz || exit 1
	@echo "== acxrun -np 2 ring (fault: transient send drop -> retry -> OK)"
	@$(BUILD)/acxrun -np 2 -fault drop:rank=0:kind=send:nth=1 $(BUILD)/itests/ring || exit 1
	@echo "== acxrun -np 2 ring (fault: 5ms delay on rank 1's first recv)"
	@$(BUILD)/acxrun -np 2 -fault delay:rank=1:kind=recv:nth=1:us=5000 $(BUILD)/itests/ring || exit 1
	@$(MAKE) --no-print-directory chaos-check || exit 1
	@$(MAKE) --no-print-directory membership-check || exit 1
	@$(MAKE) --no-print-directory metrics-check || exit 1
	@$(MAKE) --no-print-directory tseries-check || exit 1
	@$(MAKE) --no-print-directory doctor-check || exit 1
	@$(MAKE) --no-print-directory causality-check || exit 1
	@$(MAKE) --no-print-directory decode-check || exit 1
	@$(MAKE) --no-print-directory stripe-check || exit 1
	@$(MAKE) --no-print-directory disagg-check || exit 1
	@$(MAKE) --no-print-directory paged-check || exit 1
	@$(MAKE) --no-print-directory request-check || exit 1
	@$(MAKE) --no-print-directory lint || exit 1
	@$(MAKE) --no-print-directory asan-ctest || exit 1
	@echo "ALL NATIVE TESTS PASSED"

# --- survivable links end-to-end (DESIGN.md §9) ---
# chaos-ring under every wire-level fault on the socket plane (the only
# plane with reconnectable links), drain-on-death with a mid-flight rank
# kill, and a metrics-instrumented chaos leg validated by the merge tool.
.PHONY: chaos-check
chaos-check: itest tools
	@echo "== chaos-check: drop_frame (sequence gap -> NAK re-pull)"
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault drop_frame:rank=0:nth=3:count=2 $(BUILD)/itests/chaos-ring || exit 1
	@echo "== chaos-check: corrupt_frame (CRC reject -> NAK -> replay)"
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault corrupt_frame:rank=1:nth=4:count=3 $(BUILD)/itests/chaos-ring || exit 1
	@echo "== chaos-check: stall_link_ms (frozen send side, no loss)"
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault stall_link_ms:rank=0:nth=5:ms=40 $(BUILD)/itests/chaos-ring || exit 1
	@echo "== chaos-check: close_link_once (epoch-bumped reconnect + replay)"
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault close_link_once:rank=0:nth=6 $(BUILD)/itests/chaos-ring || exit 1
	@echo "== chaos-check: drain-on-death (survivors drain and exit 0)"
	@$(BUILD)/acxrun -np 3 $(BUILD)/itests/drain-on-death || exit 1
	@echo "== chaos-check: fault placement sweep (3 fixed seeds)"
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault drop_frame:rank=1:nth=7:count=1 $(BUILD)/itests/chaos-ring || exit 1
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault corrupt_frame:rank=0:nth=9:count=2 $(BUILD)/itests/chaos-ring || exit 1
	@$(BUILD)/acxrun -np 2 -transport socket \
	  -fault stall_link_ms:rank=1:nth=3:ms=60 $(BUILD)/itests/chaos-ring || exit 1
	@rm -rf $(BUILD)/chaos-metrics && mkdir -p $(BUILD)/chaos-metrics
	@echo "== chaos-check: corrupt_frame with ACX_METRICS + ACX_TRACE"
	@ACX_METRICS=$(BUILD)/chaos-metrics/run ACX_TRACE=$(BUILD)/chaos-metrics/run \
	  $(BUILD)/acxrun -np 2 -transport socket \
	  -fault corrupt_frame:rank=0:nth=2 $(BUILD)/itests/chaos-ring || exit 1
	@python3 tools/acx_trace_merge.py --validate \
	  --out $(BUILD)/chaos-metrics/merged.trace.json \
	  --metrics-out $(BUILD)/chaos-metrics/fleet.metrics.json \
	  $(BUILD)/chaos-metrics/run.rank*.trace.json \
	  $(BUILD)/chaos-metrics/run.rank*.metrics.json || exit 1
	@echo "== chaos-check: conductor kill + respawn + invariant oracle"
	@rm -rf $(BUILD)/chaos-oracle
	@python3 tools/acx_chaos.py run --np 3 --timeout 90 \
	  --acxrun $(BUILD)/acxrun --out $(BUILD)/chaos-oracle/kill \
	  --fault kill:rank=1:nth=7 \
	  -- $(BUILD)/itests/chaos-conductor || exit 1
	@echo "== chaos-check: conductor kill mid-stripe (2 lanes, big rounds)"
	@ACX_STRIPES=2 ACX_CC_INTS=16384 \
	  python3 tools/acx_chaos.py run --np 3 --timeout 90 \
	  --acxrun $(BUILD)/acxrun --out $(BUILD)/chaos-oracle/stripe-kill \
	  --fault kill:rank=1:nth=7 \
	  -- $(BUILD)/itests/chaos-conductor || exit 1
	@echo "== chaos-check: broken control (must fail, shrink, print replay)"
	@python3 tools/acx_chaos.py run --np 3 --timeout 60 --expect-fail \
	  --acxrun $(BUILD)/acxrun --out $(BUILD)/chaos-oracle/broken \
	  --fault 'stall_link_ms:rank=0:nth=3:ms=20;drop_frame:rank=0:nth=500000' \
	  -- $(BUILD)/itests/chaos-conductor || exit 1
	@$(MAKE) --no-print-directory chaos-soak SEEDS=3 || exit 1
	@echo "CHAOS CHECK PASSED"

# --- seeded multi-fault soak (tentpole PR: chaos conductor) ---
# N consecutive seeds from ACX_CHAOS_SEED_BASE (default 1000); each seed
# deterministically expands (acxrun -print-chaos) into a multi-fault
# schedule, runs the conductor under it, and is audited by the invariant
# oracle — every scheduled fault must actually fire. A nightly rotation
# just sets ACX_CHAOS_SEED_BASE=$(date +%j)000 or similar; any failure
# prints a shrunken schedule and an exact replay command.
.PHONY: chaos-soak
SEEDS ?= 3
chaos-soak: itest tools
	@python3 tools/acx_chaos.py soak --np 3 --seeds $(SEEDS) \
	  --faults 4 --mix issue,wire --timeout 90 \
	  --acxrun $(BUILD)/acxrun --out $(BUILD)/chaos-soak \
	  -- $(BUILD)/itests/chaos-conductor || exit 1

# --- elastic fleet / membership plane end-to-end (DESIGN.md §12) ---
# rolling-restart replaces every rank of the fleet one at a time under
# load (socket plane: the only one a joiner can dial into), at two fleet
# sizes, then deliberately wedges a join (ACX_RR_WEDGE=1): survivors must
# time the join out with exit 7 and flight dumps, and acx_doctor.py must
# attribute the hang to the victim even with its dump deleted — the gap
# itself is the evidence.
.PHONY: membership-check
membership-check: itest tools
	@echo "== membership-check: rolling-restart -np 2 (socket)"
	@$(BUILD)/acxrun -np 2 -timeout 120 -transport socket \
	  $(BUILD)/itests/rolling-restart || exit 1
	@echo "== membership-check: rolling-restart -np 3 (socket)"
	@$(BUILD)/acxrun -np 3 -timeout 120 -transport socket \
	  $(BUILD)/itests/rolling-restart || exit 1
	@rm -rf $(BUILD)/membership-check && mkdir -p $(BUILD)/membership-check
	@echo "== membership-check: wedged join (exit 7 + doctor attribution)"
	@ACX_RR_WEDGE=1 ACX_FLEET_JOIN_TIMEOUT_MS=8000 \
	  ACX_FLIGHT=$(BUILD)/membership-check/rr \
	  $(BUILD)/acxrun -np 3 -timeout 120 -transport socket \
	  $(BUILD)/itests/rolling-restart; \
	  st=$$?; [ $$st -eq 7 ] || { echo "wedge leg: want exit 7, got $$st"; exit 1; }
	@rm -f $(BUILD)/membership-check/rr.rank1.flight.json
	@python3 tools/acx_doctor.py \
	  --expect-anomaly dead_link --expect-culprit 1 \
	  $(BUILD)/membership-check/rr.rank*.flight.json || exit 1
	@echo "MEMBERSHIP CHECK PASSED"

# --- metrics plane end-to-end ---
# 2-rank ping-pong with metrics + tracing on, then validate every artifact
# (span balance, counter/histogram invariants) and produce the merged
# Perfetto timeline + fleet metrics with tools/acx_trace_merge.py.
.PHONY: metrics-check
metrics-check: ctest tools
	@rm -rf $(BUILD)/metrics-check && mkdir -p $(BUILD)/metrics-check
	@echo "== metrics-check: acxrun -np 2 bench_pingpong (ACX_METRICS + ACX_TRACE)"
	@ACX_METRICS=$(BUILD)/metrics-check/run ACX_TRACE=$(BUILD)/metrics-check/run \
	  ACX_TRACE_CAP=2000000 \
	  $(BUILD)/acxrun -np 2 $(BUILD)/bench_pingpong 8 > /dev/null || exit 1
	@python3 tools/acx_trace_merge.py --validate \
	  --out $(BUILD)/metrics-check/merged.trace.json \
	  --metrics-out $(BUILD)/metrics-check/fleet.metrics.json \
	  $(BUILD)/metrics-check/run.rank*.trace.json \
	  $(BUILD)/metrics-check/run.rank*.metrics.json || exit 1
	@echo "== metrics-check: flight-recorder hot-path overhead bound"
	@$(BUILD)/ctests/test_flight || exit 1
	@echo "METRICS CHECK PASSED"

# --- live telemetry plane end-to-end (DESIGN.md §13) ---
# 2-rank ping-pong with periodic sampling on, then acx_top's CI mode
# asserts series sanity (>= 2 samples/rank, monotone clocks, per-link
# wire >= payload byte accounting), the name-table ctest runs, and the
# merge tool folds the tseries stream in with barrier-anchored skew.
.PHONY: tseries-check
tseries-check: ctest tools
	@rm -rf $(BUILD)/tseries-check && mkdir -p $(BUILD)/tseries-check
	@echo "== tseries-check: acxrun -np 2 bench_pingpong (ACX_TSERIES)"
	@ACX_TSERIES=$(BUILD)/tseries-check/run ACX_TSERIES_INTERVAL_MS=50 \
	  ACX_TRACE=$(BUILD)/tseries-check/run \
	  $(BUILD)/acxrun -np 2 $(BUILD)/bench_pingpong 8 > /dev/null || exit 1
	@echo "== tseries-check: acx_top --once --json --check"
	@python3 tools/acx_top.py --once --json --check \
	  $(BUILD)/tseries-check/run > /dev/null || exit 1
	@echo "== tseries-check: skew-corrected fleet merge"
	@python3 tools/acx_trace_merge.py --validate \
	  --tseries-out $(BUILD)/tseries-check/fleet.tseries.json \
	  $(BUILD)/tseries-check/run.rank*.trace.json \
	  $(BUILD)/tseries-check/run.rank*.tseries.jsonl || exit 1
	@echo "== tseries-check: metrics name-table/enum agreement"
	@$(BUILD)/ctests/test_metrics_names || exit 1
	@echo "TSERIES CHECK PASSED"

# --- stall watchdog + hang doctor end-to-end (DESIGN.md §10) ---
# hang-doctor wedges ranks 0/1 on purpose (withheld Pready + unanswered
# recv); every stuck rank's watchdog must write a flight dump while the job
# is hung, and tools/acx_doctor.py must pair the per-rank dumps and name
# both the anomaly and the culprit rank.
.PHONY: doctor-check
doctor-check: ctest itest tools
	@rm -rf $(BUILD)/doctor-check && mkdir -p $(BUILD)/doctor-check
	@echo "== doctor-check: acxrun -np 2 hang-doctor (watchdog dumps fire)"
	@ACX_FLIGHT=$(BUILD)/doctor-check/hang \
	  $(BUILD)/acxrun -np 2 $(BUILD)/itests/hang-doctor || exit 1
	@echo "== doctor-check: acx_doctor.py names the culprit"
	@python3 tools/acx_doctor.py \
	  --expect-anomaly never_published_partition --expect-culprit 0 \
	  $(BUILD)/doctor-check/hang.rank*.flight.json || exit 1
	@echo "DOCTOR CHECK PASSED"

# --- cross-rank causal tracing end-to-end (DESIGN.md §14) ---
# causality-ping runs a strictly serialized 2-rank ping-pong on the
# socket plane with tracing on; acx_critpath.py must span-pair >= 95% of
# wire frames across the ranks (no heuristics), see non-negative one-way
# transit after the barrier-anchored skew correction, and reconstruct a
# non-empty critical path. The stall leg injects a 40 ms freeze on rank
# 0's 5th frame and the analyzer must name the 0->1 link as the longest
# edge of the step — the whole point of the plane.
.PHONY: causality-check
causality-check: itest tools
	@rm -rf $(BUILD)/causality-check && mkdir -p $(BUILD)/causality-check
	@echo "== causality-check: acxrun -np 2 causality-ping (socket, ACX_TRACE)"
	@ACX_TRACE=$(BUILD)/causality-check/ping ACX_TRACE_CAP=2000000 \
	  $(BUILD)/acxrun -np 2 -transport socket \
	  $(BUILD)/itests/causality-ping || exit 1
	@echo "== causality-check: merged trace validates"
	@python3 tools/acx_trace_merge.py --validate \
	  --out $(BUILD)/causality-check/merged.trace.json \
	  $(BUILD)/causality-check/ping.rank*.trace.json > /dev/null || exit 1
	@echo "== causality-check: span pairing + transit + critical path"
	@python3 tools/acx_critpath.py --min-pair-rate 0.95 \
	  --expect-nonneg-transit \
	  $(BUILD)/causality-check/ping.rank*.trace.json || exit 1
	@echo "== causality-check: injected stall names the 0->1 link"
	@ACX_TRACE=$(BUILD)/causality-check/stall ACX_TRACE_CAP=2000000 \
	  $(BUILD)/acxrun -np 2 -transport socket \
	  -fault stall_link_ms:rank=0:nth=5:ms=40 \
	  $(BUILD)/itests/causality-ping || exit 1
	@python3 tools/acx_critpath.py --expect-edge "0->1" \
	  $(BUILD)/causality-check/stall.rank*.trace.json || exit 1
	@echo "CAUSALITY CHECK PASSED"

# --- flash-decode kernel (ops/flash_decode.py, DESIGN.md §11) ---
# Interpret-mode parity of the Pallas decode kernel vs the dense
# reference (GQA/window/per-slot-pos/int8 grid + block-skip). No chip
# required — the kernel runs interpreted.
.PHONY: decode-check
decode-check:
	@echo "== decode-check: flash-decode interpret parity"
	@JAX_PLATFORMS=cpu python3 -m pytest tests/test_flash_decode.py -q \
	  -p no:cacheprovider || exit 1
	@echo "DECODE CHECK PASSED"

# --- multi-path striped transport end-to-end (DESIGN.md §15) ---
# chaos-ring with 64 KiB messages fanned across subflows: healthy striped
# traffic, a dropped chunk NAK-healed in its own lane's seq space, a
# stalled lane forcing cross-lane chunk reorder, a killed lane redialing
# (or degrading to survivors) under load — every payload byte-exact
# throughout — plus a striped causality leg whose merged trace still
# pairs every span and keeps one-way transit non-negative.
.PHONY: stripe-check
stripe-check: itest tools
	@echo "== stripe-check: striped chaos-ring (4 lanes, 64KiB msgs, fault-free)"
	@ACX_STRIPES=4 ACX_CHAOS_INTS=16384 $(BUILD)/acxrun -np 2 -transport socket \
	  $(BUILD)/itests/chaos-ring || exit 1
	@echo "== stripe-check: drop_frame on subflow 2 (per-lane NAK re-pull)"
	@ACX_STRIPES=4 ACX_CHAOS_INTS=16384 $(BUILD)/acxrun -np 2 -transport socket \
	  -fault drop_frame:rank=0:subflow=2:nth=4:count=2 $(BUILD)/itests/chaos-ring || exit 1
	@echo "== stripe-check: stall_link_ms on subflow 1 (cross-lane reorder)"
	@ACX_STRIPES=2 ACX_CHAOS_INTS=16384 $(BUILD)/acxrun -np 2 -transport socket \
	  -fault stall_link_ms:rank=0:subflow=1:nth=3:ms=40 $(BUILD)/itests/chaos-ring || exit 1
	@echo "== stripe-check: close_link_once on subflow 1 (lane redial under load)"
	@ACX_STRIPES=2 ACX_CHAOS_INTS=16384 $(BUILD)/acxrun -np 2 -transport socket \
	  -fault close_link_once:rank=0:subflow=1:nth=5 $(BUILD)/itests/chaos-ring || exit 1
	@rm -rf $(BUILD)/stripe-check && mkdir -p $(BUILD)/stripe-check
	@echo "== stripe-check: striped causality-ping (spans pair, transit >= 0)"
	@ACX_STRIPES=2 ACX_PING_INTS=16384 ACX_TRACE=$(BUILD)/stripe-check/ping \
	  ACX_TRACE_CAP=2000000 $(BUILD)/acxrun -np 2 -transport socket \
	  $(BUILD)/itests/causality-ping || exit 1
	@python3 tools/acx_critpath.py --min-pair-rate 0.95 \
	  --expect-nonneg-transit \
	  $(BUILD)/stripe-check/ping.rank*.trace.json || exit 1
	@echo "STRIPE CHECK PASSED"

# --- disaggregated prefill/decode serving (DESIGN.md §17) ---
# Loopback parity suite (the full wire handoff bit-equal to the
# monolithic server, mid-handoff failure requeue), a 3-rank role-split
# fleet (1 prefill + 2 decode) on the socket plane with both decode
# ranks byte-checking against a local monolithic serve, and the same
# fleet with the prefill rank SIGKILLed mid-handoff under the chaos
# oracle (supervisor respawns it, the torn handoff requeues UNCHARGED,
# the re-ship satisfies it, acx_doctor attributes the dead link).
.PHONY: disagg-check
disagg-check: tools
	@echo "== disagg-check: loopback parity + handoff-failure suite"
	@JAX_PLATFORMS=cpu python3 -m pytest tests/test_disagg.py -q \
	  -p no:cacheprovider || exit 1
	@echo "== disagg-check: 3-rank role-split fleet (1 prefill + 2 decode)"
	@ACX_ROLE=prefill,decode,decode $(BUILD)/acxrun -np 3 -timeout 240 \
	  -transport socket python3 tests/disagg_worker.py || exit 1
	@echo "== disagg-check: kill prefill mid-handoff (chaos oracle + doctor)"
	@rm -rf $(BUILD)/disagg-oracle
	@ACX_ROLE=prefill,decode,decode python3 tools/acx_chaos.py run --np 3 \
	  --timeout 240 --acxrun $(BUILD)/acxrun \
	  --out $(BUILD)/disagg-oracle/kill --fault kill:rank=0:nth=8 \
	  -- python3 tests/disagg_worker.py || exit 1
	@echo "DISAGG CHECK PASSED"

# --- paged KV cache + radix prefix sharing + page-pressure scheduling
# (DESIGN.md §19). Three legs: the pytest suite (kernel bit-parity grid,
# allocator/trie/COW units, serve_paged_greedy vs serve_greedy
# bit-equality incl. preempt-then-resume, prefix reuse), a CPU interpret
# smoke of the paged Pallas kernel proper, and the 3-rank fleet with
# decode ranks seating SHIPPED pages (byte-checked against a local
# monolithic serve) plus the same fleet with the prefill rank SIGKILLed
# under the chaos oracle.
.PHONY: paged-check
paged-check: tools
	@echo "== paged-check: paged KV parity + scheduler suite"
	@JAX_PLATFORMS=cpu python3 -m pytest tests/test_paged.py -q \
	  -p no:cacheprovider || exit 1
	@echo "== paged-check: paged Pallas kernel interpret smoke"
	@JAX_PLATFORMS=cpu python3 -m pytest \
	  "tests/test_paged.py::test_paged_flash_bit_equals_fixed_flash" -q \
	  -p no:cacheprovider || exit 1
	@echo "== paged-check: 3-rank fleet, decode ranks on paged pools"
	@ACX_ROLE=prefill,decode,decode $(BUILD)/acxrun -np 3 -timeout 240 \
	  -transport socket python3 tests/paged_worker.py || exit 1
	@echo "== paged-check: the same fleet at a chunk of 4 (requests end mid-chunk)"
	@ACX_ROLE=prefill,decode,decode ACX_PAGED_CHUNK=4 $(BUILD)/acxrun -np 3 \
	  -timeout 240 -transport socket python3 tests/paged_worker.py || exit 1
	@echo "== paged-check: kill prefill mid-handoff (paged intake rollback)"
	@rm -rf $(BUILD)/paged-oracle
	@ACX_ROLE=prefill,decode,decode python3 tools/acx_chaos.py run --np 3 \
	  --timeout 240 --acxrun $(BUILD)/acxrun \
	  --out $(BUILD)/paged-oracle/kill --fault kill:rank=0:nth=8 \
	  -- python3 tests/paged_worker.py || exit 1
	@echo "PAGED CHECK PASSED"

# --- request-journey tracing + SLO burn-rate plane (DESIGN.md §20) ---
# A 3-rank disaggregated fleet with ACX_REQLOG armed: every rank logs
# each request's lifecycle events, and tools/acx_request.py --check
# must reconstruct >= 95% of the journeys admit->finish ACROSS ranks
# (skew-corrected via the sibling traces) and emit the SLO burn-rate
# section. The second leg stalls the prefill rank's wire repeatedly
# (stall_link_ms on every frame from the 3rd) and the reconstructor
# must name the shipping edge as the fleet-dominant service phase —
# the whole point of the plane: "where did this request's time go"
# answered with the faulty leg, not a shrug.
.PHONY: request-check
request-check: tools
	@rm -rf $(BUILD)/request-check && mkdir -p $(BUILD)/request-check
	@echo "== request-check: 3-rank fleet with ACX_REQLOG armed"
	@ACX_ROLE=prefill,decode,decode ACX_REQLOG=$(BUILD)/request-check/run \
	  ACX_TRACE=$(BUILD)/request-check/run ACX_TRACE_CAP=2000000 \
	  $(BUILD)/acxrun -np 3 -timeout 240 \
	  -transport socket python3 tests/request_worker.py || exit 1
	@echo "== request-check: journeys reconstruct (>= 95% admit->finish)"
	@ACX_SERVE_ADMIT_TTFT_MS=60000 ACX_SERVE_ADMIT_ITL_MS=60000 \
	  python3 tools/acx_request.py --check --min-reconstructed 0.95 \
	  --waterfall 3 --json $(BUILD)/request-check/journeys.json \
	  $(BUILD)/request-check/run.rank*.reqlog.jsonl \
	  $(BUILD)/request-check/run.rank*.trace.json || exit 1
	@echo "== request-check: stalled wire -> dominant phase is the ship edge"
	@ACX_ROLE=prefill,decode,decode ACX_REQLOG=$(BUILD)/request-check/stall \
	  ACX_TRACE=$(BUILD)/request-check/stall ACX_TRACE_CAP=2000000 \
	  $(BUILD)/acxrun -np 3 -timeout 240 -transport socket \
	  -fault stall_link_ms:rank=0:nth=3:count=100000:ms=250 \
	  python3 tests/request_worker.py || exit 1
	@python3 tools/acx_request.py --check --expect-dominant ship \
	  --json $(BUILD)/request-check/stall.journeys.json \
	  $(BUILD)/request-check/stall.rank*.reqlog.jsonl \
	  $(BUILD)/request-check/stall.rank*.trace.json || exit 1
	@echo "REQUEST CHECK PASSED"

# Header dependency tracking (-MMD): a header edit rebuilds its users.
-include $(LIB_OBJS:.o=.d)

clean:
	rm -rf $(BUILD)

# --- ThreadSanitizer build + run (race detection the reference lacks,
# SURVEY.md §5.2). Rebuilds everything into build-tsan/ and runs the unit
# suite plus the multi-process integration tests under TSAN.
.PHONY: tsan
tsan:
	@$(MAKE) --no-print-directory BUILD=build-tsan \
	  CXXFLAGS="$(CXXFLAGS) -O1 -fsanitize=thread" \
	  LDFLAGS="-pthread -fsanitize=thread" \
	  ctest itest tools
	@for t in $(CTEST_BINS:$(BUILD)/%=build-tsan/%); do \
	  echo "== tsan $$t"; TSAN_OPTIONS=halt_on_error=1 $$t || exit 1; done
	@for t in $(ITEST_BINS:$(BUILD)/%=build-tsan/%); do \
	  echo "== tsan acxrun -np 2 $$t"; \
	  TSAN_OPTIONS=halt_on_error=1 build-tsan/acxrun -np 2 -timeout 600 $$t || exit 1; done
	@echo "== tsan acxrun -np 2 rolling-restart (socket, membership plane)"
	@TSAN_OPTIONS=halt_on_error=1 build-tsan/acxrun -np 2 -timeout 600 \
	  -transport socket build-tsan/itests/rolling-restart || exit 1
	@echo "TSAN CLEAN"

# --- static analysis (docs/DESIGN.md §18) ---
# `lint` is the cross-layer contract audit (tools/acx_audit.py: env knobs,
# ctypes bindings, metrics registry, flight kinds, crash-flush signal path)
# plus the clang thread-safety pass over the annotated concurrency core
# (include/acx/thread_annotations.h). The clang legs detect-and-skip: the
# annotations compile to nothing under gcc, so a gcc-only box still gets
# the full contract audit — just not the capability analysis.
ACX_CLANG ?= $(shell command -v clang++ 2>/dev/null)

.PHONY: lint annotcheck
lint:
	@echo "== acx_audit (contract linter)"
	@python3 tools/acx_audit.py
ifneq ($(ACX_CLANG),)
	@echo "== clang -Wthread-safety ($(ACX_CLANG))"
	@$(ACX_CLANG) -fsyntax-only -std=c++17 -Wall -Wthread-safety \
	  -Werror=thread-safety $(INCLUDES) $(LIB_SRCS) || exit 1
	@$(MAKE) --no-print-directory annotcheck
else
	@echo "== clang -Wthread-safety: SKIPPED (no clang++ on PATH; gcc" \
	  "compiles the annotations to nothing)"
endif
	@echo "LINT CLEAN"

# Probe that the annotation macros actually bite under clang: compiling
# ctests/annot_probe.cc with -DACX_ANNOT_PROBE_BAD (an unguarded write to
# a GUARDED_BY member) must FAIL under -Werror=thread-safety. Guards
# against the macros silently no-op'ing under a future clang/flag change.
annotcheck:
ifneq ($(ACX_CLANG),)
	@echo "== annotcheck: misannotated probe must fail under clang"
	@if $(ACX_CLANG) -fsyntax-only -std=c++17 -Wthread-safety \
	  -Werror=thread-safety -DACX_ANNOT_PROBE_BAD $(INCLUDES) \
	  ctests/annot_probe.cc 2>/dev/null; then \
	  echo "annotcheck: FAIL — ACX_ANNOT_PROBE_BAD compiled clean" \
	    "(thread-safety analysis is not biting)"; exit 1; \
	else echo "annotcheck: OK (probe rejected as expected)"; fi
else
	@echo "== annotcheck: SKIPPED (no clang++ on PATH)"
endif

# --- AddressSanitizer / UBSanitizer builds (mirror the tsan pattern).
# asan: heap/stack/use-after-free over the unit suite + the 2-rank
# integration tests on both planes. detect_leaks=0 because the runtime's
# process-lifetime singletons (metrics State, trace ring, flag table) are
# deliberately immortal — LSAN would report every one.
ASAN_ENV  = ASAN_OPTIONS=halt_on_error=1:detect_leaks=0:abort_on_error=1
UBSAN_ENV = UBSAN_OPTIONS=halt_on_error=1:print_stacktrace=1

.PHONY: asan ubsan asan-ctest
asan:
	@$(MAKE) --no-print-directory BUILD=build-asan \
	  CXXFLAGS="$(CXXFLAGS) -O1 -fsanitize=address -fno-omit-frame-pointer" \
	  LDFLAGS="-pthread -fsanitize=address" \
	  ctest itest tools
	@for t in $(CTEST_BINS:$(BUILD)/%=build-asan/%); do \
	  echo "== asan $$t"; $(ASAN_ENV) $$t || exit 1; done
	@for t in $(ITEST_BINS:$(BUILD)/%=build-asan/%); do \
	  echo "== asan acxrun -np 2 $$t"; \
	  $(ASAN_ENV) build-asan/acxrun -np 2 -timeout 600 $$t || exit 1; done
	@echo "== asan acxrun -np 2 ring (socket)"
	@$(ASAN_ENV) build-asan/acxrun -np 2 -timeout 600 \
	  -transport socket build-asan/itests/ring || exit 1
	@echo "ASAN CLEAN"

ubsan:
	@$(MAKE) --no-print-directory BUILD=build-ubsan \
	  CXXFLAGS="$(CXXFLAGS) -O1 -fsanitize=undefined -fno-sanitize-recover=all" \
	  LDFLAGS="-pthread -fsanitize=undefined" \
	  ctest itest tools
	@for t in $(CTEST_BINS:$(BUILD)/%=build-ubsan/%); do \
	  echo "== ubsan $$t"; $(UBSAN_ENV) $$t || exit 1; done
	@for t in $(ITEST_BINS:$(BUILD)/%=build-ubsan/%); do \
	  echo "== ubsan acxrun -np 2 $$t"; \
	  $(UBSAN_ENV) build-ubsan/acxrun -np 2 -timeout 600 $$t || exit 1; done
	@echo "UBSAN CLEAN"

# The fast asan leg `make check` runs: unit suite + one 2-rank itest per
# plane (the full matrix stays in `make asan`).
asan-ctest:
	@$(MAKE) --no-print-directory BUILD=build-asan \
	  CXXFLAGS="$(CXXFLAGS) -O1 -fsanitize=address -fno-omit-frame-pointer" \
	  LDFLAGS="-pthread -fsanitize=address" \
	  ctest itest tools
	@for t in $(CTEST_BINS:$(BUILD)/%=build-asan/%); do \
	  echo "== asan $$t"; $(ASAN_ENV) $$t || exit 1; done
	@echo "== asan acxrun -np 2 ring (shm)"
	@$(ASAN_ENV) build-asan/acxrun -np 2 -timeout 600 build-asan/itests/ring || exit 1
	@echo "== asan acxrun -np 2 ring (socket)"
	@$(ASAN_ENV) build-asan/acxrun -np 2 -timeout 600 \
	  -transport socket build-asan/itests/ring || exit 1
	@echo "ASAN CTEST LEG CLEAN"
