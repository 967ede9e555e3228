# Developer entrypoints (ref: the reference repo's Makefile targets).

PYTHON ?= python

.PHONY: test test_slow test_sanitizers bench chip-smoke bench_fastsync \
        planner-bench pallas-bench bench_secp bench_multisig mempool-bench \
        lite-bench multichip-bench vote-bench metrics-lint bench-check \
        statesync-smoke \
        flight-smoke chaos-smoke critpath-smoke critpath-bench \
        quorum-smoke soak-smoke \
        localnet-start localnet-stop build-docker-localnode

test:
	$(PYTHON) -m pytest tests/ -q

# interpret-mode pallas ladders + full fuzz sweeps (~30 min)
test_slow:
	TM_RUN_SLOW=1 $(PYTHON) -m pytest tests/ -q

# ASAN/UBSAN native builds + checkify kernel sweep (role of `make test_race`)
test_sanitizers:
	$(PYTHON) -m pytest tests/test_sanitizers.py -q

bench:
	$(PYTHON) bench.py

# the main path on one TPU chip: commit verify, fast sync, a live CLI node
# and the other selectable kernels, each checked against the host oracle.
# Fails at once without a TPU (run it through the chip tool).
chip-smoke:
	$(PYTHON) chip_smoke.py

bench_fastsync:
	$(PYTHON) scripts/bench_fastsync.py 2048 64 512

# verification-planner occupancy/throughput on the ragged valset workload
planner-bench:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_fastsync.py --ragged-valsets

# batched-verify throughput; appends a round under build/pallas_bench and
# gates ed25519_sigs_per_s (higher-is-better) plus the per-window ladder
# slope (lower-is-better — the carry-schedule regression gate) against the
# previous round.  Uses the Pallas kernel on a TPU; JAX_PLATFORMS=cpu asks
# for the XLA kernel on the CPU instead (the round names backend and
# platform), and with neither the script exits non-zero.  The run also
# measures the one-MSM-per-window RLC path against the ladder at n=512
# (ops/ed25519_msm) and gates its throughput, ed25519_msm_sigs_per_s, the
# same way.
pallas-bench:
	$(PYTHON) scripts/profile_pallas.py --ed25519-path msm \
	  --round-dir build/pallas_bench \
	  --metrics-out build/pallas_bench/verify_metrics.prom $(ARGS)
	$(PYTHON) scripts/bench_check.py --dir build/pallas_bench \
	  --metric "ed25519_sigs_per_s:0.25:higher" \
	  --metric "pallas_ladder_window_slope:0.25:lower" \
	  --metric "ed25519_msm_sigs_per_s:0.25:higher"

bench_secp:
	$(PYTHON) scripts/bench_secp.py 1024

bench_multisig:
	$(PYTHON) scripts/bench_multisig.py 1000 3 5

# mempool ingestion: serial vs micro-batched CheckTx, QoS decision rate,
# recheck throughput (headline mempool_checktx_per_s), then the signed-tx
# workload: app-serial ed25519 verify vs TxFeed planner dispatch with
# in-bench admit/reject bit-parity + >=3x floor; appends a MEMPOOL_rNN.json
# round and gates mempool_signed_checktx_per_s against the previous one
mempool-bench:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_mempool.py $(ARGS)
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_mempool.py --signed
	$(PYTHON) scripts/bench_check.py --prefix MEMPOOL \
	  --metric mempool_signed_checktx_per_s:0.25:higher

# multi-client light-client frontend vs per-client serial verification
lite-bench:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_lite.py $(ARGS)

# multi-window mesh superdispatch scaling 1 -> 8 forced-CPU devices; appends
# a MULTICHIP_rNN.json round then gates planner_windows_per_s against the
# previous parsed round
multichip-bench:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_multichip.py $(ARGS)
	$(PYTHON) scripts/bench_check.py --prefix MULTICHIP \
	  --metric planner_windows_per_s:0.25:higher

# live-vote micro-batcher: seeded vote storm through VoteSet.prevalidate +
# VoteFeed vs the serial add_vote loop, bit-parity asserted; headline
# metric is vote_verify_per_s (batched, 256 validators)
vote-bench:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_votes.py $(ARGS)
	$(PYTHON) scripts/bench_check.py --prefix VOTES \
	  --metric vote_verify_per_s:0.25:higher

# strict text-format v0.0.4 self-check of Registry.expose_text(); pass files
# to lint scrape snapshots: make metrics-lint ARGS="/tmp/m.prom"
metrics-lint:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/metrics_lint.py $(ARGS)

# fail on >20% fastsync_blocks_per_s regression between the two newest
# BENCH_r*.json rounds that parsed
bench-check:
	$(PYTHON) scripts/bench_check.py $(ARGS)

# in-process snapshot restore (producer -> chunk fetch -> light-client verify
# -> batched backfill) + linted tendermint_statesync_* scrape
statesync-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/statesync_smoke.py

# 4-node in-proc net with flight recorders on: forced >1/3 stall must trip
# the liveness watchdog, and the merged per-node dump must validate as
# Chrome trace-event JSON with agreeing commit anchors
flight-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/flight_smoke.py

# commit-latency waterfall end to end on the flight smoke's 4-node net:
# per-height phase sums must reconcile with wall height time, the
# height_phase_seconds exposition must lint with every phase label, and
# the merged trace must carry strictly nested waterfall slices
critpath-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/critpath_smoke.py

# quorum observatory end to end on the sim fabric: 4 validators (one
# silenced) with vote batching on; per-validator journeys must reconcile
# exactly with receiver first-sighting records after skew correction, the
# gossip waste ratio must be finite-positive, the merged trace must carry
# paired signer->receiver flow arrows, and the appended QUORUM_rNN.json
# round gates quorum_time_to_two_thirds_p99_seconds (lower is better)
quorum-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/quorum_smoke.py
	$(PYTHON) scripts/bench_check.py --prefix QUORUM \
	  --metric quorum_time_to_two_thirds_p99_seconds:0.25:lower

# soak observatory end to end on the sim fabric: 4 validators past 200
# heights through a mid-run fault leg, one node crashed (torn spool frame
# included) and rebuilt; whole-run sketch quantiles must match exact
# offline percentiles within the configured relative error, the fleet
# merge must be bucket-identical to merging per-node sketches, pre-crash
# spool legs must survive the rebuild, and the appended SOAK_rNN.json
# round gates soak_commit_p99_seconds (lower is better)
soak-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/soak_smoke.py
	$(PYTHON) scripts/bench_check.py --prefix SOAK \
	  --metric soak_commit_p99_seconds:0.25:lower

# signing-to-commit p99 under vote_storm + mempool_flood on the sim
# fabric, pooled from every node's critical-path waterfalls; appends a
# CRITPATH_rNN.json round then gates commit_p99_seconds (latency: lower
# is better) against the previous round
critpath-bench:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/bench_commit_path.py $(ARGS)
	$(PYTHON) scripts/bench_check.py --prefix CRITPATH \
	  --metric commit_p99_seconds:0.25:lower

# deterministic chaos/Byzantine scenario matrix over the in-proc sim fabric:
# safety + liveness + seeded-fault replayability per scenario, run-to-run
# commit-hash determinism, merged Chrome trace emitted on any failure
chaos-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_smoke.py

build-docker-localnode:
	docker build -t tendermint_tpu/localnode networks/local/localnode

# Run a 4-node testnet locally (ref Makefile:296)
localnet-start: localnet-stop build-docker-localnode
	@if ! [ -f build/node0/config/genesis.json ]; then \
	  $(PYTHON) -m tendermint_tpu.cmd.tendermint testnet --v 4 \
	    --output-dir ./build --starting-ip-address 192.168.10.2 ; fi
	docker-compose up

localnet-stop:
	docker-compose down
