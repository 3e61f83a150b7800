PYTHON ?= python
JAX_ENV := env JAX_PLATFORMS=cpu

.PHONY: test selfmon-check cluster-check steps-check chaos-check ha-check \
	query-check ingest-check storage-check compaction-check readtier-check \
	trace-check overload-check live-check scrub-check bench native

test:
	timeout -k 10 870 $(JAX_ENV) $(PYTHON) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider -p no:xdist \
		-p no:randomly

# Brief e2e run of the real agent+server pipeline; exits non-zero if any
# hop's frame ledger fails to balance or any stage reports no heartbeat.
selfmon-check:
	timeout -k 10 120 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.selfmon_check

# Brief e2e run of a 3-shard cluster + agent fleet; exits non-zero if the
# federated count diverges from the union of shard counts or any
# cluster.* fan-out hop's frame ledger fails to balance.
cluster-check:
	timeout -k 10 120 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.cluster_check

# Disaggregated read tier: 1 ingest shard + 4 stateless querier
# subprocesses over a shared object store; exits non-zero if any
# replica's answer differs from the ingest node's, the distributed
# partial-aggregate cache rescans a warm bucket or its ledgers don't
# conserve, read throughput fails to scale (multi-core hosts), or the
# ingest write p99 moves under the query storm.
readtier-check:
	timeout -k 10 300 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.readtier_check

# Kill-and-recover run of the durable transport under seeded fault
# injection (conn resets + partial writes + a mid-stream server
# restart); exits non-zero unless every high-priority frame lands in
# the store exactly once and all hop ledgers balance.
chaos-check:
	timeout -k 10 120 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.chaos_check

# Replicated-ingest failover run: 3 subprocess shards at R=2, a sender
# fleet shipping to consistent-hash ring owners, one shard SIGKILLed
# mid-stream; exits non-zero unless federated queries stay EXACT (no
# missing shards, count equals frames sent) with zero HIGH loss.
ha-check:
	timeout -k 10 300 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.ha_check

# Brief e2e run of the step-health pipeline: synthetic 4-device pod with
# one injected 2x-slow device; exits non-zero unless the regression
# detector fires once and names that device and its dominant HLO.
steps-check:
	timeout -k 10 120 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.steps_check

# Golden parity of the three query paths (legacy / numpy / native) on a
# seeded corpus, federated merge-equivalence vs a single node, and a
# warm/cold cache latency report; exits non-zero on any divergence.
query-check:
	timeout -k 10 120 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.query_check

# Live-observability gate: standing queries under a 1M-row window must
# refresh incrementally >=10x faster than from-scratch and byte-identical
# to it (and to the DF_STANDING=0 kill-switch), 3 concurrent subscribers
# each see every generation exactly once, a breached alert fires via push
# within 2s, a 3-shard federated delta recomputes only the changed shard,
# and the query.standing / exporter.<kind> hop ledgers conserve.
live-check:
	timeout -k 10 600 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.live_check

# Native ingest throughput gate: same L4 frames through the native
# columnar path and the DF_NO_NATIVE pb fallback; exits non-zero unless
# native sustains >= 2.5x the fallback's rows/s (relative gate — a slow
# CI host can't fail a fast code path) with zero drops on both arms.
ingest-check:
	timeout -k 10 300 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.ingest_check

# Durable-write SIGKILL gate for the tiered store: a subprocess server
# with --storage is killed mid-stream; exits non-zero unless every
# pre-kill ACKED frame survives the crash from on-disk segments and all
# frames land exactly once after a restart on the same data_dir, then
# a TTL sweep must evict the aged segments with every dropped row
# ledgered under segment_evict (drops observed, never silent).
storage-check:
	timeout -k 10 300 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.storage_check

# Segment-format-v2 compaction gate: 200 small format-v1 segments are
# compacted into sorted runs; exits non-zero unless answers stay
# byte-identical, selective needle scans get >= 3x faster with bloom
# indexes demonstrably pruning runs, no v1 segment survives, the
# query.scan hop ledger balances, and crash-injected compactions
# (killed after staging AND after the manifest commit) both recover
# exactly and converge to v2 on the next cycle.
compaction-check:
	timeout -k 10 600 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.compaction_check

# Dogfooded query tracing gate: a federated 3-shard query must stitch
# into exactly one trace readable through the system's own Tempo API
# (coordinator + every shard exec + prune decisions, shard spans
# parented under their own shard.call), with byte-identical results
# tracing on/off, EXPLAIN ANALYZE stage sums within 20% of e2e, and a
# conserved query.trace hop ledger on every node.
trace-check:
	timeout -k 10 120 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.trace_check

# Closed-loop QoS gate: 3 tenants offer 10x their bulk quota through
# the real server; exits non-zero on any HIGH-class loss, a tenant
# starved or outside 2x of its weighted share, unbounded ingest p99,
# an unattributed drop, an unbalanced hop ledger, or a pressure spike
# that fails to raise-then-decay the advertised level.
overload-check:
	timeout -k 10 300 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.overload_check

# Self-healing storage gate: a 3-shard federated cluster under
# sustained ingest takes bit-flips into sealed segments, a corrupted
# object-store blob, and ENOSPC into one shard's flush path; exits
# non-zero unless every corruption is detected by the checksum scrub,
# quarantined through the manifest, and repaired from the healthy
# copy (queries annotated degraded in the gap, byte-identical to the
# expected aggregates after), acks HOLD through the full disk with
# zero HIGH loss after recovery, and every hop ledger conserves.
scrub-check:
	timeout -k 10 600 $(JAX_ENV) $(PYTHON) -m deepflow_tpu.cli.scrub_check

bench:
	$(PYTHON) bench.py

# Build every native library, then fail loudly if the freshly-built
# libdfnative.so does not load at the ABI the python bindings expect —
# a stale .so must break the build here, not silently fall back at
# runtime.
native:
	$(MAKE) -C deepflow_tpu/native
	$(PYTHON) -m deepflow_tpu.native --verify-abi
