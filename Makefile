# Convenience targets; CI drives the same commands.

PY ?= python

# galaxylint: the repo-specific static-analysis suite (lock-order vs the
# canonical append_lock -> partition -> store/metadb order + blocking ops
# under hot locks, raw-jax.jit / device-sync jit discipline, typed-error
# wire-contract swallows and untyped raises, failpoint/metrics hygiene).
# Exits 0 only with ZERO unsuppressed findings; suppressions live as
# justified `# galaxylint: disable=<rule> -- why` pragmas or justified
# entries in galaxysql_tpu/devtools/baseline.json (stale entries fail).
lint:
	$(PY) -m galaxysql_tpu.devtools.lint

# lint smoke: the lint marker suite — per-rule positive/negative fixtures,
# pragma/baseline round-trips, the whole-tree zero-findings self-run, and
# the runtime lockdep witness incl. the FP_LOCK_INVERT seeded inversion
lint-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m lint -p no:cacheprovider

# full tier-1 gate (ROADMAP.md)
tier1:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# fast fusion smoke: TPC-H Q1/Q3 (+ SSB/TPC-DS fixtures) through BOTH the
# fused and unfused execution paths, asserting identical results — guards the
# pipeline segment fuser without paying for the whole suite
fusion-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m fusion -p no:cacheprovider

# fast observability smoke: EXPLAIN ANALYZE actual-rows vs result
# cardinalities, SHOW FULL STATS / information_schema.metrics round-trips,
# web /metrics + /query/<trace_id>, and the no-profiling hot-path guard
# (zero extra device dispatches vs the PR-1 fused baseline)
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m observability -p no:cacheprovider

# fast runtime-filter smoke: filter value semantics (empty build, NULL keys,
# bloom FP tolerance), planner annotation + hint gating, and result
# equivalence with RUNTIME_FILTER(OFF) on TPC-H Q3/Q5/Q9/Q18 + SSB Q2.1 on
# both the local engine and the 8-device mesh
rf-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m runtime_filter -p no:cacheprovider

# fast fragment-cache smoke: cache-on (warm, second execution) vs
# FRAGMENT_CACHE(OFF) equivalence on TPC-H Q3/Q5/Q9 + SSB Q2.1 on both the
# local engine and the 8-device mesh, plus the invalidation edges (DML/DDL
# version bumps, txn-local writes, flashback, cross-coordinator SyncBus)
cache-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m fragment_cache -p no:cacheprovider

# fast tracing smoke: TPC-H Q5 with tracing on vs off (bit-identical results,
# unchanged dispatch count when off), span-tree shape (operators, fused
# segments, MPP shard subtrees, worker graft), compile events, and a
# well-formed Chrome-trace JSON from /trace/<trace_id>
trace-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m tracing -p no:cacheprovider

# overload smoke: the resource-governance plane under sustained load — the
# workload-class admission gate (AIMD per-class limits, deadline-aware
# shedding, typed ServerOverloadError with retry-after), memory-pressure
# tiers (fragment-cache shrink, CRITICAL AP refusal + largest-query revoke),
# retry budgets + worker slow-drain backpressure piggyback, the CCL SQL
# surface (CREATE/DROP CCL_RULE) and CclManager concurrency stress, and the
# end-to-end proof: TP keeps bounded p99 and nonzero goodput while an AP
# flood sheds typed, with zero hangs and bit-identical admitted results
overload-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m overload -p no:cacheprovider

# the served SQL path on the chip: TPC-H SF1 over the MySQL wire + a TP leg,
# one process, every result checked against a pandas reference.  Fails
# without a TPU (python chip_smoke.py --dry-run-cpu --sf 0.02 debugs on a CPU)
chip-smoke:
	$(PY) chip_smoke.py

# fast batching smoke: the batching marker suite (batched vs sequential
# bit-identical results under 100+ concurrent sessions, poisoned-key error
# isolation, snapshot/txn bypass edges, static-bucket retrace guard)
# (GALAXYSQL_LOCKDEP=1: every concurrency test doubles as a lock-order
# proof — the runtime witness fails loudly on any acquisition-graph cycle)
batch-smoke:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q -m batching -p no:cacheprovider

# DML batching smoke: the dml_batch marker suite (batched vs sequential
# bit-identical table state under 100+ concurrent write sessions, poison-key
# error isolation, own-txn bypass, read-your-writes after async GSI apply,
# replica reply-leg-drop exactly-once, group commit, CDC coalescing +
# replay equivalence, the hatch trio, steady-state retrace/dispatch guards)
# (GALAXYSQL_LOCKDEP=1: the lockdep witness rides every write-path test)
dml-smoke:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q -m dml_batch -p no:cacheprovider

# chaos smoke: the fault-injection suite over a real worker subprocess —
# retry transparency + dedupe-window exactly-once (reply-leg drop), circuit
# breaker open/half-open/closed, MAX_EXECUTION_TIME deadline kills, sync-epoch
# cache healing, XA crash-restart recovery, replica read failover, and the
# fixed-seed fault-schedule matrix driving TPC-H Q5 + concurrent point DML
# (bit-identical-or-typed-error, zero hangs, zero double-applies)
# (GALAXYSQL_LOCKDEP=1: fault-schedule concurrency doubles as a lock proof)
chaos-smoke:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q -m chaos -p no:cacheprovider

# skew smoke: heavy-hitter hybrid joins + salted aggregation vs SKEW(OFF)
# bit-identical across the Zipf theta sweep (8-virtual-device mesh), both
# hybrid orientations, stats-drift deactivation, fragment-cache rekeying on
# hot-key-set change, the hatch trio, and shard-skew observability surfaces
skew-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m skew -p no:cacheprovider

# workload-insight smoke: statement-digest aggregation (exec/error counts,
# windows, digest stability across literals), the event journal, slow-log
# digest linkage, SHOW/information_schema/web/Prometheus surfaces, the
# plan-regression sentinel end-to-end, summary-on-vs-off bit-identical
# results, race-free concurrent aggregation, and the zero-extra-dispatch /
# zero-device-sync hot-path guard
summary-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m summary -p no:cacheprovider

# rebalance smoke: partition-granular elasticity — SPLIT/MERGE/MOVE PARTITION
# end-to-end (bucket-map conversion routing identity, shadow backfill + CDC
# catchup + FastChecker verify + TSO-fenced cutover), crash-resume from every
# checkpoint, verify-mismatch rollback restoring the source byte-identically,
# the open-transaction cutover drain, the heat-driven balancer policy with
# its admission-pressure yield, and the SHOW REBALANCE surfaces
# (GALAXYSQL_LOCKDEP=1: the move path's partition/router lock choreography
# doubles as a lock-order proof)
rebalance-smoke:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q -m rebalance -p no:cacheprovider

# rebalance chaos: crash schedules at EVERY job state transition (task
# boundaries, mid-backfill chunk, mid-catchup page, inside the cutover before
# and after the swap) with DML racing the move and readers watching —
# bit-identical-or-typed-error, zero lost/duplicated acked writes, and
# crash-resume completing from the last checkpoint (or undo restoring the
# source exactly)
chaos-rebalance:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q -m rebalance_chaos -p no:cacheprovider

# kernel smoke: the chip's sort formulations against the CPU's scatter ones
# as relations (NULL keys, empty build, duplicate keys, overflow-ladder
# doubling, both hybrid orientations, TPC-H Q1/Q3/Q5/Q6/Q9), and the
# persistent AOT compile cache (restart round trip with zero steady
# retraces, corrupted entries recompiling, metrics/EXPLAIN surfaces)
kernel-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m kernel -p no:cacheprovider

# self-heal smoke: the quarantine state machine end-to-end — a genuine
# stats-driven join-order regression auto-rolls-back, verifies over
# PLAN_HEAL_VERIFY_EXECS executions, and promotes (bit-identical results,
# one plan_rollback + one plan_promoted per episode); plus stats-drift
# repair, flap damping / HEAL_FAILED park + ANALYZE re-arm, probation
# resuming across a coordinator restart, the ENABLE_PLAN_AUTOHEAL /
# GALAXYSQL_PLAN_AUTOHEAL=0 detect-only hatches, and the surfaces parity
heal-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m selfheal -p no:cacheprovider

# SLO-plane smoke: the slo marker suite — deterministic burn/recover under
# an injected latency failpoint (fast+slow window burn, slo_burn/critical,
# /health degraded, recovery re-arm), compile-retrace anomaly detection,
# CREATE/DROP SLO restart persistence, the SHOW/info-schema/web surfaces,
# and the zero-dispatch / zero-transfer sampler guards
slo-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m slo -p no:cacheprovider

# incident flight-recorder smoke: the incident marker suite — tail-sampled
# trace retention (slow/shed/error tails kept at sample_rate=0, phase
# breakdown on every root span), the injected-burn end-to-end (one bundle,
# implicated digest, retained trace + metric window + admission state),
# cooldown dedupe, SHOW INCIDENTS / info-schema / web surfaces, the
# router-hop trace graft over a real subprocess peer, and the hot-path
# guard (unchanged dispatch counts, zero steady retraces, sampling on)
incident-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m incident -p no:cacheprovider

# serving-tier smoke: the router marker suite — consistent-hash affinity,
# session pinning + typed-once failover, cluster-wide admission gossip,
# placement-driven locality, SHOW COORDINATORS / SHOW CLUSTER surfaces,
# the hatch trio, and the coordinator-kill chaos test over real
# subprocesses.  Lockdep-armed: router/gossip paths hold instance locks.
scaleout-smoke:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q \
		-m router -p no:cacheprovider

# columnar HTAP replica: CDC-tailed delta+base tier bit-identical to the
# row store at arbitrary watermarks, crash-resume, compaction vs racing
# writes, DDL-mid-tail reseed, routing gates + hatch trio, SHOW/info-schema
# surfaces.  Lockdep-armed: the tailer holds the columnar lock over
# partition snapshots and metadb persistence.
columnar-smoke:
	JAX_PLATFORMS=cpu GALAXYSQL_LOCKDEP=1 $(PY) -m pytest tests/ -q \
		-m columnar -p no:cacheprovider

.PHONY: tier1 fusion-smoke obs-smoke rf-smoke cache-smoke trace-smoke \
	batch-smoke chaos-smoke skew-smoke summary-smoke heal-smoke overload-smoke \
	dml-smoke lint lint-smoke rebalance-smoke chaos-rebalance kernel-smoke \
	slo-smoke scaleout-smoke columnar-smoke incident-smoke chip-smoke
