#!/usr/bin/env bash
# Verification gates.
#
#   scripts/verify.sh          tier-1 gate — the EXACT command from ROADMAP.md,
#                              so builders and reviewers run the same check.
#   scripts/verify.sh faults   resilience fault-matrix stage: runs the
#                              scheduled-fault + crash-point suite under a
#                              FIXED seed set, so resilience regressions are
#                              reproducible across machines.
#   scripts/verify.sh pipeline pipelined-scheduler determinism stage: the
#                              randomized-oracle parity tests with
#                              scan.parallelism forced to 1 and then to 8 —
#                              pipelined output must be bit-identical to the
#                              sequential path at both extremes. Runs with
#                              the native parquet encoder forced, so the
#                              pipelined flush/compaction encode stages are
#                              exercised through paimon_tpu.encode
#                              (conftest asserts encode{files_native} > 0).
#   scripts/verify.sh lanes    key-lane compression parity stage: the
#                              tests/test_lanes.py + merge-kernel suites run
#                              TWICE — PAIMON_TPU_LANE_COMPRESSION forced on,
#                              then forced off — so compressed and legacy
#                              paths both prove bit-identical merge output.
#   scripts/verify.sh mesh     mesh-execution parity stage: the mesh-executor
#                              suite + mesh table ops + the randomized oracle
#                              run TWICE on the forced 8-device virtual CPU
#                              mesh — PAIMON_TPU_MERGE_ENGINE forced mesh,
#                              then forced single — so the mesh-sharded and
#                              single-device execution engines both prove
#                              bit-identical merge output.
#   scripts/verify.sh dicts    compressed-domain merge parity stage: the
#                              tests/test_dict_domain.py suite (which
#                              compares merge.dict-domain on vs off
#                              directly per table) plus the randomized
#                              whole-store oracle run TWICE —
#                              PAIMON_TPU_DICT_DOMAIN forced 1, then 0 —
#                              so dictionary-code and expanded-string
#                              merges both prove bit-identical output.
#   scripts/verify.sh soak     traffic-soak stage: the writer flow-control /
#                              conflict-storm suite plus a bounded (~60 s
#                              total) DETERMINISTIC mini-soak — fixed seed,
#                              3 writers / 2 readers / 5% injected faults —
#                              asserting snapshot-consistent reads (oracle
#                              log), zero failed commits, zero lost or
#                              duplicated rows, zero leaked worker threads
#                              (conftest), and a post-soak orphan sweep
#                              leaving the file set exactly equal to the
#                              reachable closure. Nightly-scale knobs live
#                              in benchmarks/soak_bench.py.
#   scripts/verify.sh proc-soak  process-grain crash-soak stage: the crash-
#                              point / recovery / load-shedding suite, then
#                              a bounded DETERMINISTIC multi-process soak —
#                              fixed seed, 2 writer + 1 reader OS processes
#                              sharing only the warehouse filesystem, four
#                              scripted kill -9 deaths at distinct commit/
#                              flush crash points plus seeded random
#                              SIGKILLs, respawn + journal recovery,
#                              periodic orphan sweeps — asserting >= 3 kills
#                              survived, final scan == journal-oracle fold,
#                              zero lost/duplicated rows, zero read errors,
#                              and a post-sweep file set exactly equal to
#                              the reachable closure. Nightly-scale knobs
#                              live in benchmarks/soak_bench.py --process.
#   scripts/verify.sh join     device-join parity stage: the
#                              tests/test_join.py suite (kernel oracle
#                              parity across skew x null rates x engines x
#                              partitions, the pinned 50%-skew regression,
#                              code-domain joins, SQL JOIN vs pandas,
#                              vectorized lookups) run TWICE —
#                              PAIMON_TPU_LANE_COMPRESSION forced on, then
#                              off — so compressed and legacy key lanes
#                              both prove bit-identical join output; the
#                              second pass also forces the dict-domain
#                              reader on.
#   scripts/verify.sh get      batched point-get parity stage: the
#                              tests/test_point_get.py suite (randomized
#                              get_batch == scalar lookup() == fold parity
#                              across schemas x engines, bloom key-index
#                              pruning, read-your-writes tiers, typed-BUSY
#                              serving, the compaction-chain cancel
#                              regression) run TWICE — PAIMON_TPU_KEY_BLOOM
#                              forced 1, then 0 — so gets prove identical
#                              with and without bloom key indexes on every
#                              written file.
#   scripts/verify.sh subscribe  CDC subscription stage: the subscription
#                              suite (decode-once fan-out, consumer-fix
#                              regression, expiry-pinning e2e, cdc wire
#                              roundtrips over Flight, typed shed + resume)
#                              plus a ~45 s deterministic subscriber soak —
#                              2 writers at 5% faults, 4 subscribers incl.
#                              one deliberately slow (typed shed +
#                              consumer-id resume), 1 subscriber OS process
#                              kill -9'd and respawned — asserting every
#                              subscriber's folded changelog stream ==
#                              pinned-snapshot scan at its checkpoint, 0
#                              lost/duplicated rows, 0 untyped sheds, and
#                              the conftest thread/process-leak checks.
#   scripts/verify.sh cluster  cluster-service stage: the coordinator/worker
#                              suite (epoch fencing, reassigned-exactly-once,
#                              debt-charge release on death, routed gets +
#                              subscriptions, distributed join partitions,
#                              subscription-driven query refresh), then a
#                              ~45 s DETERMINISTIC cluster soak — 2 worker
#                              OS processes x 2 virtual devices each running
#                              merge.engine=mesh over their bucket ranges,
#                              the coordinator as the only committer, the
#                              cluster compaction service draining debt,
#                              scripted kill -9 deaths (one mid-ingest-flush,
#                              one MID-COMPACTION, one between prepare_commit
#                              and the ship RPC) plus seeded random SIGKILLs
#                              — asserting >= 2 kills survived, fold == final
#                              scan, 0 lost/dup rows, 0 leaked files, and
#                              sampled read-amp p99 <= the adaptive ceiling.
#   scripts/verify.sh elastic  elastic-cluster stage: the tests/test_elastic.py
#                              suite (live bucket rescale parity + pinned
#                              readers + data-file cache reuse, join-steal
#                              scale-out, planned retire handoff, hot-bucket
#                              read replicas incl. randomized replica/oracle
#                              consistency and replica-death failover, push
#                              route invalidation), then a ~60 s DETERMINISTIC
#                              elastic soak — 2 workers under continuous
#                              ingest with one scripted live rescale 4->8 at
#                              30% (one worker armed to die with its rewrite
#                              files durable but unshipped), one worker admit
#                              at 50% (join-steal handoff), one planned
#                              retire at 70% — asserting >= 1 kill survived,
#                              0 lost/dup rows, 0 leaked files.
#   scripts/verify.sh encode   native-encoder roundtrip parity stage: the
#                              full test_encode suite (incl. the slow
#                              corpus sweep) with the encoder forced
#                              native — every natively-written file must
#                              read back bit-identically through BOTH the
#                              native decoder and pyarrow.
#   scripts/verify.sh gateway  multi-tenant gateway stage: the gateway
#                              suite (per-tenant admission, typed-shed
#                              canonicalization, hedged reads + loser
#                              cancellation, SLO surface) INCLUDING the
#                              slow-marked ~45 s DETERMINISTIC mixed-kind
#                              storm — 64 closed-loop clients across 4
#                              tenants (one deliberately greedy) against
#                              a 2-worker cluster with one latency-shamed
#                              worker, fixed seed — asserting the greedy
#                              tenant sheds TYPED (retry_after set, 0
#                              untyped sheds), the quiet tenant's latency
#                              stays bounded relative to its solo
#                              baseline, hedges stay within the
#                              max-fraction budget, and every hedge
#                              attempt drains (no orphaned RPC, no
#                              leaked "paimon-gw" thread via conftest).
#   scripts/verify.sh mega     production mega-soak stage: the kill-schedule /
#                              scenario-matrix / chaos-composition suite
#                              (tests/test_mega_soak.py), then a bounded
#                              (~90 s) DETERMINISTIC two-cell mega soak —
#                              flagship (cluster + gateway + branch/tag) and
#                              dict-dynamic (dynamic buckets + consumer
#                              expiry) on one composed chaos store, every
#                              plane (writers, getters, subscribers, SQL,
#                              expiry/sweep churn) live at once, scripted
#                              kill -9 deaths at registered crash points plus
#                              seeded random SIGKILLs — asserting >= 3 kills
#                              across >= 2 process kinds survived, one
#                              consistent:true verdict (0 lost/dup rows, 0
#                              untyped sheds, 0 pinned-read errors, post-
#                              sweep disk set == reachable closure).
#                              Nightly-scale knobs live in
#                              benchmarks/mega_soak_bench.py.
#   scripts/verify.sh sql-cluster  distributed-SQL parity stage: the
#                              tests/test_sql_cluster.py suite (scatter-
#                              gather fragments at 1/2/4 workers vs the
#                              single-process evaluator vs pandas, worker
#                              kill mid-query incl. the slow SIGKILL OS-
#                              process test, typed-BUSY admission) run
#                              TWICE — PAIMON_TPU_SQL_CODE_DOMAIN forced 1
#                              (partials combined as dictionary codes),
#                              then 0 (expanded values on the wire) — so
#                              both combine currencies prove bit-identical
#                              distributed results.
#   scripts/verify.sh sql-shuffle  distributed shuffle-aggregation parity
#                              stage: the tests/test_sql_shuffle.py suite
#                              (value-hash partitioner twins, shuffle
#                              parity at 2/4 workers, range-owner death
#                              mid-query, duplicate-dispatch idempotence,
#                              frag-cache layout-epoch keying incl. a live
#                              8->16 rescale) plus tests/test_sql_cluster.py
#                              run TWICE — PAIMON_TPU_SQL_SHUFFLE forced 1
#                              (every GROUP BY combines via worker↔worker
#                              exchange), then 0 (single-point coordinator
#                              combine) — so both aggregation topologies
#                              prove bit-identical to the single-process
#                              evaluator.
#
# Exits non-zero on test failure/timeout; tier-1 prints DOTS_PASSED=<n>
# (count of passing tests) for trend comparison.
set -o pipefail
cd "$(dirname "$0")/.."

if [ "${1:-}" = "pipeline" ]; then
  # lane compression forced ON: retry/prefetch interactions run through the
  # compressed merge kernels (ISSUE 6)
  for par in 1 8; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_SCAN_PARALLELISM=$par PAIMON_TPU_PARQUET_ENCODER=native \
      PAIMON_TPU_LANE_COMPRESSION=1 \
      timeout -k 10 600 python -m pytest tests/test_pipeline.py tests/test_encode.py -q \
      -k 'parity or fault or flush or pipelined' \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "faults" ]; then
  # mesh engine + code-domain merge forced ON: the fault matrix
  # (transient retries, crash points, torn writes) must stay green through
  # the mesh-sharded executor, its feeder workers and the dictionary-code
  # merge currency
  exec env JAX_PLATFORMS=cpu PAIMON_TPU_FAULT_SEEDS="0 1 2 3 4" PAIMON_TPU_PARQUET_ENCODER=native \
    PAIMON_TPU_LANE_COMPRESSION=1 PAIMON_TPU_MERGE_ENGINE=mesh PAIMON_TPU_DICT_DOMAIN=1 \
    timeout -k 10 600 python -m pytest tests/test_resilience.py tests/test_commit_faults.py \
    tests/test_encode.py::test_native_encoder_under_transient_faults -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "dicts" ]; then
  # parity suite (compares the table option on vs off directly), then the
  # randomized whole-store oracle with the code domain forced on and off
  for dd in 1 0; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_DICT_DOMAIN=$dd \
      timeout -k 10 600 python -m pytest tests/test_dict_domain.py tests/test_randomized_oracle.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "mesh" ]; then
  # parity suites with the merge execution engine forced mesh, then single:
  # both sides of the merge.engine switch must produce bit-identical output
  # (the conftest forces the 8-device virtual CPU mesh)
  # the code domain rides along forced ON (ISSUE 10): mesh-batched merges
  # must stay bit-identical when their lanes are dictionary codes
  for eng in mesh single; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_MERGE_ENGINE=$eng PAIMON_TPU_DICT_DOMAIN=1 \
      timeout -k 10 600 python -m pytest tests/test_mesh_exec.py \
      tests/test_randomized_oracle.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "lanes" ]; then
  # parity suite with compression forced on, then forced off: both sides of
  # the merge.lane-compression switch must produce bit-identical output
  for comp in 1 0; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_LANE_COMPRESSION=$comp \
      timeout -k 10 600 python -m pytest tests/test_lanes.py tests/test_merge_kernel.py \
      tests/test_randomized_oracle.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "soak" ]; then
  # no -m filter: this stage INCLUDES the slow-marked ~45 s stage soak.
  # PAIMON_TPU_SOAK_ADAPTIVE=1: the churn compactor is the LUDA-style
  # adaptive scheduler (ISSUE 11) instead of periodic full compaction
  exec env JAX_PLATFORMS=cpu PAIMON_TPU_SOAK_DURATION=45 PAIMON_TPU_SOAK_SEED=0 \
    PAIMON_TPU_SOAK_ADAPTIVE=1 \
    timeout -k 10 600 python -m pytest tests/test_soak.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "proc-soak" ]; then
  env JAX_PLATFORMS=cpu \
    timeout -k 10 300 python -m pytest tests/test_proc_soak.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  exec env JAX_PLATFORMS=cpu timeout -k 10 240 python -m paimon_tpu.service.proc_soak \
    --duration 45 --writers 2 --readers 1 --seed 0 \
    --scripted-kills "commit:manifests-written:2:kill,commit:snapshot-committed:2:kill,flush:files-written:3:kill,commit:before-manifests:2:kill" \
    --kill-period 9 --sweep-period 12 --min-kills 3
fi

if [ "${1:-}" = "join" ]; then
  # parity suite with lane compression forced on, then off (the kernels'
  # global lane plan is the piece that differs); the compressed pass also
  # forces the code-domain reader so table-level joins run on codes
  env JAX_PLATFORMS=cpu PAIMON_TPU_LANE_COMPRESSION=1 PAIMON_TPU_DICT_DOMAIN=1 \
    timeout -k 10 600 python -m pytest tests/test_join.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  exec env JAX_PLATFORMS=cpu PAIMON_TPU_LANE_COMPRESSION=0 \
    timeout -k 10 600 python -m pytest tests/test_join.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "get" ]; then
  # parity suite with bloom key indexes forced onto every written file,
  # then forced off: batched gets must serve identical rows either way
  # (pruning is an optimization, never a semantic)
  for kb in 1 0; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_KEY_BLOOM=$kb \
      timeout -k 10 600 python -m pytest tests/test_point_get.py tests/test_lookup.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "subscribe" ]; then
  # no -m filter: this stage INCLUDES the slow-marked ~45 s subscriber soak
  # and the subscriber-process kill -9 test
  exec env JAX_PLATFORMS=cpu PAIMON_TPU_SOAK_DURATION=45 PAIMON_TPU_SOAK_SEED=0 \
    timeout -k 10 600 python -m pytest tests/test_subscription.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "cluster" ]; then
  env JAX_PLATFORMS=cpu \
    timeout -k 10 400 python -m pytest tests/test_cluster.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  exec env JAX_PLATFORMS=cpu timeout -k 10 240 python -m paimon_tpu.service.cluster \
    --duration 45 --workers 2 --readers 1 --seed 0 \
    --scripted-kills "flush:files-written:2:kill,cluster:compact-executing:1:kill,cluster:before-ship:2:kill" \
    --kill-period 10 --sweep-period 15 --min-kills 2
fi

if [ "${1:-}" = "elastic" ]; then
  env JAX_PLATFORMS=cpu \
    timeout -k 10 600 python -m pytest tests/test_elastic.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  exec env JAX_PLATFORMS=cpu timeout -k 10 300 python -m paimon_tpu.service.cluster \
    --duration 60 --workers 2 --readers 1 --seed 0 --buckets 4 \
    --scripted-kills "rescale:files-written:1:kill" \
    --kill-period 0 --sweep-period 20 \
    --elastic-script "rescale:8@0.3,admit@0.5,retire@0.7" --min-kills 1
fi

if [ "${1:-}" = "gateway" ]; then
  # no -m filter: this stage INCLUDES the slow-marked ~45 s seeded
  # mixed-kind tenant-isolation storm
  exec env JAX_PLATFORMS=cpu PAIMON_TPU_SOAK_DURATION=45 PAIMON_TPU_SOAK_SEED=0 \
    timeout -k 10 600 python -m pytest tests/test_gateway.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
fi

if [ "${1:-}" = "mega" ]; then
  env JAX_PLATFORMS=cpu \
    timeout -k 10 300 python -m pytest tests/test_mega_soak.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  exec env JAX_PLATFORMS=cpu timeout -k 10 420 python -m paimon_tpu.service.mega_soak \
    --cells flagship,dict-dynamic --duration 25 --workers 2 --seed 0 \
    --kill-period 8 --min-kills 3 --min-kill-kinds 2
fi

if [ "${1:-}" = "sql-cluster" ]; then
  # no -m filter: includes the slow SIGKILL OS-process worker-kill test.
  # Code-domain combine forced on, then off: distributed aggregation must
  # be bit-identical to the single-process evaluator in both currencies
  for cd in 1 0; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_SQL_CODE_DOMAIN=$cd \
      timeout -k 10 600 python -m pytest tests/test_sql_cluster.py tests/test_sql_select.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "sql-shuffle" ]; then
  # shuffle exchange forced on, then off: every grouped query must be
  # bit-identical to the single-process evaluator whether partials combine
  # peer-to-peer at range owners or single-point at the coordinator
  for sh in 1 0; do
    env JAX_PLATFORMS=cpu PAIMON_TPU_SQL_SHUFFLE=$sh \
      timeout -k 10 600 python -m pytest tests/test_sql_shuffle.py tests/test_sql_cluster.py -q \
      -p no:cacheprovider -p no:xdist -p no:randomly || exit $?
  done
  exit 0
fi

if [ "${1:-}" = "encode" ]; then
  exec env JAX_PLATFORMS=cpu PAIMON_TPU_PARQUET_ENCODER=native \
    timeout -k 10 600 python -m pytest tests/test_encode.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
fi

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
