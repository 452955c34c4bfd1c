#!/usr/bin/env bash
# Tier-1 verify — the ROADMAP.md command, encapsulated.
#
#   scripts/run_tier1.sh            # full tier-1 pytest run (870s budget)
#   scripts/run_tier1.sh --smoke    # fast pre-flight: schema validators
#                                   # + a 3-step traced bench.py --trace run
#                                   # + the DDP overlap audit (8-device
#                                   #   CPU variant of pod_comm_budget,
#                                   #   incl. the hierarchical-schedule
#                                   #   gate: one-member-per-slice DCN
#                                   #   groups, per-hop dtype split,
#                                   #   APX203 ABSENT + the flat
#                                   #   negative twin still firing)
#                                   # + the memory-budget audit (--cpu8)
#                                   # + the ckpt save->kill->elastic-
#                                   #   restore roundtrip (--cpu8)
#                                   # + the guard chaos audit (--cpu8):
#                                   #   clean-run zero interventions,
#                                   #   NaN-spike rewind bitwise vs a
#                                   #   fault-free oracle, skip-class
#                                   #   convergence, guard schema
#                                   # + the integrity audit (--cpu8):
#                                   #   silent mantissa-bitflip caught
#                                   #   by cross-replica fingerprints,
#                                   #   minority named by quorum vote,
#                                   #   repaired in place bitwise vs
#                                   #   oracle; no-majority falls
#                                   #   through to coordinated rewind;
#                                   #   EF-int8 hierarchical sync
#                                   #   fingerprint-clean
#                                   # + the cluster control-plane audit
#                                   #   (--cpu8): zombie write/delete
#                                   #   fenced after a generation bump,
#                                   #   coordinated cross-rank rewind
#                                   #   bitwise vs oracle with exactly
#                                   #   one bump, split-brain intent +
#                                   #   CAS refused, hung collective
#                                   #   named, cluster schema
#                                   # + apexlint on the flagship steps
#                                   #   incl. the guarded/ckpt
#                                   #   self-audit targets (asserts
#                                   #   zero error findings)
#                                   # + the cross-rank SPMD congruence
#                                   #   audit (--mesh dp2x4 on the
#                                   #   cpu8 mesh, --fail-on error)
#                                   # + the link probe (--cpu8): sweep
#                                   #   collectives per mesh axis, fit
#                                   #   alpha-beta, emit a MEASURED
#                                   #   MeshModel JSON
#                                   # + the goodput audit (--cpu8):
#                                   #   per-step bucket attribution
#                                   #   closes over wall time within
#                                   #   5%, a seeded synthetic slow
#                                   #   rank is named with its slowest
#                                   #   span class, and the measured
#                                   #   model round-trips through
#                                   #   apexlint --mesh with APX203 hop
#                                   #   evidence from the measured
#                                   #   bytes/s
#                                   # + the pod observatory audit
#                                   #   (--cpu8): cross-rank timeline
#                                   #   merge recovers injected clock
#                                   #   offsets, collective skew blamed
#                                   #   on the seeded (rank, span),
#                                   #   goodput comm_skew/comm_wire
#                                   #   split still closes, 4-process
#                                   #   merge on real clocks, measured
#                                   #   hop wire time vs plan within
#                                   #   the stated band + staled-model
#                                   #   negative twin, podview schema
#                                   #   incl. the committed fixture
#                                   # + the numerics observatory audit
#                                   #   (--cpu8): per-tensor dynamic-
#                                   #   range fold zero-dispatch on the
#                                   #   BERT step, e4m3-boundary tensor
#                                   #   flagged at the right site with
#                                   #   a scale that fixes it,
#                                   #   ScaleHistory bitwise vs oracle,
#                                   #   numerics schema
#                                   # + the roofline observatory audit
#                                   #   (--cpu8): per-op attribution
#                                   #   closure on the committed BERT
#                                   #   fixture, the known fused-
#                                   #   backward gap named, AOT-only
#                                   #   path, sentinel seeded positive
#                                   #   + negative twin
#                                   # + the mesh pre-flight explainer
#                                   #   (--cpu8): per-axis HBM closure
#                                   #   + ZeRO ~1/N declared shards,
#                                   #   wire pricing vs the alpha-beta
#                                   #   plan within band, flat ranked
#                                   #   below hierarchical with APX203
#                                   #   attached, sharding schema
#                                   # + the kernel autotuner audit
#                                   #   (--cpu8 --interpret): block-
#                                   #   shape sweep accounted compile-
#                                   #   exact under autotune_scope,
#                                   #   DB round-trip + loud stale
#                                   #   refusal, committed DB exact-key
#                                   #   hits on every family, zero
#                                   #   steady-state autotune compiles,
#                                   #   tune_report covers the fused-
#                                   #   backward roofline candidate
#
# Exit status is pytest's (or the first failing smoke step). The full
# run prints DOTS_PASSED=<n> — the count of passing-test dots the driver
# tracks — whether or not the run hit the timeout.

set -u -o pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${TIER1_TIMEOUT:-870}"
LOG="${TIER1_LOG:-/tmp/_t1.log}"

if [[ "${1:-}" == "--smoke" ]]; then
    set -e
    tmp="$(mktemp -d)"
    trap 'rm -rf "$tmp"' EXIT

    echo "== smoke: metrics schema validator (self-test stream)"
    JAX_PLATFORMS=cpu python - "$tmp/metrics.jsonl" <<'EOF'
import sys
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from apex_tpu import monitor
logger = monitor.MetricsLogger(
    sinks=[monitor.JSONLSink(sys.argv[1])], flush_every=2)
m = monitor.metrics_init()
for i in range(4):
    m = m.count_step(jnp.bool_(True)).record_loss(float(i))
    logger.record(m)
logger.close()
EOF
    python scripts/check_metrics_schema.py --kind metrics "$tmp/metrics.jsonl"

    echo "== smoke: 3-step traced bench (bench.py --trace)"
    # run inside $tmp so TRACE*.json(l) artifacts never land in the tree
    repo="$(pwd)"
    (cd "$tmp" && JAX_PLATFORMS=cpu python "$repo/bench.py" --trace)

    echo "== smoke: trace schema validator on the bench event stream"
    python scripts/check_metrics_schema.py --kind trace \
        "$tmp/TRACE_EVENTS.jsonl"

    echo "== smoke: Chrome trace is valid JSON with traceEvents"
    python - "$tmp/TRACE.json" <<'EOF'
import json, sys
ct = json.load(open(sys.argv[1]))
assert isinstance(ct.get("traceEvents"), list) and ct["traceEvents"], \
    "TRACE.json has no traceEvents"
EOF

    echo "== smoke: DDP overlap audit (8-device CPU variant)"
    # includes the hierarchical compressed-sync gate: the factored
    # (2-slice x 4) mesh compiles int8 ICI reduce-scatter ->
    # one-member-per-slice DCN reduce -> ICI all-gather, APX203 stays
    # ABSENT on that module (exit 1 on reappearance), and the flat
    # negative twin still fires it — the ROADMAP item-2 done-state.
    JAX_PLATFORMS=cpu python scripts/pod_comm_budget.py --cpu8

    echo "== smoke: memory-budget audit (8-device CPU variant)"
    # asserts: (a) class attribution == memory_analysis within 1%,
    # (b) ZeRO optimizer state ~1/N vs replicated, (c) compile_watch
    # 1 steady-state compile + named changed arg on a forced retrace
    JAX_PLATFORMS=cpu python scripts/memory_budget.py --cpu8

    echo "== smoke: checkpoint save->kill->elastic-restore roundtrip"
    # asserts: (a) SIGKILL mid-save (both crash points) leaves the
    # previous committed checkpoint as latest + hash-verified loadable,
    # (b) ZeRO run saved on the 8-mesh resumes on a 4-mesh bitwise vs
    # an uninterrupted 4-mesh run, (c) async capture stall bounded by
    # the full save, (d) the ckpt event stream passes --kind ckpt
    JAX_PLATFORMS=cpu python scripts/ckpt_roundtrip.py --cpu8

    echo "== smoke: guard chaos audit (8-device CPU mesh)"
    # asserts: (a) a fault-free guarded run triggers ZERO guard events
    # and compiles bit-identical HLO under observation, (b) an injected
    # param-NaN spike rewinds (rejecting the corrupted newer ckpt) and
    # its post-rewind losses + final params bitwise-match an oracle
    # that never saw the poison window, (c) grad-NaN/Inf + corrupt-
    # batch faults are skipped in-graph and still converge, (d) the
    # guard event stream passes --kind guard
    JAX_PLATFORMS=cpu python scripts/chaos_audit.py --cpu8

    echo "== smoke: integrity silent-divergence audit (8-device CPU mesh)"
    # asserts: (a) a fault-free fingerprinted run logs ZERO integrity
    # events with bit-identical HLO under host polling, (b) a seeded
    # FINITE mantissa bitflip on replica 1 (silent to the NaN/spike
    # probes) is detected within check_every steps, the minority named
    # by quorum vote, and repaired IN PLACE (no rewind, cursor
    # untouched) bitwise vs a fault-free oracle, (c) a 2-of-2
    # no-majority divergence falls through to the coordinated-rewind
    # path with exactly one generation bump, (d) the EF-int8
    # hierarchical sync runs fingerprint-clean (the collectives-v2
    # runtime proof), (e) every stream passes --kind integrity
    JAX_PLATFORMS=cpu python scripts/integrity_audit.py --cpu8

    echo "== smoke: cluster control-plane audit (8-device CPU mesh)"
    # asserts: (a) a rank paused through an escalation + relaunch has
    # its late checkpoint write AND retention delete refused by the
    # generation fence (latest_checkpoint untouched), (b) rank-
    # asymmetric param corruption resolves to ONE agreed rewind target
    # (oldest good step wins) with exactly one generation bump and
    # post-rewind losses + params bitwise vs a fault-free oracle on
    # both ranks, (c) a split-brain generation claim is refused at
    # intent verification and at the CAS bump, (d) a hung collective
    # is named + escalated and every stream passes --kind cluster
    JAX_PLATFORMS=cpu python scripts/cluster_audit.py --cpu8

    echo "== smoke: apexlint flagship steps (--fail-on error)"
    # lints the flagship ResNet-O2 and BERT-LAMB steps (CPU structural
    # downscalings) PLUS the guard-instrumented step, the ckpt
    # snapshot copy program and the dynamics-instrumented step (the
    # self-audit targets) against the committed baseline — which
    # starts EMPTY, so any new error-severity finding (donation miss,
    # host transfer, f64 creep, RNG reuse, non-replayable randomness,
    # unscaled narrow cast, scale leak) breaks this gate
    JAX_PLATFORMS=cpu python scripts/apexlint.py --flagship all \
        --baseline scripts/apexlint_baseline.json --fail-on error \
        --jsonl "$tmp/lint.jsonl"

    echo "== smoke: lint schema validator on the apexlint event stream"
    python scripts/check_metrics_schema.py --kind lint "$tmp/lint.jsonl"

    echo "== smoke: apexlint precision certification sweep (O0-O3)"
    # the precision pass (APX3xx, docs/linting.md#apx3xx) over both
    # flagships REBUILT at every amp opt level: the amp machinery's
    # scale/unscale/cast structure must certify statically at each
    # level — an unscaled narrow cast, a scale leaking past the
    # unscale, or a master-weight violation is an error against the
    # same empty baseline
    JAX_PLATFORMS=cpu python scripts/apexlint.py --flagship both \
        --opt-level all --baseline scripts/apexlint_baseline.json \
        --fail-on error --jsonl "$tmp/lint_precision.jsonl"

    echo "== smoke: lint schema validator on the precision stream"
    python scripts/check_metrics_schema.py --kind lint \
        "$tmp/lint_precision.jsonl"

    echo "== smoke: apexlint cross-rank congruence audit (cpu8, dp2x4)"
    # the SPMD pass over the DDP flagship steps compiled on the
    # FACTORED 2-slice x 4-chip mesh with the hierarchical comm_plan
    # (collectives v2): asserts zero APX201 deadlock/divergence and
    # zero error-severity findings. APX203-clean is now the EXPECTED
    # flagship state (docs/linting.md) — the flat negative twin that
    # proves the rule still fires lives in pod_comm_budget --cpu8 and
    # tests/test_pod_hlo.py.
    JAX_PLATFORMS=cpu python scripts/apexlint.py --flagship both \
        --mesh dp2x4 --baseline scripts/apexlint_baseline.json \
        --fail-on error --jsonl "$tmp/lint_mesh.jsonl"

    echo "== smoke: lint schema validator on the cross-rank stream"
    python scripts/check_metrics_schema.py --kind lint \
        "$tmp/lint_mesh.jsonl"

    echo "== smoke: link probe (8-device CPU mesh, measured MeshModel)"
    # sweeps all-reduce/reduce-scatter/all-gather per mesh axis, fits
    # alpha-beta, and emits a MeshModel JSON with MEASURED
    # link_bytes_per_s + calibration provenance; the emitted stream
    # validates under --kind goodput and the artifact self-checks its
    # round-trip through parse_mesh_spec
    JAX_PLATFORMS=cpu python scripts/link_probe.py --cpu8 \
        --out "$tmp/mesh_measured.json" --jsonl "$tmp/linkfit.jsonl"
    python scripts/check_metrics_schema.py --kind goodput \
        "$tmp/linkfit.jsonl"

    echo "== smoke: goodput attribution + straggler + calibration audit"
    # asserts: (a) the goodput ledger's bucket sum closes over each
    # step's measured wall time within 5% (recompile bucket present on
    # step 0 only; injected input-wait and joined ckpt stall land in
    # their buckets), (b) a seeded synthetic slow rank is flagged with
    # hysteresis and named with its slowest span class, feeding the
    # watchdog's early-warning tier, (c) link_probe's measured
    # MeshModel round-trips through apexlint --mesh with APX203 hop
    # milliseconds computed from the MEASURED bytes/s, (d) every
    # stream passes --kind goodput
    JAX_PLATFORMS=cpu python scripts/goodput_audit.py --cpu8

    echo "== smoke: pod observatory audit (--cpu8)"
    # asserts: (a) the synthetic 4-rank merge recovers injected clock
    # offsets to sub-us residual and blames EVERY collective on the
    # seeded (rank 2, data/load) with the exact skew/wire split, the
    # critical path chains wait->wire, and the podview stream + the
    # committed fixture validate under --kind podview, (b) a
    # pod-measured skew joins OUT of comm_wire into comm_skew with the
    # bucket closure intact (oversized claims clamped), (c) 4 real
    # processes with unrelated perf_counter origins merge through
    # barrier-released collective spans and blame the seeded slow
    # rank, (d) measured per-hop wire time agrees with plan_comm's
    # hop_seconds within the stated band on the calibrated dp2x4 mesh
    # AND the deliberately staled model fires the drift flag with
    # link_probe advice
    JAX_PLATFORMS=cpu python scripts/pod_audit.py --cpu8

    echo "== smoke: numerics observatory audit (--cpu8)"
    # asserts: (a) the instrumented structural BERT step (numerics
    # fold + grad-site ScaleHistory through Amp.step) emits ZERO
    # surprise verdicts with compiled HLO bit-identical under per-step
    # host polling and no host ops, (b) a seeded tensor straddling the
    # e4m3 underflow boundary is flagged at the correct site with a
    # verdict naming the minimum safe format and a recommended_scale
    # that, applied, drives the measured underflow below threshold,
    # (c) ScaleHistory tracks a synthetic amax ramp matching a
    # pure-numpy oracle bitwise through grow/shrink/backoff, (d) the
    # stream passes --kind numerics with all three kinds present
    JAX_PLATFORMS=cpu python scripts/numerics_audit.py --cpu8

    echo "== smoke: training-dynamics observatory audit (--cpu8)"
    # asserts: (a) the GNS/B_crit estimator recovers a KNOWN injected
    # gradient noise scale within 25% through the real pipeline
    # (8-replica shard_map, the registered ddp/dynamics_* collectives,
    # the EMA fold), with the G2/S intermediates matching their
    # analytic values, (b) bit-replicated gradients measure cosine and
    # Adasum projection = 1 while a seeded-decorrelation twin drops to
    # the analytic ~1/sqrt(world) cosine regime, (c) the
    # noise-calibrated convergence comparator flags a too-high-LR
    # trajectory at the seeded divergence step under a band calibrated
    # from paired-seed runs AND stays quiet on a paired-seed twin,
    # (d) Amp.step(dynamics=...) leaves losses and params bitwise
    # identical observed-vs-not at O0-O3, (e) the stream passes
    # --kind dynamics with all three kinds and the
    # dynamics/no-extra-dispatch compile-check case is green
    JAX_PLATFORMS=cpu python scripts/dynamics_audit.py --cpu8

    echo "== smoke: roofline observatory audit (--cpu8)"
    # asserts: (a) the per-op roofline join over the committed
    # BERT-layer fixture closes over the trace's module device time
    # within 5%, classifies attention compute-bound / LayerNorm
    # memory-bound, and worst_gaps names the PERF.md round-5 fused-
    # backward attention gap (~549 us measured vs its ~436 us d=64 MXU
    # floor), (b) an AOT-only report carries measured_us=null analytic
    # rows with dot FLOPs folded into calling fusions, (c) the
    # sentinel flags a seeded 45% MFU drop on the committed r01–r05
    # trajectory AND passes clean on the unmodified trajectory (the
    # negative twin), (d) every stream passes --kind roofline
    JAX_PLATFORMS=cpu python scripts/roofline_audit.py --cpu8

    echo "== smoke: mesh pre-flight explainer (--cpu8)"
    # asserts: (a) per-axis HBM closes over the memory report's class
    # totals and the ZeRO candidate's declared opt-state shards show
    # the ~1/N local/global ratio, (b) per-axis wire pricing agrees
    # with the alpha-beta comm plan within the stated band on both
    # hops, (c) the flat candidate is ranked below the hierarchical
    # one WITH an APX203 verdict attached while the hierarchical one
    # is clean, (d) the emitted stream passes --kind sharding
    JAX_PLATFORMS=cpu python scripts/mesh_explain.py --cpu8

    echo "== smoke: kernel autotuner audit (sweep -> DB -> dispatch, --cpu8)"
    # asserts: (a) the interpret-mode block-shape sweep over all five
    # kernel families accounts for EXACTLY its candidate count in
    # compile_watch's autotune_scope and shows a measurable best-vs-
    # worst spread on >=1 family, while a steady-state tuned dispatch
    # re-jit adds ZERO autotune compiles and records an exact-key DB
    # hit, (b) the tuning DB round-trips save->load->exact-key-hit,
    # nearest-miss shapes return None (defaults), and a seeded stale
    # entry is refused LOUDLY naming its fingerprint, (c) the committed
    # scripts/kernel_tuning_db.json loads with a winner for every
    # family and 5/5 exact-key hits on the sweep shapes, (d)
    # tune_report joins the DB against the roofline fixture's
    # worst_gaps — the PERF.md fused-backward attention candidate
    # (~549 us vs ~436 us) shows as COVERED — and both tune-event
    # streams pass --kind roofline
    JAX_PLATFORMS=cpu python scripts/kernel_tune.py --cpu8 --interpret

    echo "smoke ok"
    exit 0
fi

rm -f "$LOG"
timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
# ROADMAP's class plus X: a progress line containing an xpassed test
# must not drop its passing dots from the count
echo "DOTS_PASSED=$(grep -aE '^[.FEsxX]+( *\[ *[0-9]+%\])?$' "$LOG" \
    | tr -cd . | wc -c)"
exit "$rc"
