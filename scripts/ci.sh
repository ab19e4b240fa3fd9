#!/usr/bin/env bash
# Single CI entrypoint (ISSUE 2 satellite): syntax gate + tier-1 suite.
#
#   scripts/ci.sh            # everything
#   scripts/ci.sh --syntax   # compileall gate only (seconds)
#
# The pytest invocation is the ROADMAP tier-1 command plus --strict-markers
# (unknown @pytest.mark.* names fail fast instead of silently never
# deselecting; known markers are declared in pyproject.toml).  The registry/
# beacon/aggregator tests (tests/test_fleet_metrics.py) and the obs unit
# tests ride inside the tier-1 run — they are Python-only and never skip.

set -euo pipefail
cd "$(dirname "$0")/.."

echo "== syntax gate (compileall) =="
# whole trees plus the new entry points by name, so a rename/removal of a
# gated file fails the gate instead of silently shrinking it
python -m compileall -q -f \
    p2p_distributed_tswap_tpu \
    analysis \
    analysis/fleet_top.py \
    analysis/bus_scaling.py \
    analysis/task_timeline.py \
    analysis/blackbox.py \
    p2p_distributed_tswap_tpu/obs/registry.py \
    p2p_distributed_tswap_tpu/obs/beacon.py \
    p2p_distributed_tswap_tpu/obs/events.py \
    p2p_distributed_tswap_tpu/obs/flightrec.py \
    p2p_distributed_tswap_tpu/obs/fleet_aggregator.py \
    p2p_distributed_tswap_tpu/runtime/region.py \
    p2p_distributed_tswap_tpu/runtime/shardmap.py \
    p2p_distributed_tswap_tpu/runtime/buspool.py \
    p2p_distributed_tswap_tpu/runtime/simagent.py \
    p2p_distributed_tswap_tpu/runtime/busns.py \
    p2p_distributed_tswap_tpu/runtime/solverd.py \
    p2p_distributed_tswap_tpu/ops/field_repair.py \
    p2p_distributed_tswap_tpu/ops/field_fused.py \
    p2p_distributed_tswap_tpu/ops/sector.py \
    scripts/sector_fuzz.py \
    analysis/sector_bench.py \
    tests/test_sector.py \
    p2p_distributed_tswap_tpu/obs/slo.py \
    p2p_distributed_tswap_tpu/obs/audit.py \
    scripts/audit_smoke.py \
    scripts/chaos_gate.py \
    p2p_distributed_tswap_tpu/runtime/ha.py \
    scripts/ha_smoke.py \
    p2p_distributed_tswap_tpu/obs/health.py \
    scripts/health_smoke.py \
    p2p_distributed_tswap_tpu/obs/capture.py \
    analysis/fleetsim.py \
    analysis/tenant_scaling.py \
    analysis/field_bench.py \
    p2p_distributed_tswap_tpu/parallel/mesh.py \
    p2p_distributed_tswap_tpu/parallel/solver_mesh.py \
    p2p_distributed_tswap_tpu/parallel/virtual_mesh.py \
    p2p_distributed_tswap_tpu/parallel/sharded.py \
    p2p_distributed_tswap_tpu/parallel/sharded2d.py \
    p2p_distributed_tswap_tpu/ops/tiled_distance.py \
    analysis/mesh_bench.py \
    scripts/mesh_smoke.py \
    scripts/bus_smoke.py \
    scripts/trace_smoke.py \
    scripts/field_fuzz.py \
    scripts/federation_smoke.py \
    p2p_distributed_tswap_tpu/runtime/fleet.py \
    p2p_distributed_tswap_tpu/runtime/plan_codec.py \
    p2p_distributed_tswap_tpu/runtime/shmlane.py \
    p2p_distributed_tswap_tpu/runtime/bus_client.py \
    bench.py
echo "syntax OK"

if [[ "${1:-}" == "--syntax" ]]; then
    exit 0
fi

echo "== codec fuzz gate =="
# random fleets through both plan codecs (ISSUE 3 satellite): py/cpp
# packed encoders must be byte-identical and resident packed planning
# must equal stateless JSON planning; plus pos1 beacon fuzz (ISSUE 4)
JAX_PLATFORMS=cpu python scripts/codec_fuzz.py

echo "== field-repair fuzz gate =="
# ISSUE 9: random obstacle-toggle sequences through the bounded-region
# repair must stay bit-identical to full recompute (chained, so drift
# compounds), incl. ROI-overflow fallback + freed-door window growth
JAX_PLATFORMS=cpu python scripts/field_fuzz.py

echo "== sector planner fuzz gate =="
# ISSUE 19: seeded random worlds + chained toggles through the
# hierarchical sector planner — corridor descent valid from every
# start, suboptimality <= the committed 0.05 bound, and apply_toggles
# == from-scratch rebuild after every block/unblock batch
JAX_PLATFORMS=cpu python scripts/sector_fuzz.py

echo "== busd relay micro-smoke =="
# N-client fanout sanity under the fast relay framing (ISSUE 4): fast +
# legacy subscribers, wildcard region watcher, hub fanout counters
JAX_PLATFORMS=cpu python scripts/bus_smoke.py

echo "== busd shard-pool smoke =="
# federated 3-shard pool (ISSUE 6): cross-shard publish, wildcard
# spanning without duplicates, peering to a legacy client, and the
# one-shard-kill degradation contract
JAX_PLATFORMS=cpu python scripts/bus_smoke.py --shards 3

echo "== busd shm-lane smoke =="
# zero-copy same-host lanes + per-region beacon aggregation (ISSUE 18):
# shm1 negotiation, every beacon over the rings with zero TCP fallbacks,
# >= 4x agg1 fanout cut, lane files reclaimed on close
JAX_PLATFORMS=cpu python scripts/bus_smoke.py --shm

echo "== trace smoke =="
# ISSUE 5: a tiny live fleet under JG_TRACE=1 JG_TRACE_SAMPLE=1.0 must
# reconstruct >= 1 fully-attributed task timeline (task_timeline.py
# --once --json) — proof the trace context propagates on the real wire
JAX_PLATFORMS=cpu python scripts/trace_smoke.py

echo "== fleetsim SLO gate =="
# ISSUE 7: scaled-down production-load rehearsal — a tiny wire-faithful
# sim fleet over a live 2-shard busd pool + centralized manager, judged
# against the relaxed CI spec (deterministic seed).  Any SLO breach OR a
# signal gone dark (exit 2) fails CI.  The breach drill then re-judges
# the SAME measured signals against a known-breaching spec and demands
# exit 1 — proof the gate can actually trip, every run.
if [[ -x cpp/build/mapd_bus ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python analysis/fleetsim.py \
        --agents 24 --side 24 --tick-ms 250 --shards 2 \
        --settle 14 --window 12 --seed 1 \
        --spec scripts/fleetsim_ci.spec.json \
        --out /tmp/jg_fleetsim_ci.json \
        --log-dir /tmp/jg_fleetsim_ci_logs
    drill=0
    JAX_PLATFORMS=cpu python -m p2p_distributed_tswap_tpu.obs.slo \
        --signals /tmp/jg_fleetsim_ci.json \
        --spec scripts/fleetsim_ci.breach.json >/dev/null || drill=$?
    if [[ "$drill" != 1 ]]; then
        echo "fleetsim gate did not trip on the breaching spec" \
             "(exit $drill)" >&2
        exit 1
    fi
    echo "fleetsim gate OK (breach drill tripped as expected)"
else
    echo "fleetsim gate SKIPPED (no C++ toolchain / binaries)"
fi

echo "== dynamic-world smoke =="
# ISSUE 9: a live fleet (busd + manager --solver tpu + solverd + sim
# pool) with walls closing every few seconds via world_update_request;
# the incremental field repairs must route the fleet around them —
# completion ratio 1.0 and >= 1 accepted toggle, judged from the
# artifact the run writes.
if [[ -x cpp/build/mapd_bus ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    # fresh artifact every run: a stale file from a previous invocation
    # must never pass the gate for a build whose run crashed early
    rm -f /tmp/jg_dynworld_ci.json
    JAX_PLATFORMS=cpu python analysis/fleetsim.py \
        --agents 12 --side 24 --tick-ms 250 --solver tpu --solverd-cpu \
        --settle 12 --window 15 --seed 1 --no-trace \
        --world-toggle-cells 5 --world-toggle-every 5 \
        --spec scripts/fleetsim_ci.spec.json \
        --out /tmp/jg_dynworld_ci.json \
        --log-dir /tmp/jg_dynworld_ci_logs || true
    JAX_PLATFORMS=cpu python - <<'PY'
import json, sys
r = json.load(open("/tmp/jg_dynworld_ci.json"))["rungs"][0]
sig = r["signals"]
world = r.get("world") or {}
ok = (sig.get("fleet.completion_ratio") == 1.0
      and world.get("toggles_accepted", 0) >= 1
      and world.get("updates_seen", 0) >= 1)
print("dynamic-world smoke:", json.dumps({
    "completion": sig.get("fleet.completion_ratio"),
    "world": world}))
sys.exit(0 if ok else 1)
PY
    echo "dynamic-world smoke OK"
    # ISSUE 9 satellite (ROADMAP item 2 headroom): N tenants admitted
    # LIVE through solver.admit tenant_hello — exit 0 iff every tenant
    # is welcomed and completes >= 1 task
    JAX_PLATFORMS=cpu python analysis/fleetsim.py --tenants 2 --solverd-cpu \
        --agents 4 --side 24 --settle 10 --window 25 \
        --log-dir /tmp/jg_dynworld_ci_logs
else
    echo "dynamic-world smoke SKIPPED (no C++ toolchain / binaries)"
fi

echo "== audit smoke =="
# ISSUE 10: state-consistency gate, both halves every run — a tiny live
# fleet must end with ZERO confirmed divergences (a fleet that cannot
# prove itself consistent fails CI), then the injected-corruption drill
# must confirm a roster divergence and bisect it to the exact lane +
# field (a gate that cannot trip is no gate)
if [[ -x cpp/build/mapd_bus && -x cpp/build/mapd_manager_centralized ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python scripts/audit_smoke.py \
        --log-dir /tmp/jg_audit_ci_logs
else
    echo "audit smoke SKIPPED (no C++ toolchain / binaries)"
fi

echo "== replay + chaos gate =="
# ISSUE 11: the committed capture must replay deterministically — two
# clean replays completing the identical task-id set with equal audit
# ledger/view digests at the final watermark — and then an injected
# solverd SIGKILL mid-replay MUST be detected and localized by the
# audit plane (a confirmed silent record naming solverd) with zero
# tasks lost or duplicated.  A chaos gate that cannot trip is no gate.
if [[ -x cpp/build/mapd_bus && -x cpp/build/mapd_manager_centralized ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python scripts/chaos_gate.py \
        --capture results/captures/ci_small.capture.json --ci \
        --log-dir /tmp/jg_chaos_ci_logs
    # schema versioning is load-bearing: a future/unknown capture
    # version must be REJECTED (exit 2), never half-replayed
    rej=0
    python - >/dev/null 2>&1 <<'PY' || rej=$?
import json, sys, tempfile, os
sys.path.insert(0, os.getcwd())
doc = json.load(open("results/captures/ci_small.capture.json"))
doc["version"] = "capture999"
p = tempfile.mktemp(suffix=".json")
json.dump(doc, open(p, "w"))
sys.path.insert(0, "scripts")
import chaos_gate
sys.exit(chaos_gate.main(["--capture", p]))
PY
    if [[ "$rej" != 2 ]]; then
        echo "chaos gate accepted an unknown capture version" \
             "(exit $rej)" >&2
        exit 1
    fi
    echo "replay + chaos gate OK (determinism pair held, solverd kill" \
         "detected + localized, unknown version rejected)"
else
    echo "replay + chaos gate SKIPPED (no C++ toolchain / binaries)"
fi

echo "== HA failover smoke =="
# ISSUE 15: a live fleet with a warm standby — SIGKILL the active
# mid-flight; the standby must promote inside one claim window with
# ledger/view digests EQUAL to the active's last shipped ones, the
# auditor must confirm the silent active, and every injected task must
# complete exactly once (zero lost, zero duplicated).  The federated
# variant (2x1: kill one region's active) rides the chaos gate above
# as the recovery-required manager_handoff_kill row.
if [[ -x cpp/build/mapd_bus && -x cpp/build/mapd_manager_centralized ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python scripts/ha_smoke.py \
        --log-dir /tmp/jg_ha_ci_logs
else
    echo "HA failover smoke SKIPPED (no C++ toolchain / binaries)"
fi

echo "== health plane smoke =="
# ISSUE 16: the continuous watcher over a live fleet — a steady clean
# run must record ZERO alerts (false-alert gate), then a diurnal-ramp
# overload must be FORECAST >= 2 evaluation intervals before the
# confirmed hard breach, attributed to the overloaded manager with an
# actuator recommendation, and the page must ship an auto-captured
# replayable capture1 artifact.  An alerting plane that cries wolf, or
# one that only confirms after the outage, both fail CI.
if [[ -x cpp/build/mapd_bus && -x cpp/build/mapd_manager_centralized ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python scripts/health_smoke.py \
        --log-dir /tmp/jg_health_ci_logs
else
    echo "health plane smoke SKIPPED (no C++ toolchain / binaries)"
fi

echo "== federation smoke =="
# ISSUE 14: a live 2x1 federated world — two (manager, solverd-less)
# region pairs on one bus, explicit world-spanning tasks (half cross
# the border), every task must complete EXACTLY once, the handoff
# protocol must fire and ack, and each region's ledger digests must
# reconcile drained at the final watermark
if [[ -x cpp/build/mapd_bus && -x cpp/build/mapd_manager_centralized ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python scripts/federation_smoke.py \
        --log-dir /tmp/jg_federation_ci_logs
else
    echo "federation smoke SKIPPED (no C++ toolchain / binaries)"
fi

echo "== mesh-solverd smoke =="
# ISSUE 13: the mesh==flat digest gate runs unconditionally (byte-
# identical packed responses + audit digests over a 2-way virtual
# mesh, JG_SOLVER_MESH-unset flat-path pin); the live half (tiny fleet
# served BY a mesh solverd, every task completes) self-skips without
# the C++ runtime
JAX_PLATFORMS=cpu python scripts/mesh_smoke.py \
    --log-dir /tmp/jg_mesh_ci_logs

echo "== multi-tenant smoke =="
# ISSUE 8: two namespaced fleets (real C++ managers behind JG_BUS_NS +
# wire-faithful sim pools) on ONE busd + ONE multi-tenant solverd.
# Asserts both tenants complete tasks through the shared device
# super-batch with zero cross-tenant resyncs/evictions — cross-talk on
# the namespaced wire would stall a fleet and fail the gate.
if [[ -x cpp/build/mapd_bus && -x cpp/build/mapd_manager_centralized ]] \
        || { command -v cmake >/dev/null && command -v ninja >/dev/null; }
then
    JAX_PLATFORMS=cpu python analysis/tenant_scaling.py --smoke \
        --log-dir /tmp/jg_tenant_ci_logs
else
    echo "multi-tenant smoke SKIPPED (no C++ toolchain / binaries)"
fi

echo "== tier-1 suite =="
rm -f /tmp/_t1.log
# `|| rc=$?` keeps set -e from aborting before the DOTS_PASSED diagnostic
# below — which matters exactly when tests fail (pipefail makes $? pytest's
# exit status, not tee's)
rc=0
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --strict-markers \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log || rc=$?
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
exit "$rc"
