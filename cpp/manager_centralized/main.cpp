// mapd_manager_centralized — "all pathfinding done centrally" (SURVEY C5).
//
// Native rebuild of src/bin/centralized/manager.rs: tracks per-peer
// AgentState {current_pos, goal_pos, task, task_phase} from position_update
// messages, runs a planning tick every 500 ms (one sequential TSWAP step over
// all tracked agents), emits a move_instruction per agent, flips
// pickup -> delivery goals when agents reach pickups, assigns tasks to idle
// agents with a pending queue drained on position updates and completions,
// auto-reassigns a fresh task on completion, bounded-cache cleanup every
// 30 s, --clean to ignore re-discovered peers, and the stdin operator CLI.
//
// Planning backends:
//   --solver=cpu  (default) native sequential TSWAP (common/tswap.hpp)
//   --solver=tpu  delegate each tick to the JAX solver daemon
//                 (runtime/solverd.py) over bus topic "solver" — the
//                 BASELINE.json north-star deployment shape.
//
// Usage: mapd_manager_centralized [--port P] [--map FILE] [--seed S]
//                                 [--clean] [--solver cpu|tpu]

#include <poll.h>
#include <signal.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <fstream>
#include <map>
#include <optional>
#include <random>
#include <set>
#include <sstream>
#include <string>
#include <tuple>
#include <vector>

#include "../common/audit.hpp"
#include "../common/bus.hpp"
#include "../common/events.hpp"
#include "../common/grid.hpp"
#include "../common/ha.hpp"
#include "../common/json.hpp"
#include "../common/knobs.hpp"
#include "../common/log.hpp"
#include "../common/plan_codec.hpp"
#include "../common/region.hpp"
#include "../common/trace.hpp"
#include "../common/tswap.hpp"

using namespace mapd;

namespace {

volatile sig_atomic_t g_stop = 0;
void handle_stop(int) { g_stop = 1; }

enum class Phase { None, ToPickup, ToDelivery };

struct AgentInfo {
  Cell pos = 0;
  Cell goal = 0;
  std::optional<Json> task;
  Phase phase = Phase::None;
  int64_t last_seen_ms = 0;
  int64_t dispatched_ms = 0;  // when .task was (re-)sent, for resend grace
};

}  // namespace

int main(int argc, char** argv) {
  Knobs knobs(argc, argv);
  set_log_level(knobs);
  const std::string bus_host = knobs.get_str("--host", "MAPD_BUS_HOST",
                                             "127.0.0.1");
  const uint16_t port = static_cast<uint16_t>(
      knobs.get_int("--port", "MAPD_BUS_PORT", 7400));
  const std::string map_file = knobs.get_str("--map", "MAPD_MAP", "");
  const std::string solver = knobs.get_str("--solver", "MAPD_SOLVER", "cpu");
  const bool clean = knobs.get_bool("--clean", "MAPD_CLEAN");
  // open-loop mode (ISSUE 11): no auto-refill on completion — the load
  // is exactly what the operator injects (task/tasks/taskat).  Replay
  // (fleetsim --replay) requires this, or every done would mint a fresh
  // rng task the captured window never contained.
  const bool open_loop = knobs.get_bool("--open-loop", "MAPD_OPEN_LOOP");
  const uint64_t seed = static_cast<uint64_t>(knobs.get_int(
      "--seed", "MAPD_SEED",
      static_cast<int64_t>(std::random_device{}())));
  // RuntimeConfig knobs, reference-parity defaults (core/config.py).
  const int64_t planning_ms =
      knobs.get_int("--planning-interval-ms", "MAPD_PLANNING_INTERVAL_MS",
                    500);                                      // ref :567
  const int64_t cleanup_ms =
      knobs.get_int("--cleanup-interval-ms", "MAPD_CLEANUP_INTERVAL_MS",
                    30000);                                    // ref :727
  const size_t max_agents = static_cast<size_t>(
      knobs.get_int("--max-tracked-agents", "MAPD_MAX_TRACKED_AGENTS",
                    500));                                     // ref :734
  const size_t max_known_peers = static_cast<size_t>(
      knobs.get_int("--max-known-peers", "MAPD_MAX_KNOWN_PEERS",
                    1000));                                    // ref :752
  const int64_t agent_stale_ms =
      knobs.get_int("--agent-stale-ms", "MAPD_AGENT_STALE_MS", 60000);
  // --solver=tpu resilience: plan natively while the solver daemon has
  // been silent this long (the fleet must not stall if solverd dies).
  const int64_t solver_failover_ms =
      knobs.get_int("--solver-failover-ms", "MAPD_SOLVER_FAILOVER_MS", 5000);
  // Plan-wire codec for --solver=tpu: "packed" (default fast path —
  // base64 int32 snapshot/delta packets, see common/plan_codec.hpp) or
  // "json" (the legacy per-agent object wire; solverd always accepts it).
  const std::string plan_codec =
      knobs.get_str("--plan-codec", "JG_PLAN_CODEC", "packed");
  // an agent that keeps reporting idle this long past dispatch never got
  // its task (delivery lost in a bus outage) — re-send the same task
  const int64_t task_resend_ms =
      knobs.get_int("--task-resend-ms", "MAPD_TASK_RESEND_MS", 5000);
  // region-sharded heartbeats (ISSUE 4): agents beacon packed pos1 on
  // mapd.pos.<rx>.<ry>; the manager subscribes the wildcard so agent
  // heartbeats stop fanning out to every other agent.  JG_REGION_GOSSIP=0
  // falls back to flat position_update.
  const bool region_gossip =
      knobs.get_int("--region-gossip", "JG_REGION_GOSSIP", 1) != 0;
  // dynamic worlds (ISSUE 9): accept world_update_request toggles, mutate
  // the grid, and broadcast caps-negotiated world_update frames.
  // JG_DYNAMIC_WORLD=0 is the kill switch — requests are counted and
  // DROPPED, the world1 cap never rides plan_request, and the wire stays
  // byte-identical to the static build.  A NAMESPACED manager (JG_BUS_NS
  // set — a tenant on a multi-tenant solverd) defaults OFF: the solverd
  // grid is shared across tenants and drops tenant-plane world frames,
  // so accepting toggles here would diverge this fleet's grid from its
  // planner's (agents walled in by a phantom wall).  An explicit
  // --dynamic-world/JG_DYNAMIC_WORLD=1 still overrides for
  // single-tenant-behind-a-namespace setups.
  const char* ns_env = getenv("JG_BUS_NS");
  const bool dynamic_world =
      knobs.get_int("--dynamic-world", "JG_DYNAMIC_WORLD",
                    (ns_env && *ns_env) ? 0 : 1) != 0;
  // audit plane (ISSUE 10): periodic state-consistency digest beacons on
  // mapd.audit (task-ledger FNV chain + packed-encoder shadow ring keyed
  // by plan seq and world epoch) plus the bisect drill responder.
  // JG_AUDIT=0 is the kill switch: no subscription, no frames — the
  // wire stays byte-identical to the pre-audit build.
  const bool audit_on =
      knobs.get_int("--audit", "JG_AUDIT", 1) != 0;
  const int64_t audit_interval_ms =
      knobs.get_int("--audit-interval-ms", "JG_AUDIT_INTERVAL_MS", 2000);
  // federated world regions (ISSUE 14): --regions CxR partitions the
  // world into rectangular regions, each owned by its own
  // (manager, solverd) pair; THIS manager owns --region-id.  Ownership,
  // hysteresis and the border-mirror strip follow the canon in
  // common/region.hpp ≡ runtime/region.py (golden-tested).  Unset /
  // "1" is the kill switch: no subscription, no frames, no filters —
  // the single-manager wire stays byte-identical.
  const std::string regions_spec =
      knobs.get_str("--regions", "JG_REGIONS", "1");
  const int region_id = static_cast<int>(
      knobs.get_int("--region-id", "JG_REGION_ID", 0));
  const int fed_hyst = static_cast<int>(knobs.get_int(
      "--fed-hysteresis", "JG_FED_HYSTERESIS", kDefaultFedHysteresis));
  const int fed_border = static_cast<int>(knobs.get_int(
      "--fed-border", "JG_FED_BORDER", kDefaultFedBorder));
  const int64_t handoff_retry_ms = knobs.get_int(
      "--handoff-retry-ms", "JG_HANDOFF_RETRY_MS", 1000);
  // a federated fleet runs one plan wire per region ("solver.r<id>");
  // the default keeps the legacy single-plane topic
  const std::string solver_topic =
      knobs.get_str("--solver-topic", "JG_SOLVER_TOPIC", "solver");
  // audit-pairing namespace: the auditor joins manager↔solverd digests
  // by ns, so each region pair gets a label (e.g. "r0") WITHOUT bus
  // namespacing; defaults to the tenant ns for namespaced fleets
  const std::string audit_ns = knobs.get_str(
      "--audit-ns", "JG_AUDIT_NS", (ns_env && *ns_env) ? ns_env : "");
  // control-plane HA (ISSUE 15): with --ha/JG_HA=1 the active manager
  // continuously ships its task ledger + dispatch watermarks as
  // ledger1 records on raw topic mapd.ha and renews a liveness lease;
  // --standby tails that stream as a warm replica, promotes on lease
  // expiry inside one claim window, and an old-incarnation active that
  // resumes DEMOTES instead of dual-dispatching.  JG_HA unset/0 keeps
  // the single-manager wire byte-identical: nothing published or
  // subscribed on mapd.ha (raw-socket pin test in tests/test_ha.py).
  const bool ha_standby_boot = knobs.get_bool("--standby",
                                              "JG_HA_STANDBY");
  const bool ha_on =
      ha_standby_boot || knobs.get_int("--ha", "JG_HA", 0) != 0;
  const int64_t ha_lease_ms = knobs.get_int(
      "--ha-lease-ms", "JG_HA_LEASE_MS", ha::kDefaultLeaseMs);
  // the takeover sweep-hold (PR 4's post-outage hold, reused): a
  // promoted standby waits this long for an in-flight task's agent to
  // report before re-queueing it — an agent already claiming the task
  // must never be double-dispatched.  Defaults to one claim window
  // (the idle-but-dispatched re-send grace).
  const int64_t ha_hold_ms = knobs.get_int(
      "--ha-hold-ms", "JG_HA_HOLD_MS", task_resend_ms);
  signal(SIGINT, handle_stop);
  signal(SIGTERM, handle_stop);
  signal(SIGPIPE, SIG_IGN);
  // span tracing (JG_TRACE=1 or --trace): same schema as the Python
  // tracer; analysis/trace_report.py merges this file with solverd's
  trace_init("manager_centralized", knobs.get_bool("--trace", nullptr));
  // lifecycle events + flight recorder (ISSUE 5): always-on black box,
  // trace-context propagation gated by JG_TRACE_CTX
  events_init("manager_centralized");
  const bool tctx = trace_ctx_enabled();
  // trace_id = run-epoch | task_id: the epoch salt keeps ids unique
  // across manager restarts sharing one log dir (task ids restart at 1).
  // 20 epoch bits keep every id under 2^53 — the JSON wire carries
  // numbers as doubles, and a larger id would round on the way through
  const int64_t trace_epoch = (unix_ms() & 0xFFFFF) << 32;

  Grid grid = Grid::default_grid();
  if (!map_file.empty()) {
    auto g = Grid::from_file(map_file);
    if (!g) {
      fprintf(stderr, "cannot load map %s\n", map_file.c_str());
      return 1;
    }
    grid = *g;
  }
  DistanceCache dc(grid);
  std::mt19937_64 rng(seed);

  // federation canon (ISSUE 14): parse + validate before any wire I/O —
  // a half-parsed world partition must never route silently
  FedMap fed = FedMap::parse(regions_spec);
  if (!fed.valid()) {
    fprintf(stderr, "bad --regions spec %s (want N or CxR)\n",
            regions_spec.c_str());
    return 2;
  }
  const bool fed_on = fed.total() > 1;
  if (fed_on && (region_id < 0 || region_id >= fed.total())) {
    fprintf(stderr, "--region-id %d out of range for %s\n", region_id,
            regions_spec.c_str());
    return 2;
  }
  const FedRect my_rect =
      fed_on ? fed.rect_of(grid.width, grid.height, region_id) : FedRect{};
  if (fed_on && (my_rect.x0 >= my_rect.x1 || my_rect.y0 >= my_rect.y1)) {
    // ceil-width slabs can leave trailing regions EMPTY on narrow
    // grids (e.g. 4x1 on a 9-wide map): a manager owning no cells
    // would strand every task injected into it — fail loudly
    fprintf(stderr,
            "--regions %s leaves region %d empty on a %dx%d grid\n",
            regions_spec.c_str(), region_id, grid.width, grid.height);
    return 2;
  }

  BusClient bus;
  std::string my_id = random_peer_id();
  if (!bus.connect(bus_host, port, my_id)) {
    fprintf(stderr, "cannot connect to bus on port %u\n", port);
    return 1;
  }
  // the active-role subscription set, shared by normal startup and a
  // standby's promotion (a warm standby subscribes ONLY mapd.ha until
  // it takes over — it must not ingest fleet traffic it cannot act on)
  auto subscribe_active = [&]() {
    bus.subscribe("mapd");
    if (region_gossip) {
      if (fed_on) {
      // interest-scoped gossip (ISSUE 14): a region manager needs only
      // the beacon topics covering ITS rectangle expanded by the
      // handoff/mirror margin — subscribing the fleet-wide wildcard
      // would make every manager process every beacon and the message
      // plane would not scale with region count at all.  Coverage: an
      // agent we track can stand at most `hyst` cells outside the rect
      // before the escape handoff fires, and mirrors live within
      // `border` cells — the +1 guard band covers the crossing beat.
      // The expanded-rect topics also spread across the SHARDED bus
      // pool by the region indices (runtime/shardmap.py), so each
      // manager's gossip load lands on its regions' shards.
      const int gcells = static_cast<int>(knobs.get_int(
          "--region-cells", "JG_REGION_CELLS", kDefaultRegionCells));
      const int exp = std::max(fed_border, fed_hyst) + 1;
      const int x0 = std::max(0, my_rect.x0 - exp);
      const int y0 = std::max(0, my_rect.y0 - exp);
      const int x1 = std::min(grid.width - 1, my_rect.x1 - 1 + exp);
      const int y1 = std::min(grid.height - 1, my_rect.y1 - 1 + exp);
      int n_topics = 0;
      for (int gy = y0 / gcells; gy <= y1 / gcells; ++gy)
        for (int gx = x0 / gcells; gx <= x1 / gcells; ++gx) {
          bus.subscribe(std::string(kPosTopicPrefix) +
                        std::to_string(gx) + "." + std::to_string(gy));
          ++n_topics;
        }
      log_info("🗺️  region %d gossip scope: %d topic(s) over "
               "[%d,%d)x[%d,%d)+%d\n", region_id, n_topics, my_rect.x0,
               my_rect.x1, my_rect.y0, my_rect.y1, exp);
      } else {
        bus.subscribe(kPosTopicWildcard);
      }
    }
    if (solver == "tpu") bus.subscribe(solver_topic);
    // cross-region handoffs arrive on this region's own fed topic
    if (fed_on) bus.subscribe(FedMap::fed_topic(region_id));
    // audit plane rides the un-namespaced operator topic (raw): a
    // tenant manager's digests must reach the cross-tenant auditor
    if (audit_on) bus.subscribe(audit::kAuditTopic, /*raw=*/true);
  };
  if (!ha_standby_boot) subscribe_active();
  // the HA plane rides its own raw topic — active (replication source,
  // rival-claim detection) and standby (the replica tail) both join
  if (ha_on) bus.subscribe(ha::kHaTopic, /*raw=*/true);
  // survive a bus restart (reconnect + resubscribe inside BusClient);
  // agents re-announce themselves on their own reconnect, so tracking
  // repopulates within a heartbeat
  bus.set_reconnect([]() {});
  // live-metrics beacon: registry snapshot on mapd.metrics every ~2 s
  // (fleet_top / obs.fleet_aggregator merge it with the Python peers')
  bus.enable_metrics_beacon("manager_centralized");
  // world-epoch tracking (ISSUE 10 satellite): the epoch + dynamic-world
  // gauges are ALWAYS present, so fleet_top's WORLD line shows a
  // 0-epoch (or dynamic-OFF) manager instead of omitting it — the PR 9
  // caveat (namespaced managers silently diverging from a toggling
  // operator plane) becomes visible instead of folklore
  metrics_gauge("manager.world_seq", 0.0);
  metrics_gauge("manager.dynamic_world", dynamic_world ? 1.0 : 0.0);
  if (fed_on) {
    // federation gauges are the aggregator's REGIONS-section evidence
    metrics_gauge("manager.region", static_cast<double>(region_id));
    metrics_gauge("manager.regions", static_cast<double>(fed.total()));
    metrics_gauge("manager.fed_pending_handoffs", 0.0);
  }
  log_info("🧠 centralized manager %s up (grid %dx%d, solver=%s%s)\n",
           my_id.c_str(), grid.width, grid.height, solver.c_str(),
           clean ? ", clean" : "");
  log_info("Commands: task | tasks N | metrics | save <file> | "
           "save path <file> | reset | quit\n");

  std::map<std::string, AgentInfo> agents;
  std::set<std::string> known_left;
  // ---- federation state (ISSUE 14) ----
  // border-strip foreign agents, fed into the move-emission guard (the
  // boundary-planning-correctness contract; see emit_moves).
  // cell_since tracks how long the body has HELD its current cell: a
  // freshly arrived mirror is presumed transiting and blocks the cell;
  // one parked past the block window becomes pass-through — a foreign
  // idle agent may sit on a border cell indefinitely, and a permanent
  // block there starves every crossing route (found live: the 2x1
  // ladder's crossing throughput collapsed ~4x under an unconditional
  // guard).
  struct Mirror {
    Cell cell = 0;
    int64_t last_seen = 0;
    int64_t cell_since = 0;
  };
  std::map<std::string, Mirror> mirrors;
  const int64_t mirror_block_ms = knobs.get_int(
      "--fed-mirror-block-ms", "JG_FED_MIRROR_BLOCK_MS", 3000);
  // mirror EXPIRY must outlive the block window (and slow heartbeats):
  // evicting a parked foreign body between its beacons would drop the
  // very mirror_cells entry the boundary guard reads
  const int64_t mirror_expire_ms = std::max<int64_t>(
      knobs.get_int("--fed-mirror-expire-ms", "JG_FED_MIRROR_EXPIRE_MS",
                    6000),
      2 * mirror_block_ms);
  auto mirror_touch = [&](const std::string& peer, Cell c) {
    const int64_t now2 = mono_ms();
    auto [mit, fresh] = mirrors.try_emplace(peer);
    if (fresh || mit->second.cell != c) mit->second.cell_since = now2;
    mit->second.cell = c;
    mit->second.last_seen = now2;
  };
  // outbound handoffs: seq-chained per destination region, retransmitted
  // until acked; a peer with an unacked record is in transfer limbo and
  // is never re-adopted from its beacons (handing_off)
  struct OutHandoff {
    Json frame;
    std::string peer;
    int dst = 0;
    int64_t first_send_ms = 0;  // creation order (eviction key —
                                // retransmits refresh last_send_ms
                                // even for a dead neighbor's backlog)
    int64_t last_send_ms = 0;
    // the replication-stream view of this record (ISSUE 15): a warm
    // standby receives the full unacked outbox and, on takeover,
    // RESUMES the retransmit with the original seq + epoch
    ha::HandoffOut ho;
  };
  std::map<std::pair<int, int64_t>, OutHandoff> handoff_unacked;
  std::map<int, int64_t> handoff_next_seq;
  // sender incarnation: a RESTARTED manager reuses seq numbers from 1,
  // and a receiver whose dedup set remembered the old incarnation
  // would ack-without-applying — silently losing the lane and its
  // task.  Every handoff frame carries this epoch.
  const int64_t fed_epoch = unix_ms();
  // receiver dedup: per source region, PER SENDER EPOCH applied-seq
  // sets (bounded) — a replayed/retransmitted handoff can never
  // double-admit an agent (or double-dispatch its task).  Per-epoch
  // (not newest-epoch-only, ISSUE 15): a promoted standby legitimately
  // retransmits its dead active's old-epoch records while sending new
  // ones under its own epoch, and BOTH chains must stay exactly-once.
  std::map<int, std::map<int64_t, std::set<int64_t>>> handoff_applied;
  std::set<std::string> handing_off;
  // peers recently adopted via handoff (peer -> flag expiry): shipped
  // as "handoff_peers" on plan_requests so solverd attributes the
  // fresh lanes (solverd.lanes_admitted{cause=handoff}).  STICKY for a
  // few seconds rather than cleared on first send — the flagged
  // request can be lost to a seq gap, and the recovery snapshot that
  // re-declares the lane must still carry the attribution (solverd
  // only counts NEWLY named lanes, so repeat flags never double-count)
  std::map<std::string, int64_t> handoff_admitted;
  int64_t last_handoff_retry = 0;
  // CLAIM-AWARE adoption (the double-tracking guard): a beacon inside
  // our rect but still within a NEIGHBOR's hysteresis reach may belong
  // to that neighbor (its escape check has not fired yet) — adopting
  // it immediately puts two managers on one body, which wedges both
  // ledgers (found live by the 2x2 ladder: border-hovering agents
  // collected conflicting tasks from two planners).  Such candidates
  // wait: either the neighbor's handoff arrives and claims them, or
  // the grace period expires and they were genuinely unclaimed (a
  // fresh agent spawned in the band) and we adopt.
  const int64_t claim_grace_ms = knobs.get_int(
      "--fed-claim-grace-ms", "JG_FED_CLAIM_GRACE_MS", 4000);
  std::map<std::string, int64_t> claim_candidates;  // peer -> first seen
  std::vector<FedRect> fed_rects;
  if (fed_on)
    for (int rid = 0; rid < fed.total(); ++rid)
      fed_rects.push_back(fed.rect_of(grid.width, grid.height, rid));
  // claimable = outside EVERY other region's hysteresis reach: any
  // neighbor that still owned the agent there would already have
  // escaped-and-handed-off (the thresholds are the same geometry)
  auto fed_claimable = [&](int x, int y) {
    for (int rid = 0; rid < fed.total(); ++rid) {
      if (rid == region_id) continue;
      if (!FedMap::escaped(x, y, fed_rects[rid], fed_hyst)) return false;
    }
    return true;
  };
  // cells targeted by move_instructions of the last two planning ticks:
  // a world toggle must not close a cell an agent is currently walking
  // into (its position_update lands a beat after the instruction) —
  // protected alongside positions/goals/task endpoints below
  std::set<Cell> recent_move_targets, prev_move_targets;
  std::deque<Json> pending_tasks;  // pending_task_requests (ref :367-436)
  // Task ids that were re-queued from a dead/stale agent (at-least-once
  // hazard: the original agent may still be alive and complete the task).
  // A later `done` for such an id cancels the pending duplicate, or — if
  // already re-dispatched — is counted once and never double-refilled.
  std::set<long long> requeued_ids;
  std::set<long long> completed_ids;
  // HA write-ahead (ISSUE 15): fresh Task dispatches are deferred here
  // until the ledger record covering them has shipped on the
  // replication stream — an agent must never hold a task no shipped
  // record knows, or a takeover loses it (found live by the failover
  // chaos row: a dispatch landing between 500 ms replication beats
  // died with the active).  Flushed once per main-loop iteration,
  // AFTER ha_replicate — sub-tick added latency, zero-loss ordering.
  std::deque<Json> ha_task_outbox;
  TaskMetricsCollector task_metrics;
  PathComputationMetrics path_metrics;
  // task-id allocation: federated managers mint from DISJOINT residue
  // classes (ids ≡ region_id mod region count) — colliding ids across
  // regions would poison every dedup/ownership filter keyed by task id
  // (two regions each "owning" a task 56; found live by the 2x2 ladder)
  const uint64_t task_id_stride = fed_on ? fed.total() : 1;
  uint64_t next_task_id = fed_on ? 1 + region_id : 1;
  // advance past an externally minted id (taskat / handed-in ledger
  // entries) while PRESERVING this region's residue class
  auto bump_task_id_past = [&](uint64_t id) {
    if (next_task_id > id) return;
    // O(1): a replay can inject ids in the billions, and a unit-step
    // loop here would stall the single bus-processing thread
    next_task_id += ((id - next_task_id) / task_id_stride + 1)
                    * task_id_stride;
  };
  int64_t plan_seq = 0;
  // per-task wire-hop ledger (common/events.hpp: send advances, receive
  // max-merges, bounded by oldest-id eviction)
  TaskHopLedger hops(trace_epoch);

  auto free_cells = grid.free_cells();
  auto gen_point = [&]() { return free_cells[rng() % free_cells.size()]; };
  // federated task sampling: pickups come from OUR region's free cells
  // (each region manager generates its own load), deliveries stay
  // global — cross-region tasks arise naturally and exercise the
  // handoff path exactly like a world-spanning workload would
  std::vector<Cell> rect_free;
  auto rebuild_rect_free = [&]() {
    rect_free.clear();
    if (!fed_on) return;
    for (Cell c : free_cells) {
      const int x = grid.x_of(c), y = grid.y_of(c);
      if (x >= my_rect.x0 && x < my_rect.x1 && y >= my_rect.y0 &&
          y < my_rect.y1)
        rect_free.push_back(c);
    }
  };
  rebuild_rect_free();
  auto gen_pickup = [&]() {
    return (fed_on && !rect_free.empty())
               ? rect_free[rng() % rect_free.size()]
               : gen_point();
  };

  auto point_json = [&](Cell c) {
    Json p;
    p.push_back(Json(grid.x_of(c)));
    p.push_back(Json(grid.y_of(c)));
    return p;
  };
  auto parse_point = [&](const Json& j) -> std::optional<Cell> {
    const auto& arr = j.as_array();
    if (arr.size() != 2) return std::nullopt;
    int x = static_cast<int>(arr[0].as_int());
    int y = static_cast<int>(arr[1].as_int());
    if (!grid.in_bounds(x, y)) return std::nullopt;
    return grid.cell(x, y);
  };

  auto make_task = [&]() {
    Cell pickup = gen_pickup(), delivery = gen_point();
    while (delivery == pickup) delivery = gen_point();
    Json t;
    t.set("pickup", point_json(pickup))
        .set("delivery", point_json(delivery))
        .set("peer_id", Json())
        .set("task_id", static_cast<int64_t>(next_task_id));
    next_task_id += task_id_stride;
    return t;
  };

  // Future-goal hints for the packed solver wire: the delivery cell of a
  // freshly assigned task becomes a goal only at the pickup flip, many
  // ticks later — shipping it as a hint lets solverd pre-sweep the field
  // in its idle window instead of stalling the tick it goes live.
  std::vector<int32_t> plan_hints;

  // hop 0 = task creation: the trace root every later hop counts from
  auto queue_task = [&]() {
    Json t = make_task();
    long long id = t["task_id"].as_int();
    if (tctx) {
      codec::TraceCtx t0{trace_epoch | id, 0, unix_ms()};
      event_emit("task.queue", &t0, id);
    }
    pending_tasks.push_back(std::move(t));
  };

  auto assign_task = [&](const std::string& peer, Json task) {
    task.set("peer_id", peer);
    uint64_t id = static_cast<uint64_t>(task["task_id"].as_int());
    TaskMetric m;
    m.task_id = id;
    m.peer_id = peer;
    m.sent_time = unix_ms();
    task_metrics.add_metric(m);
    AgentInfo& a = agents[peer];
    a.task = task;
    a.phase = Phase::ToPickup;
    a.dispatched_ms = mono_ms();
    if (auto p = parse_point(task["pickup"])) a.goal = *p;
    if (solver == "tpu" && plan_codec != "json")
      if (auto dl = parse_point(task["delivery"]))
        if (plan_hints.size() < 4096)
          plan_hints.push_back(static_cast<int32_t>(*dl));
    if (tctx) {
      auto t = hops.next(static_cast<long long>(id));
      task.set("tc", tc_json(t));
      a.task = task;  // the stored copy carries the context for re-sends
      event_emit("task.dispatch", &t, static_cast<long long>(id), peer);
    }
    if (ha_on)
      ha_task_outbox.push_back(task);  // write-ahead: record ships first
    else
      bus.publish("mapd", task);
    // live dispatch counter: the fleet rollup derives tasks/s and the
    // completion ratio from the dispatched/completed counter pair
    metrics_count("manager.tasks_dispatched");
    log_info("📤 Task %llu -> %s\n", static_cast<unsigned long long>(id),
             peer.c_str());
  };

  // Push an agent's in-flight task back onto the pending queue (front: it
  // was already dispatched once and should not starve behind fresh tasks).
  // Used when an agent dies (peer_left) or ages out silently — the
  // reference loses such tasks (decentralized/manager.rs:185-189).
  auto requeue_task = [&](const std::string& peer, const AgentInfo& a,
                          const char* why) {
    if (!a.task) return;
    Json t = *a.task;
    long long id = t["task_id"].as_int();
    log_info("♻️  %s %s, re-queueing task %lld\n", why, peer.c_str(), id);
    t.set("peer_id", Json());
    requeued_ids.insert(id);  // at-least-once: dedupe a late done (see below)
    if (tctx) {
      codec::TraceCtx t0 = hops.current(id);
      event_emit("task.requeue", &t0, id, peer);
    }
    pending_tasks.push_front(std::move(t));
  };

  // ---- cross-region handoff, outbound (ISSUE 14) ----
  // The agent lane AND its in-flight task-ledger entry move to the
  // neighbor manager in one packed handoff1 record: seq-chained per
  // (src, dst) pair, retransmitted until handoff_ack, dedup-guarded on
  // the receiver.  The agent leaves OUR tracking immediately (its lane
  // vanishes from the next plan delta; solverd drops it), and beacons
  // from it are ignored while the record is unacked (handing_off) so a
  // quick return can never make two managers plan one body.
  auto send_handoff = [&](const std::string& peer, const AgentInfo& a,
                          int dst) {
    codec::HandoffRec r;
    const int64_t hseq = ++handoff_next_seq[dst];
    r.seq = hseq;
    r.src_region = region_id;
    r.peer = peer;
    r.pos = static_cast<int32_t>(a.pos);
    r.goal = static_cast<int32_t>(a.goal);
    r.phase = a.phase == Phase::ToDelivery ? 2
              : (a.phase == Phase::ToPickup ? 1 : 0);
    if (a.task) {
      r.has_task = true;
      r.task_id = (*a.task)["task_id"].as_int();
      if (auto p = parse_point((*a.task)["pickup"]))
        r.pickup = static_cast<int32_t>(*p);
      if (auto p = parse_point((*a.task)["delivery"]))
        r.delivery = static_cast<int32_t>(*p);
      // the ledger entry LEAVES this region with the lane: surrender
      // any local at-least-once claim on its future done, or a task
      // that was displacement-requeued here earlier would be counted
      // by BOTH regions when it completes (found by the smoke's
      // mgr_completed <= injected bound)
      requeued_ids.erase(r.task_id);
    }
    Json f;
    f.set("type", "handoff1")
        .set("src", static_cast<int64_t>(region_id))
        .set("dst", static_cast<int64_t>(dst))
        .set("seq", hseq)
        .set("epoch", fed_epoch)
        .set("peer_id", peer)
        .set("data", codec::encode_b64(codec::encode_handoff(r)));
    bus.publish(FedMap::fed_topic(dst), f);
    ha::HandoffOut ho;
    ho.dst = dst;
    ho.seq = hseq;
    ho.epoch = fed_epoch;
    ho.peer = peer;
    ho.pos = r.pos;
    ho.goal = r.goal;
    ho.phase = static_cast<uint8_t>(r.phase);
    ho.has_task = r.has_task;
    ho.task_id = r.task_id;
    ho.pickup = r.pickup;
    ho.delivery = r.delivery;
    const int64_t send_ms = mono_ms();
    handoff_unacked[{dst, hseq}] =
        OutHandoff{f, peer, dst, send_ms, send_ms, ho};
    handing_off.insert(peer);
    metrics_count("manager.handoffs_sent");
    metrics_gauge("manager.fed_pending_handoffs",
                  static_cast<double>(handoff_unacked.size()));
    log_info("🛫 handoff %lld: %s -> region %d%s\n",
             static_cast<long long>(hseq), peer.c_str(), dst,
             r.has_task ? " (with task)" : "");
    // bounded outbox: with a dead neighbor the chain never acks — past
    // the cap the oldest record's task re-queues LOCALLY (at-least-once;
    // the done path dedups by task id like every other requeue)
    while (handoff_unacked.size() > 1024) {
      // evict the OLDEST record by creation time (a dead neighbor's
      // backlog), never map-order begin() — that would cancel a LIVE
      // destination's fresh in-flight handoff first
      auto oldest = handoff_unacked.begin();
      for (auto it2 = handoff_unacked.begin();
           it2 != handoff_unacked.end(); ++it2)
        if (it2->second.first_send_ms < oldest->second.first_send_ms)
          oldest = it2;
      auto pkt = codec::decode_b64(oldest->second.frame["data"].as_str());
      if (pkt) {
        if (auto rec = codec::decode_handoff(*pkt); rec && rec->has_task) {
          Json t;
          t.set("pickup", point_json(static_cast<Cell>(rec->pickup)))
              .set("delivery", point_json(static_cast<Cell>(rec->delivery)))
              .set("peer_id", Json())
              .set("task_id", rec->task_id);
          requeued_ids.insert(rec->task_id);
          pending_tasks.push_front(std::move(t));
        }
      }
      handing_off.erase(oldest->second.peer);
      metrics_count("manager.handoff_outbox_overflow");
      handoff_unacked.erase(oldest);
      // the pending gauge is the operator's dead-neighbor evidence —
      // it must track evictions, not just sends/acks
      metrics_gauge("manager.fed_pending_handoffs",
                    static_cast<double>(handoff_unacked.size()));
    }
  };

  // drain the pending queue onto idle tracked agents (ref :367-436)
  auto try_assign_pending = [&]() {
    while (!pending_tasks.empty()) {
      std::string idle_peer;
      for (auto& [peer, a] : agents)
        if (!a.task) {
          idle_peer = peer;
          break;
        }
      if (idle_peer.empty()) return;
      Json t = pending_tasks.front();
      pending_tasks.pop_front();
      assign_task(idle_peer, std::move(t));
    }
  };

  auto emit_moves = [&](const std::vector<std::string>& ids,
                        const std::vector<Cell>& next) {
    Span sp("manager.emit_moves");
    // border-mirror guard (ISSUE 14): cells currently occupied by
    // FOREIGN agents (the neighbor's border-strip beacons we mirror).
    // The planner does not know those bodies — including them as
    // immovable lanes deadlocks TSWAP's rotation resolution (found
    // live: the 2x2 ladder froze at the four-border crossing) — so
    // boundary correctness is enforced HERE instead, exactly like
    // moves_blocked_world: never instruct an agent into an occupied
    // border cell; the lane waits a tick and routes on once the
    // foreign agent moves.
    std::set<Cell> mirror_cells;
    if (fed_on) {
      const int64_t now2 = mono_ms();
      for (const auto& [mp, mc] : mirrors)
        if (now2 - mc.cell_since < mirror_block_ms)
          mirror_cells.insert(mc.cell);
    }
    for (size_t k = 0; k < ids.size(); ++k) {
      auto it = agents.find(ids[k]);
      if (it == agents.end()) continue;
      if (next[k] == it->second.pos) continue;  // no-op moves not sent
      if (fed_on && mirror_cells.count(next[k])) {
        metrics_count("manager.moves_blocked_mirror");
        continue;
      }
      if (!grid.is_free(next[k])) {
        // dynamic worlds (ISSUE 9): a plan computed against the
        // pre-toggle mask may still point into a freshly closed cell
        // until solverd's repair lands — the manager, as the system of
        // record for the world, never instructs an agent into a wall
        // (the lane waits a tick; the repaired field routes it around)
        metrics_count("manager.moves_blocked_world");
        continue;
      }
      recent_move_targets.insert(next[k]);
      Json mi;
      mi.set("type", "move_instruction")
          .set("peer_id", ids[k])
          .set("next_pos", point_json(next[k]))
          .set("timestamp", unix_ms());
      // the steered agent's task context rides its instructions, so the
      // execution leg correlates on the receive side (task.exec)
      if (tctx && it->second.task) {
        long long tid = (*it->second.task)["task_id"].as_int();
        auto t = hops.next(tid);
        mi.set("tc", tc_json(t));
      }
      bus.publish("mapd", mi);
      trace_count("manager.moves_emitted");
    }
  };

  // TSWAP goal exchanges ARE task re-assignments: when a step hands agent
  // k the goal agent j held, the task (and phase) follows the goal, and
  // the manager — the system of record for assignments — re-broadcasts
  // the exchanged Tasks so agent-side positional completion tracks the
  // NEW task.  (The decentralized agents do exactly this on the wire with
  // swap_request/response; this is the centralized equivalent.)
  //
  // History: round 4 instead RESET goals from tasks every tick ("never
  // persist swapped goals") — that fixed the wrong-delivery freeze but
  // created a rarer head-on LIVELOCK: two agents meeting at even
  // separation trigger a Rule-4 rotation, retreat one cell, have their
  // goals snapped back, and repeat forever (observed as fleets frozen
  // right after a pickup flip in the round-4/5 flaky e2e runs).  With
  // tasks following goals, the rotation is real progress exactly like
  // the offline kernel (solver/step.py slot permutation).
  //
  // Push-extension goals (a parked blocker retargeted at the mover's
  // CELL, not at any agent's goal) match no donor: the movement pass
  // already resolved the pair this tick, and the blocker keeps its task
  // state — its goal resets next tick.  An agent whose task was donated
  // away and who received none becomes idle (task_withdrawn tells it to
  // drop its stale copy); try_assign_pending refills it.  Exchanged-task
  // re-broadcasts make the receiving agent re-emit received/started
  // metrics, which update the original record's timestamps — accepted
  // (the task is genuinely being re-assigned).
  auto adopt_goal_exchanges = [&](const std::vector<std::string>& ids,
                                  const std::vector<Cell>& old_goals,
                                  const std::vector<Cell>& new_goals) {
    Span sp("manager.adopt_goal_exchanges");
    struct Incoming {
      std::optional<Json> task;
      Phase phase = Phase::None;
      bool set = false;
    };
    std::multimap<Cell, size_t> donors;  // old goal cell -> index
    for (size_t k = 0; k < ids.size(); ++k)
      if (new_goals[k] != old_goals[k]) donors.insert({old_goals[k], k});
    if (donors.empty()) return;
    std::vector<Incoming> incoming(ids.size());
    std::vector<char> donated(ids.size(), 0);
    for (size_t k = 0; k < ids.size(); ++k) {
      if (new_goals[k] == old_goals[k]) continue;
      auto range = donors.equal_range(new_goals[k]);
      for (auto it = range.first; it != range.second; ++it) {
        size_t j = it->second;
        if (donated[j] || j == k) continue;  // each donor gives once
        AgentInfo& aj = agents[ids[j]];
        incoming[k] = Incoming{aj.task, aj.phase, true};
        donated[j] = 1;
        break;
      }
    }
    auto withdraw = [&](const std::string& peer, const Json& task) {
      Json w;
      w.set("type", "task_withdrawn")
          .set("task_id", task["task_id"])
          .set("peer_id", peer);
      if (tctx) {
        auto t = hops.next(task["task_id"].as_int());
        w.set("tc", tc_json(t));
      }
      bus.publish("mapd", w);
      trace_count("manager.goal_exchanges");
      log_info("🔁 task %lld exchanged away from %s\n",
               task["task_id"].as_int(), peer.c_str());
    };
    for (size_t k = 0; k < ids.size(); ++k) {
      if (!donated[k] && !incoming[k].set) continue;
      AgentInfo& a = agents[ids[k]];
      if (donated[k] && !incoming[k].set) {
        // task handed away, nothing received: now idle
        if (a.task) withdraw(ids[k], *a.task);
        a.task.reset();
        a.phase = Phase::None;
        a.goal = a.pos;
      } else if (incoming[k].set) {
        // received an IDLE donor's positional goal while donating a task
        // away: the agent must drop its stale copy too
        if (donated[k] && a.task && !incoming[k].task)
          withdraw(ids[k], *a.task);
        if (!donated[k] && a.task) {
          // the receiver's own task was claimed by NOBODY (its new goal
          // came from a push-extension coincidence): never drop a live
          // task — back onto the pending queue it goes.  The agent must
          // also hear task_withdrawn, or its live stale copy could
          // positionally double-done the re-dispatched task.
          withdraw(ids[k], *a.task);
          requeue_task(ids[k], a, "exchange displaced");
        }
        a.task = incoming[k].task;
        a.phase = incoming[k].phase;
        if (a.task) {
          a.task->set("peer_id", ids[k]);
          auto cell = parse_point((*a.task)[
              a.phase == Phase::ToDelivery ? "delivery" : "pickup"]);
          if (cell) a.goal = *cell;
          a.dispatched_ms = mono_ms();
          if (tctx) {
            long long tid = (*a.task)["task_id"].as_int();
            auto t = hops.next(tid);
            a.task->set("tc", tc_json(t));
            event_emit("task.exchange", &t, tid, ids[k]);
          }
          bus.publish("mapd", *a.task);  // the re-assignment, on the wire
          log_info("🔁 task %lld exchanged to %s\n",
                   (*a.task)["task_id"].as_int(), ids[k].c_str());
        } else {
          a.phase = Phase::None;
          a.goal = a.pos;
        }
      }
    }
    try_assign_pending();  // displaced tasks go straight back out
  };

  // pickup-arrival phase transitions (ref :695-709): the MANAGER flips the
  // goal to delivery in centralized mode
  auto pickup_transitions = [&]() {
    for (auto& [peer, a] : agents) {
      if (a.phase == Phase::ToPickup && a.task) {
        auto pk = parse_point((*a.task)["pickup"]);
        if (pk && a.pos == *pk) {
          if (auto dl = parse_point((*a.task)["delivery"])) {
            a.goal = *dl;
            a.phase = Phase::ToDelivery;
            if (tctx) {
              // centralized mode: the MANAGER knows the pickup flip (the
              // agent is a dumb body) — the pickup hop comes from here
              long long tid = (*a.task)["task_id"].as_int();
              codec::TraceCtx t0 = hops.current(tid);
              event_emit("task.pickup", &t0, tid, peer);
            }
            log_info("📍 %s reached pickup, now -> delivery\n", peer.c_str());
          }
        }
      }
    }
  };

  auto plan_native = [&]() {
    Span sp("manager.plan_native");
    std::vector<std::string> ids;
    std::vector<Cell> old_goals;
    std::vector<TswapAgent> ta;
    for (auto& [peer, a] : agents) {
      ids.push_back(peer);
      old_goals.push_back(a.goal);
      ta.push_back(TswapAgent{static_cast<int>(ta.size()), a.pos, a.goal});
    }
    if (ids.empty()) return;
    auto t0 = std::chrono::steady_clock::now();
    {
      Span sp("manager.tswap_step",
              "\"agents\":" + std::to_string(ta.size()));
      tswap_step(ta, dc);
    }
    int64_t us = std::chrono::duration_cast<std::chrono::microseconds>(
                     std::chrono::steady_clock::now() - t0)
                     .count();
    path_metrics.record_micros(us, unix_ms());
    std::vector<Cell> next(ids.size()), new_goals(ids.size());
    for (size_t k = 0; k < ids.size(); ++k) {
      next[k] = ta[k].v;
      new_goals[k] = ta[k].g;
    }
    emit_moves(ids, next);
    // swapped/rotated goals carry their tasks with them (see
    // adopt_goal_exchanges: the round-4 reset-every-tick alternative
    // livelocks head-on pairs)
    adopt_goal_exchanges(ids, old_goals, new_goals);
  };

  // goals as they were SENT for the in-flight plan_seq: the daemon's
  // returned goals are relative to these, and any goal mutation between
  // request and response (completion, fresh assignment, idle reset) must
  // not be misread as an exchange
  std::map<std::string, Cell> sent_goals;
  // packed fast path: delta tracking against the state as sent (shadow),
  // periodic snapshot resync, and the seq-gap recovery trigger
  const bool use_packed = (plan_codec != "json");
  codec::PackedFleetEncoder plan_enc;
  int64_t plan_sent_ms = 0;  // fresh-response RTT (manager.plan_rtt_ms)
  // world epoch (monotone, bumped per accepted world_update batch):
  // every audit digest carries it as the second watermark
  int64_t world_seq = 0;
  // accumulated accepted toggles (cell -> blocked, last state wins):
  // replayed to a resyncing solverd so a daemon restarted mid-run
  // re-learns every wall instead of silently planning on the original
  // map (the stale_epoch divergence the audit plane exposes)
  std::map<int32_t, int> world_state;
  // audit shadow ring (ISSUE 10): per-tick digests of the fleet state AS
  // SENT, keyed by plan seq — shipped inside every audit beacon so the
  // auditor joins solverd's post-apply mirror digest at the exact same
  // watermark despite the 2 s beacon cadence vs the 500 ms tick
  std::deque<audit::Entry> audit_ring;

  // ---- control-plane HA state (ISSUE 15) ----
  bool ha_role_standby = ha_standby_boot;   // current role (can flip)
  // incarnation epoch: every HA frame carries it; a takeover bumps it
  // past the dead active's, and the lower (incarnation, peer) of two
  // claimants always demotes (split-brain guard, ha::should_demote)
  int64_t ha_incarnation = unix_ms();
  ha::LedgerEncoder ha_enc(ha_incarnation);
  ha::LedgerReplica ha_rep;
  // the active's lease as the standby sees it (auditor silent-peer rule)
  std::string ha_active_peer;
  int64_t ha_active_inc = 0;
  int64_t ha_lease_last = 0;
  int64_t ha_lease_interval = ha_lease_ms;
  int64_t ha_active_repl_seq = 0;
  // the last record's shipped digests — the takeover announcement
  // proves digest equality against exactly these
  uint64_t ha_active_ld = 0, ha_active_vd = 0;
  bool ha_have_active_digests = false;
  // a fresh standby knows nothing: ask for a snapshot immediately (the
  // plan wire's snapshot-resync path, reused) instead of waiting for
  // the active's next organic delta — which would gap anyway
  bool ha_need_resync = ha_standby_boot;
  int64_t ha_last_resync_req = 0;
  // post-takeover restore set: in-flight replica entries wait here for
  // their agent's next beacon (sweep-hold) instead of being re-queued
  // into a double dispatch; the hold expiry re-queues survivors
  std::map<std::string, Json> ha_restore_task;
  std::map<std::string, Phase> ha_restore_phase;
  int64_t ha_hold_until = 0;
  bool ha_promoted = false;  // this process took over at least once
  const int64_t ha_started = mono_ms();
  // operator lines (taskat under replay) arriving while still standby
  // are deferred and drained at promotion, never dropped
  std::deque<std::string> ha_deferred_cmds;
  bool ha_drain_cmds = false;
  int64_t last_ha_lease = 0, last_ha_repl = 0;
  auto ha_role_gauges = [&]() {
    metrics_gauge("manager.ha_role", ha_role_standby ? 0.0 : 1.0,
                  "role=\"active\"");
    metrics_gauge("manager.ha_role", ha_role_standby ? 1.0 : 0.0,
                  "role=\"standby\"");
  };
  if (ha_on) {
    ha_role_gauges();
    metrics_gauge("manager.ha_replica_lag_entries", 0.0);
  }

  // one world_update fan-out for all three broadcast sites (operator
  // toggles, snapshot-resync replay, HA takeover replay — a wire
  // change applied to one site but not the others would silently
  // desynchronize world state): JSON [x,y,b] on "mapd" for
  // agents/harnesses, packed world1 (or [cell,b] JSON on a JSON plan
  // wire) on the solver topic.  Frames carry the CURRENT world_seq.
  auto publish_world_update = [&](const std::vector<int32_t>& cells,
                                  const std::vector<int32_t>& blocked,
                                  bool to_mapd) {
    if (to_mapd) {
      Json fleet_toggles;
      for (size_t k = 0; k < cells.size(); ++k) {
        Json t;
        t.push_back(Json(static_cast<int64_t>(grid.x_of(cells[k]))));
        t.push_back(Json(static_cast<int64_t>(grid.y_of(cells[k]))));
        t.push_back(Json(static_cast<int64_t>(blocked[k])));
        fleet_toggles.push_back(t);
      }
      Json wu;
      wu.set("type", "world_update")
          .set("world_seq", world_seq)
          .set("toggles", fleet_toggles);
      bus.publish("mapd", wu);
    }
    if (solver == "tpu") {
      Json su;
      su.set("type", "world_update").set("world_seq", world_seq);
      if (use_packed) {
        su.set("codec", codec::kCodecName)
            .set("data", codec::encode_b64(
                     codec::encode_world(world_seq, cells, blocked)));
      } else {
        Json st;
        for (size_t k = 0; k < cells.size(); ++k) {
          Json t;
          t.push_back(Json(static_cast<int64_t>(cells[k])));
          t.push_back(Json(static_cast<int64_t>(blocked[k])));
          st.push_back(t);
        }
        su.set("toggles", st);
      }
      bus.publish(solver_topic, su);
    }
  };

  auto plan_request_tpu = [&]() {
    Span sp("manager.plan_request_encode");
    if (use_packed) {
      std::vector<std::tuple<std::string, int32_t, int32_t>> fleet;
      fleet.reserve(agents.size());
      for (auto& [peer, a] : agents)
        fleet.emplace_back(peer, static_cast<int32_t>(a.pos),
                           static_cast<int32_t>(a.goal));
      if (fleet.empty()) return;
      codec::Packet pkt = plan_enc.encode_tick(++plan_seq, fleet);
      if (tctx) {
        // plan-chain trace: its own id namespace (bit 31 salt) so plan
        // frames never collide with task traces in the timeline
        pkt.has_trace = true;
        pkt.trace = codec::TraceCtx{
            trace_epoch | 0x80000000LL | (plan_seq & 0x7FFFFFFF), 1,
            unix_ms()};
      }
      if (audit_on) {
        // digest the post-encode shadow: exactly the state solverd's
        // mirror must hold after applying THIS packet (same canon:
        // sorted-by-lane (lane,pos,goal) i32 triples, obs/audit.py)
        audit::LaneDigest ld;
        for (const auto& [lane, pg] : plan_enc.shadow_map())
          ld.add(lane, pg.first, pg.second);
        audit::Entry e;
        e.section = audit::kSecShadow;
        e.count = ld.count;
        e.seq = plan_seq;
        e.epoch = world_seq;
        e.digest = ld.digest();
        audit_ring.push_back(e);
        while (audit_ring.size() > 8) audit_ring.pop_front();
      }
      if (pkt.kind == codec::kSnapshot)
        metrics_count("manager.plan_snapshots");
      else
        // snapshots carry the whole fleet by design; only genuine deltas
        // feed the O(churn) evidence counter
        metrics_count("manager.delta_agents",
                      static_cast<double>(pkt.idx.size()));
      Json caps;
      caps.push_back(Json(codec::kCodecName));
      // trace1 cap: this peer reads trace blocks on packed responses
      if (tctx) caps.push_back(Json("trace1"));
      // world1 cap (ISSUE 9): this manager may emit world_update frames;
      // gated so JG_DYNAMIC_WORLD=0 keeps the request bytes identical
      if (dynamic_world) caps.push_back(Json(codec::kWorldCap));
      Json req;
      req.set("type", "plan_request")
          .set("seq", plan_seq)
          .set("codec", codec::kCodecName)
          .set("caps", caps)
          .set("base_seq", pkt.base_seq)
          .set("data", codec::encode_b64(pkt));
      if (!plan_hints.empty()) {
        Json hints;
        for (int32_t c : plan_hints) hints.push_back(Json(c));
        req.set("hints", hints);
        plan_hints.clear();
      }
      if (fed_on && !handoff_admitted.empty()) {
        // recently handed-off lanes: solverd attributes their
        // admission (lanes_admitted{cause=handoff}); expired flags
        // prune here
        const int64_t now2 = mono_ms();
        Json hp;
        for (auto hit2 = handoff_admitted.begin();
             hit2 != handoff_admitted.end();) {
          if (now2 > hit2->second) {
            hit2 = handoff_admitted.erase(hit2);
          } else {
            hp.push_back(Json(hit2->first));
            ++hit2;
          }
        }
        if (!hp.is_null()) req.set("handoff_peers", hp);
      }
      plan_sent_ms = mono_ms();
      bus.publish(solver_topic, req);
      return;
    }
    Json req;
    Json arr;
    std::map<std::string, Cell> snap;
    for (auto& [peer, a] : agents) {
      Json e;
      e.set("peer_id", peer)
          .set("pos", point_json(a.pos))
          .set("goal", point_json(a.goal));
      arr.push_back(e);
      snap[peer] = a.goal;
    }
    if (arr.is_null()) return;
    req.set("type", "plan_request").set("seq", ++plan_seq).set("agents", arr);
    if (tctx)
      req.set("tc", tc_json(trace_epoch | 0x80000000LL |
                                (plan_seq & 0x7FFFFFFF), 1));
    sent_goals = std::move(snap);
    plan_sent_ms = mono_ms();
    bus.publish(solver_topic, req);
  };

  // ---- dynamic worlds (ISSUE 9) ----
  // An operator/harness asks for obstacle toggles with
  //   {"type":"world_update_request","toggles":[[x,y,blocked01],...]}
  // on "mapd".  The manager is the system of record for the world: it
  // VALIDATES each toggle (in bounds; a closing cell must be free,
  // unoccupied, and not a live goal or any task's pickup/delivery — a
  // wall through a task endpoint would strand the task forever), mutates
  // its grid, resets the native distance cache, and broadcasts the
  // accepted batch as caps-negotiated world_update frames: JSON
  // [x,y,blocked] on "mapd" for agents/harnesses, and packed world1 (or
  // [cell,blocked] JSON when the plan wire is JSON) on "solver" so the
  // daemon repairs its cached fields.  The requester gets a
  // world_update_applied ack with per-toggle rejection reasons.
  auto handle_world_request = [&](const Json& d) {
    if (!dynamic_world) {
      metrics_count("manager.world_updates_ignored");
      return;
    }
    std::set<Cell> protected_cells;
    auto protect_task = [&](const Json& t) {
      if (auto p = parse_point(t["pickup"])) protected_cells.insert(*p);
      if (auto p = parse_point(t["delivery"])) protected_cells.insert(*p);
    };
    for (auto& [peer, a] : agents) {
      protected_cells.insert(a.pos);
      protected_cells.insert(a.goal);
      if (a.task) protect_task(*a.task);
    }
    for (const auto& t : pending_tasks) protect_task(t);
    // in-flight moves: instructions already published may not have
    // echoed back as position_updates yet — closing their target would
    // wall the walking agent in
    protected_cells.insert(recent_move_targets.begin(),
                           recent_move_targets.end());
    protected_cells.insert(prev_move_targets.begin(),
                           prev_move_targets.end());
    std::vector<int32_t> cells, blocked;
    Json rejected;
    for (const auto& e : d["toggles"].as_array()) {
      const auto& arr = e.as_array();
      if (arr.size() != 3) {
        // malformed entries must still show in the ack — the requester
        // reconciles accepted + rejected against what it submitted
        Json r;
        r.push_back(Json(static_cast<int64_t>(-1)));
        r.push_back(Json(static_cast<int64_t>(-1)));
        r.push_back(Json(std::string("malformed")));
        rejected.push_back(r);
        continue;
      }
      const int x = static_cast<int>(arr[0].as_int());
      const int y = static_cast<int>(arr[1].as_int());
      const bool blk = arr[2].as_int() != 0;
      auto reject = [&](const char* why) {
        Json r;
        r.push_back(Json(static_cast<int64_t>(x)));
        r.push_back(Json(static_cast<int64_t>(y)));
        r.push_back(Json(std::string(why)));
        rejected.push_back(r);
      };
      if (!grid.in_bounds(x, y)) {
        reject("out_of_bounds");
        continue;
      }
      const Cell c = grid.cell(x, y);
      if ((grid.free[c] != 0) == !blk) {
        reject("noop");
        continue;
      }
      if (blk && protected_cells.count(c)) {
        reject("occupied");
        continue;
      }
      grid.free[c] = blk ? 0 : 1;
      cells.push_back(static_cast<int32_t>(c));
      blocked.push_back(blk ? 1 : 0);
      world_state[static_cast<int32_t>(c)] = blk ? 1 : 0;
    }
    if (!cells.empty()) {
      ++world_seq;
      dc.clear();  // native fields rebuild against the new mask on demand
      free_cells = grid.free_cells();
      rebuild_rect_free();  // region task sampling tracks the new mask
      metrics_count("manager.world_updates");
      metrics_count("manager.world_toggles",
                    static_cast<double>(cells.size()));
      metrics_gauge("manager.world_seq", static_cast<double>(world_seq));
      publish_world_update(cells, blocked, /*to_mapd=*/true);
      log_info("🌍 world update %lld: %zu toggle(s) applied, %zu free "
               "cell(s) remain\n",
               static_cast<long long>(world_seq), cells.size(),
               free_cells.size());
    }
    if (rejected.is_null()) rejected = Json(JsonArray{});
    Json ack;
    ack.set("type", "world_update_applied")
        .set("world_seq", world_seq)
        .set("accepted", static_cast<int64_t>(cells.size()))
        .set("rejected", rejected);
    bus.publish("mapd", ack);
  };

  // ---- the ledger, enumerated once (ISSUE 10 + ISSUE 15) ----
  // Pending queue + every in-flight assignment + post-takeover hold
  // entries, in deterministic order (pending, agents, restore).  BOTH
  // the audit digests (ledger_tuples below) and the HA replication
  // stream derive from this ONE enumeration — the takeover
  // digest-equality acceptance holds only while they enumerate
  // identical state, so there is exactly one source.
  auto ha_ledger_tasks = [&]() {
    std::vector<ha::LedgerTask> out;
    auto cells_of = [&](const Json& t, int32_t* pk, int32_t* dl) {
      auto p = parse_point(t["pickup"]);
      auto d2 = parse_point(t["delivery"]);
      *pk = p ? static_cast<int32_t>(*p) : -1;
      *dl = d2 ? static_cast<int32_t>(*d2) : -1;
    };
    for (const auto& t : pending_tasks) {
      ha::LedgerTask lt;
      lt.task_id = t["task_id"].as_int();
      lt.state = audit::kTaskPending;
      cells_of(t, &lt.pickup, &lt.delivery);
      out.push_back(std::move(lt));
    }
    for (auto& [peer, a] : agents) {
      if (!a.task) continue;
      ha::LedgerTask lt;
      lt.task_id = (*a.task)["task_id"].as_int();
      lt.state = a.phase == Phase::ToDelivery ? audit::kTaskToDelivery
                                              : audit::kTaskToPickup;
      cells_of(*a.task, &lt.pickup, &lt.delivery);
      lt.peer = peer;
      out.push_back(std::move(lt));
    }
    // post-takeover hold entries (ISSUE 15): an in-flight task waiting
    // for its agent to report is STILL in this ledger — dropping it
    // would read as a lost task at the very watermark the takeover is
    // judged on
    for (auto& [peer, tj] : ha_restore_task) {
      ha::LedgerTask lt;
      lt.task_id = tj["task_id"].as_int();
      auto ph = ha_restore_phase.find(peer);
      lt.state = (ph != ha_restore_phase.end()
                  && ph->second == Phase::ToDelivery)
                     ? audit::kTaskToDelivery
                     : audit::kTaskToPickup;
      cells_of(tj, &lt.pickup, &lt.delivery);
      lt.peer = peer;
      out.push_back(std::move(lt));
    }
    return out;
  };

  // ---- audit plane (ISSUE 10): ledger digests, beacon, drill ----
  // (task_id, state, pickup, delivery) tuples sorted by (id, state) —
  // the ledger canon of obs/audit.py, derived from the one enumeration
  auto ledger_tuples = [&]() {
    std::vector<std::tuple<int64_t, uint8_t, int32_t, int32_t>> tup;
    for (const auto& t : ha_ledger_tasks())
      tup.emplace_back(t.task_id, t.state, t.pickup, t.delivery);
    std::sort(tup.begin(), tup.end());
    return tup;
  };

  auto publish_audit_beacon = [&]() {
    // a warm standby (ISSUE 15) beacons its REPLICA's digests at the
    // replicated watermarks — the auditor sees replica convergence
    // live, and the takeover digest-equality is externally checkable
    const bool stby = ha_on && ha_role_standby;
    std::vector<audit::Entry> entries;
    if (!stby)
      entries.assign(audit_ring.begin(), audit_ring.end());
    std::vector<std::tuple<int64_t, uint8_t, int32_t, int32_t>> tup;
    if (stby) {
      for (const auto& [tid, t] : ha_rep.tasks)
        tup.emplace_back(tid, t.state, t.pickup, t.delivery);
      std::sort(tup.begin(), tup.end());
    } else {
      tup = ledger_tuples();
    }
    const int64_t wm_seq = stby ? ha_rep.plan_seq : plan_seq;
    const int64_t wm_epoch = stby ? ha_rep.world_seq : world_seq;
    audit::LedgerDigest ld;
    int64_t pending = 0, to_pickup = 0, to_delivery = 0;
    std::vector<int64_t> inflight;
    for (const auto& [id, st, pk, dl] : tup) {
      ld.add(id, st, pk, dl);
      if (st == audit::kTaskPending) ++pending;
      else if (st == audit::kTaskToPickup) ++to_pickup;
      else ++to_delivery;
      if (st != audit::kTaskPending) inflight.push_back(id);
    }
    audit::Entry el;
    el.section = audit::kSecLedger;
    el.count = ld.count;
    el.seq = wm_seq;
    el.epoch = wm_epoch;
    el.digest = ld.digest();
    entries.push_back(el);
    std::sort(inflight.begin(), inflight.end());
    audit::Entry ev;
    ev.section = audit::kSecView;
    ev.count = static_cast<uint32_t>(inflight.size());
    ev.seq = wm_seq;
    ev.epoch = wm_epoch;
    ev.digest = audit::view_digest(inflight);
    entries.push_back(ev);
    Json caps;
    caps.push_back(Json(std::string(audit::kAuditCap)));
    Json buckets;
    buckets.set("pending", pending)
        .set("to_pickup", to_pickup)
        .set("to_delivery", to_delivery);
    Json b;
    b.set("type", "audit_beacon")
        .set("peer_id", my_id)
        .set("proc", stby ? "manager_standby" : "manager_centralized")
        .set("ns", audit_ns)
        .set("ts_ms", unix_ms())
        .set("interval_s", audit_interval_ms / 1000.0)
        .set("caps", caps)
        .set("dynamic_world", dynamic_world)
        .set("buckets", buckets)
        .set("data", codec::b64_encode(audit::encode_audit(entries)));
    bus.publish(audit::kAuditTopic, b, /*raw=*/true);
  };

  // Bisect drill responder: range digests over lane halves ("shadow")
  // or task-id halves ("ledger"), rows at the leaf — the auditor
  // recurses to the first divergent lane without any full-state ship.
  auto handle_drill = [&](const Json& d) {
    if (!audit_on) return;
    const std::string target = d["target"].as_str();
    if (target != "manager_centralized" && target != my_id) return;
    const std::string view = d["view"].as_str();
    const int64_t lo = d["lo"].as_int();
    const int64_t hi = d["hi"].as_int();
    const bool want_rows = d["rows"].as_bool() || hi - lo <= 4;
    Json resp;
    resp.set("type", "audit_drill_response")
        .set("req_id", d["req_id"])
        .set("peer_id", my_id)
        .set("target", target)
        .set("view", view)
        .set("lo", lo)
        .set("hi", hi);
    if (view == "ledger") {
      audit::LedgerDigest ld;
      for (const auto& [id, st, pk, dl] : ledger_tuples()) {
        if (id < lo || id >= hi) continue;
        ld.add(id, st, pk, dl);
      }
      resp.set("digest", audit::digest_hex(ld.digest()))
          .set("count", static_cast<int64_t>(ld.count));
    } else {  // "shadow"
      audit::LaneDigest ldg;
      Json rows;
      for (const auto& [lane, pg] : plan_enc.shadow_map()) {
        if (lane < lo || lane >= hi) continue;
        ldg.add(lane, pg.first, pg.second);
        if (want_rows) {
          Json r;
          r.push_back(Json(static_cast<int64_t>(lane)));
          r.push_back(Json(static_cast<int64_t>(pg.first)));
          r.push_back(Json(static_cast<int64_t>(pg.second)));
          r.push_back(Json(static_cast<int64_t>(1)));
          r.push_back(Json(plan_enc.peer_of(lane)));
          rows.push_back(r);
        }
      }
      resp.set("digest", audit::digest_hex(ldg.digest()))
          .set("count", static_cast<int64_t>(ldg.count));
      if (want_rows) {
        if (rows.is_null()) rows = Json(JsonArray{});
        resp.set("rows", rows);
      }
    }
    bus.publish(audit::kAuditTopic, resp, /*raw=*/true);
  };

  // --solver=tpu liveness (declared before the HA lambdas: a
  // promotion must reset the failover clock, or a standby's whole
  // pre-takeover uptime reads as daemon silence)
  int64_t last_plan_response = mono_ms();
  bool failed_over = false;

  // ---- control-plane HA lambdas (ISSUE 15) ----
  auto ha_replicate = [&]() {
    // the unacked cross-region handoff outbox rides every record
    // wholesale: a promoted standby RESUMES the retransmit-until-ack
    // loop instead of losing a mid-transfer task
    std::vector<ha::HandoffOut> hovec;
    hovec.reserve(handoff_unacked.size());
    for (const auto& [hk, out] : handoff_unacked) {
      (void)hk;
      hovec.push_back(out.ho);
    }
    auto rec = ha_enc.encode_tick(plan_seq, world_seq,
                                  static_cast<int64_t>(next_task_id),
                                  ha_ledger_tasks(), world_state, hovec);
    if (!rec) return;
    const std::string blob = codec::b64_encode(ha::encode_ledger(*rec));
    Json f;
    f.set("type", "ledger1")
        .set("ns", audit_ns)
        .set("peer_id", my_id)
        .set("incarnation", ha_incarnation)
        .set("seq", rec->seq)
        .set("data", blob);
    bus.publish(ha::kHaTopic, f, /*raw=*/true);
    metrics_count("manager.ha_repl_records");
    metrics_count("manager.ha_repl_bytes",
                  static_cast<double>(blob.size()));
    metrics_gauge("manager.ha_repl_seq",
                  static_cast<double>(ha_enc.last_seq()));
  };

  // the write-ahead flush: ship the record covering every deferred
  // dispatch, THEN release the Task frames to the agents
  auto ha_flush = [&]() {
    if (!ha_on || ha_role_standby) return;
    ha_replicate();
    while (!ha_task_outbox.empty()) {
      bus.publish("mapd", ha_task_outbox.front());
      ha_task_outbox.pop_front();
    }
  };

  auto ha_publish_lease = [&]() {
    Json f;
    f.set("type", "ha_lease")
        .set("ns", audit_ns)
        .set("peer_id", my_id)
        .set("incarnation", ha_incarnation)
        .set("interval_ms", ha_lease_ms)
        .set("repl_seq", ha_enc.last_seq());
    bus.publish(ha::kHaTopic, f, /*raw=*/true);
  };

  // takeover: become the region's system of record inside one claim
  // window — seed the ledger from the replica, replay the accumulated
  // world toggles at the replicated epoch, announce the bumped
  // incarnation WITH the digest-equal watermark proof, and hold
  // in-flight entries for their agents (the sweep-hold) so a task an
  // agent already claims is never double-dispatched.
  auto ha_promote = [&](const char* why) {
    ha_role_standby = false;
    ha_promoted = true;
    ha_incarnation = std::max(unix_ms(), ha_active_inc + 1);
    ha_enc = ha::LedgerEncoder(ha_incarnation);
    metrics_count("manager.ha_takeovers");
    ha_role_gauges();
    subscribe_active();
    // seed the ledger: pending entries go straight to the queue,
    // in-flight ones wait in the restore set for their agent's beacon
    for (const auto& [tid, t] : ha_rep.tasks) {
      Json tj;
      tj.set("pickup", point_json(static_cast<Cell>(t.pickup)))
          .set("delivery", point_json(static_cast<Cell>(t.delivery)))
          .set("peer_id", Json())
          .set("task_id", tid);
      if (t.state == audit::kTaskPending || t.peer.empty()) {
        pending_tasks.push_back(std::move(tj));
      } else {
        tj.set("peer_id", t.peer);
        ha_restore_task[t.peer] = std::move(tj);
        ha_restore_phase[t.peer] = t.state == audit::kTaskToDelivery
                                       ? Phase::ToDelivery
                                       : Phase::ToPickup;
      }
    }
    if (ha_rep.next_task_id > 0)
      bump_task_id_past(static_cast<uint64_t>(ha_rep.next_task_id));
    // resume the dead active's unacked cross-region handoffs (ISSUE
    // 15): rebuild each original frame (same seq + ORIGINAL epoch, so
    // the receiver's per-epoch dedup keeps working — already-applied
    // records re-ack, lost ones apply) and let the retransmit loop
    // drive them; their tasks are NOT in our ledger (they left with
    // the record) and must not be re-queued locally — that would
    // double-dispatch against a receiver that did apply.
    if (fed_on) {
      for (const auto& h : ha_rep.handoffs) {
        codec::HandoffRec r;
        r.seq = h.seq;
        r.src_region = region_id;
        r.peer = h.peer;
        r.pos = h.pos;
        r.goal = h.goal;
        r.phase = h.phase;
        r.has_task = h.has_task;
        r.task_id = h.task_id;
        r.pickup = h.pickup;
        r.delivery = h.delivery;
        Json f;
        f.set("type", "handoff1")
            .set("src", static_cast<int64_t>(region_id))
            .set("dst", static_cast<int64_t>(h.dst))
            .set("seq", h.seq)
            .set("epoch", h.epoch)
            .set("peer_id", h.peer)
            .set("data", codec::encode_b64(codec::encode_handoff(r)));
        handoff_unacked[{h.dst, h.seq}] =
            OutHandoff{f, h.peer, h.dst, mono_ms(), 0, h};
        handing_off.insert(h.peer);
        auto& nxt = handoff_next_seq[h.dst];
        nxt = std::max(nxt, h.seq);
        metrics_count("manager.ha_restored_handoffs");
      }
      if (!handoff_unacked.empty())
        metrics_gauge("manager.fed_pending_handoffs",
                      static_cast<double>(handoff_unacked.size()));
    }
    // world replay: adopt the replicated toggle state at the
    // replicated epoch, then re-broadcast it exactly like the
    // snapshot-resync world replay — agents and solverd re-learn every
    // wall from the NEW system of record
    if (!ha_rep.world.empty() || ha_rep.world_seq > world_seq) {
      const Cell cells_total = static_cast<Cell>(grid.free.size());
      for (const auto& [c, bl] : ha_rep.world) {
        if (c < 0 || c >= cells_total) continue;
        grid.free[c] = bl ? 0 : 1;
        world_state[c] = bl ? 1 : 0;
      }
      world_seq = std::max(world_seq, ha_rep.world_seq);
      dc.clear();
      free_cells = grid.free_cells();
      rebuild_rect_free();
      metrics_gauge("manager.world_seq", static_cast<double>(world_seq));
      if (dynamic_world && !world_state.empty()) {
        std::vector<int32_t> cells, blocked;
        for (const auto& [c, b2] : world_state) {
          cells.push_back(c);
          blocked.push_back(b2);
        }
        publish_world_update(cells, blocked, /*to_mapd=*/true);
      }
    }
    ha_hold_until = mono_ms() + ha_hold_ms;
    // the solver-failover clock starts NOW: the standby's whole
    // pre-takeover uptime must not read as solverd silence
    last_plan_response = mono_ms();
    failed_over = false;
    // the takeover announcement: self-computed audit-canon digests
    // over the seeded ledger MUST equal the failed active's last
    // shipped digests — the acceptance equality, on the wire for any
    // judge (ha_smoke, chaos_gate, fleet_top)
    auto [ld, vd] = ha::ledger_view_digests(ha_ledger_tasks());
    Json t;
    t.set("type", "ha_takeover")
        .set("ns", audit_ns)
        .set("peer_id", my_id)
        .set("incarnation", ha_incarnation)
        .set("why", std::string(why))
        .set("repl_seq", ha_rep.seq)
        .set("plan_seq", ha_rep.plan_seq)
        .set("world_seq", ha_rep.world_seq)
        .set("ledger_digest", audit::digest_hex(ld))
        .set("view_digest", audit::digest_hex(vd))
        .set("active_ledger_digest",
             ha_have_active_digests ? audit::digest_hex(ha_active_ld)
                                    : std::string(""))
        .set("active_view_digest",
             ha_have_active_digests ? audit::digest_hex(ha_active_vd)
                                    : std::string(""))
        .set("active_peer", ha_active_peer)
        .set("pending", static_cast<int64_t>(pending_tasks.size()))
        .set("inflight", static_cast<int64_t>(ha_restore_task.size()));
    bus.publish(ha::kHaTopic, t, /*raw=*/true);
    last_ha_lease = 0;  // start leasing immediately
    ha_drain_cmds = !ha_deferred_cmds.empty();
    ha_replicate();  // a rival standby can tail US from this moment
    log_info("👑 HA takeover (%s): incarnation %lld, %zu pending + %zu "
             "in-flight restored @ repl seq %lld (ledger %s)\n",
             why, static_cast<long long>(ha_incarnation),
             pending_tasks.size(), ha_restore_task.size(),
             static_cast<long long>(ha_rep.seq),
             audit::digest_hex(ld).c_str());
    try_assign_pending();
  };

  // the split-brain guard's losing side: surrender the ledger to the
  // higher-incarnation claimant and become ITS warm standby — an
  // old-incarnation active that resumes must never dual-dispatch
  auto ha_demote = [&](int64_t inc, const std::string& peer) {
    log_warn("⚠️  HA demote: %s claims incarnation %lld > mine %lld; "
             "surrendering the active role\n", peer.c_str(),
             static_cast<long long>(inc),
             static_cast<long long>(ha_incarnation));
    ha_role_standby = true;
    metrics_count("manager.ha_demotions");
    ha_role_gauges();
    pending_tasks.clear();
    agents.clear();
    ha_restore_task.clear();
    ha_restore_phase.clear();
    ha_hold_until = 0;
    handoff_unacked.clear();
    handing_off.clear();
    requeued_ids.clear();
    ha_task_outbox.clear();
    ha_rep = ha::LedgerReplica();
    ha_have_active_digests = false;
    ha_need_resync = true;
    ha_active_peer = peer;
    ha_active_inc = inc;
    ha_lease_last = mono_ms();
  };

  // one entry point for every mapd.ha frame (both roles).  Returns
  // true when the frame was an HA frame (handled or filtered).
  auto ha_handle_frame = [&](const Json& d) -> bool {
    const std::string& type = d["type"].as_str();
    if (type != "ha_lease" && type != "ledger1" &&
        type != "ha_takeover" && type != "ha_resync_request")
      return false;
    if (d["ns"].as_str() != audit_ns) return true;  // another pair's
    const std::string peer = d["peer_id"].as_str();
    if (peer == my_id) return true;  // own frame echoed back
    const int64_t inc = d["incarnation"].as_int();
    if (type == "ha_resync_request") {
      if (!ha_role_standby) {
        metrics_count("manager.ha_resync_requests");
        ha_enc.request_snapshot();
        ha_replicate();
      }
      return true;
    }
    // an active-claiming frame: while active ourselves, the lower
    // (incarnation, peer) demotes — deterministic on both sides
    if (!ha_role_standby) {
      if (ha::should_demote(ha_incarnation, my_id, inc, peer))
        ha_demote(inc, peer);
      return true;
    }
    // standby: any claimant frame renews the lease (a zombie with a
    // LOWER incarnation than the freshest seen never does)
    if (inc >= ha_active_inc) {
      if (inc > ha_active_inc) {
        // a NEW active incarnation announced itself: our chain (if
        // any) is from the old one — resync against the new stream
        ha_active_inc = inc;
        ha_need_resync = true;
      }
      ha_active_peer = peer;
      ha_lease_last = mono_ms();
      if (type == "ha_lease") {
        const int64_t iv = d["interval_ms"].as_int();
        if (iv > 0) ha_lease_interval = iv;
        ha_active_repl_seq = d["repl_seq"].as_int();
        metrics_gauge("manager.ha_replica_lag_entries",
                      static_cast<double>(std::max<int64_t>(
                          0, ha_active_repl_seq - ha_rep.seq)));
      }
    }
    if (type == "ledger1") {
      auto raw = codec::b64_decode(d["data"].as_str());
      std::optional<ha::LedgerRec> rec;
      if (raw) rec = ha::decode_ledger(*raw);
      if (!rec) {
        metrics_count("manager.ha_bad_records");
        return true;
      }
      switch (ha_rep.apply(*rec)) {
        case ha::ApplyResult::kApplied:
          ha_active_ld = rec->ledger_digest;
          ha_active_vd = rec->view_digest;
          ha_have_active_digests = true;
          ha_need_resync = false;
          metrics_gauge("manager.ha_replica_lag_entries", 0.0);
          break;
        case ha::ApplyResult::kDivergent:
          // applied but the recomputed digests disagree: this replica
          // must RESYNC, never promote on bad state
          metrics_count("manager.ha_replica_divergence");
          ha_have_active_digests = false;
          ha_need_resync = true;
          break;
        case ha::ApplyResult::kGap:
          metrics_count("manager.ha_replica_gaps");
          ha_need_resync = true;
          // the last-known active digests describe a PRE-GAP ledger: a
          // takeover forced before the resync lands must not claim
          // equality against them — the proof is honestly unavailable
          ha_have_active_digests = false;
          break;
        case ha::ApplyResult::kStale:
          metrics_count("manager.ha_stale_records");
          break;
      }
      metrics_gauge("manager.ha_repl_seq",
                    static_cast<double>(ha_rep.seq));
    }
    return true;
  };

  auto handle_plan_response = [&](const Json& d) {
    // one-way solverd->manager latency (trace ctx echoed by the daemon;
    // JSON wire carries "tc", the packed response its trace1 block)
    if (auto t = tc_parse(d))
      event_emit("plan.response", &*t, d["seq"].as_int(), "solverd",
                 t->send_ms);
    if (d["seq"].as_int() != plan_seq) {
      trace_count("manager.stale_plan_responses");
      return;  // stale tick
    }
    Span sp("manager.plan_response_apply");
    // Only FRESH (applied) responses prove the daemon useful: a daemon
    // whose latency always exceeds the planning tick produces nothing but
    // stale responses, and counting those as liveness would suppress the
    // failover while no plan of its ever lands.
    last_plan_response = mono_ms();
    if (failed_over) {
      failed_over = false;
      log_info("🔌 solver daemon responding again; leaving native "
               "failover\n");
      // this tick's moves were already planned natively — applying the
      // daemon's plan too would send agents two conflicting instructions
      return;
    }
    int64_t us = d["duration_micros"].as_int();
    path_metrics.record_micros(us, unix_ms());
    // end-to-end planning latency as the fleet pays it: request publish ->
    // fresh response applied (the crossover harness compares this against
    // the native path's tick_ms)
    metrics_observe("manager.plan_rtt_ms",
                    static_cast<double>(mono_ms() - plan_sent_ms));
    std::vector<std::string> ids;
    std::vector<Cell> next, old_goals, new_goals;
    if (d["codec"].as_str() == codec::kCodecName) {
      // packed response: int32 (lane, next_cell, goal_cell) triplets for
      // lanes that moved or changed goal; lanes resolve through the
      // encoder's roster, sent-state through its shadow
      auto pkt = codec::decode_b64(d["data"].as_str());
      if (!pkt || pkt->kind != codec::kResponse) {
        metrics_count("manager.bad_plan_packets");
        return;
      }
      if (pkt->has_trace)
        event_emit("plan.response", &pkt->trace, d["seq"].as_int(),
                   "solverd", pkt->trace.send_ms);
      const Cell cells = static_cast<Cell>(grid.width * grid.height);
      for (size_t k = 0; k < pkt->idx.size(); ++k) {
        Cell np = static_cast<Cell>(pkt->pos[k]);
        if (np < 0 || np >= cells) continue;
        const std::string& peer = plan_enc.peer_of(pkt->idx[k]);
        if (peer.empty()) continue;
        auto it = agents.find(peer);
        if (it == agents.end()) continue;
        ids.push_back(peer);
        next.push_back(np);
        // same phantom-exchange guard as the JSON path: judged against
        // the goal the request carried (the encoder's shadow)
        auto sh = plan_enc.shadow_of(pkt->idx[k]);
        const bool unchanged = sh && sh->second == it->second.goal;
        Cell ng = static_cast<Cell>(pkt->goal[k]);
        old_goals.push_back(it->second.goal);
        new_goals.push_back(unchanged && ng >= 0 && ng < cells
                                ? ng : it->second.goal);
      }
    } else {
      for (const auto& mv : d["moves"].as_array()) {
        auto np = parse_point(mv["next_pos"]);
        if (!np) continue;
        const std::string& peer = mv["peer_id"].as_str();
        auto it = agents.find(peer);
        if (it == agents.end()) continue;
        ids.push_back(peer);
        next.push_back(*np);
        // exchanges are judged against the goal THE REQUEST carried, and
        // only for agents whose goal is unchanged since — a completion or
        // fresh assignment in flight must not fabricate a phantom exchange
        auto sg = sent_goals.find(peer);
        const bool unchanged = sg != sent_goals.end()
                               && sg->second == it->second.goal;
        old_goals.push_back(it->second.goal);
        auto ng = parse_point(mv["goal"]);
        new_goals.push_back(ng && unchanged ? *ng : it->second.goal);
      }
    }
    emit_moves(ids, next);
    // the daemon's returned post-swap goals re-assign tasks exactly like
    // the native path (adopt_goal_exchanges)
    adopt_goal_exchanges(ids, old_goals, new_goals);
  };

  auto save_csv = [&](const std::string& path, const std::string& content) {
    std::ofstream out(path);
    if (!out) {
      log_warn("⚠️  cannot write %s\n", path.c_str());
      return;
    }
    out << content;
    log_info("💾 saved %s\n", path.c_str());
  };

  auto handle_command = [&](const std::string& line) -> bool {
    std::istringstream in(line);
    std::string cmd;
    in >> cmd;
    if (cmd == "quit" || cmd == "exit") return false;
    if (ha_on && ha_role_standby
        && (cmd == "task" || cmd == "tasks" || cmd == "taskat")) {
      // operator load arriving at a warm standby (a replay driver
      // re-routing around a dead active): deferred, drained at
      // promotion — a standby must never mint or queue tasks itself.
      // Past the cap the line is DROPPED — loudly: the counter + log
      // are the only way a judge's "missing task" traces back here.
      if (ha_deferred_cmds.size() < 10000) {
        ha_deferred_cmds.push_back(line);
      } else {
        metrics_count("manager.ha_deferred_dropped");
        log_warn("⚠️  standby deferred-command queue full; dropping "
                 "operator line: %s\n", line.c_str());
      }
      return true;
    }
    if (cmd == "task") {
      queue_task();
      try_assign_pending();
    } else if (cmd == "tasks") {
      size_t n = 0;
      in >> n;
      if (!n) n = agents.size();
      for (size_t k = 0; k < n; ++k) queue_task();
      try_assign_pending();
      log_info("📦 queued %zu tasks (%zu pending)\n", n, pending_tasks.size());
    } else if (cmd == "taskat") {
      // replay injection (ISSUE 11): queue a task with EXPLICIT
      // endpoints and (optionally) an explicit id, so a captured
      // window re-drives as a deterministic load instead of a fresh
      // rng sample.  fleetsim --replay writes these lines.
      long long px = -1, py = -1, dx = -1, dy = -1, id = -1;
      in >> px >> py >> dx >> dy;
      if (!(in >> id)) id = -1;
      if (!grid.in_bounds(static_cast<int>(px), static_cast<int>(py)) ||
          !grid.in_bounds(static_cast<int>(dx), static_cast<int>(dy))) {
        log_warn("⚠️  taskat: out-of-bounds (%lld,%lld)->(%lld,%lld)\n",
                 px, py, dx, dy);
        metrics_count("manager.taskat_rejected");
      } else {
        if (id >= 0)
          bump_task_id_past(static_cast<uint64_t>(id));
        uint64_t tid;
        if (id >= 0) {
          tid = static_cast<uint64_t>(id);
        } else {
          tid = next_task_id;
          next_task_id += task_id_stride;
        }
        Json t;
        t.set("pickup", point_json(grid.cell(static_cast<int>(px),
                                             static_cast<int>(py))))
            .set("delivery", point_json(grid.cell(static_cast<int>(dx),
                                                  static_cast<int>(dy))))
            .set("peer_id", Json())
            .set("task_id", static_cast<int64_t>(tid));
        if (tctx) {
          codec::TraceCtx t0{trace_epoch | static_cast<long long>(tid), 0,
                             unix_ms()};
          event_emit("task.queue", &t0, static_cast<long long>(tid));
        }
        pending_tasks.push_back(std::move(t));
        try_assign_pending();
      }
    } else if (cmd == "metrics") {
      log_info("%s\n", task_metrics.statistics().to_string().c_str());
      if (auto ps = path_metrics.statistics())
        log_info("%s\n", ps->to_string().c_str());
      log_info("%s\n",
               MetricsRegistry::instance().network_summary_string().c_str());
      // live registry dump (Prometheus text): ticks, cache, per-topic bytes
      log_info("%s", MetricsRegistry::instance().expose_text().c_str());
    } else if (cmd == "save") {
      std::string a, b;
      in >> a >> b;
      if (a == "path")
        save_csv(b.empty() ? "path_metrics.csv" : b,
                 path_metrics.to_csv_string());
      else
        save_csv(a.empty() ? "task_metrics.csv" : a,
                 task_metrics.to_csv_string());
    } else if (cmd == "reset") {
      task_metrics.clear();
      path_metrics.clear();
      pending_tasks.clear();
      for (auto& [peer, a] : agents) {
        a.task.reset();
        a.phase = Phase::None;
        a.goal = a.pos;
      }
      log_info("🔄 state reset\n");
    } else if (!cmd.empty()) {
      Json raw;
      raw.set("raw", line);
      bus.publish("mapd", raw);
    }
      return true;
  };

  int64_t last_plan = 0, last_cleanup = mono_ms(), last_audit = 0;
  std::string stdin_buf;
  bool running = true;

  while (running && !g_stop && bus.connected()) {
    // poll every shard link plus stdin (stdin stays LAST in the vector)
    std::vector<pollfd> pfds;
    bus.append_pollfds(pfds);
    pfds.push_back({STDIN_FILENO, POLLIN, 0});
    poll(pfds.data(), static_cast<nfds_t>(pfds.size()), 100);

    if (pfds.back().revents & POLLIN) {
      char buf[4096];
      ssize_t n = read(STDIN_FILENO, buf, sizeof(buf));
      if (n > 0) {
        stdin_buf.append(buf, static_cast<size_t>(n));
        size_t nl;
        while ((nl = stdin_buf.find('\n')) != std::string::npos) {
          std::string line = stdin_buf.substr(0, nl);
          stdin_buf.erase(0, nl + 1);
          if (!handle_command(line)) {
            running = false;
            break;
          }
        }
      } else if (n == 0) {
        running = false;
      }
    }

    bool alive = bus.pump(
        [&](const BusClient::Msg& m) {
          const Json& d = m.data;
          const std::string& type = d["type"].as_str();
          // HA plane first (ISSUE 15): ha frames are handled in either
          // role; everything else is IGNORED while standby — a warm
          // replica must never ingest fleet traffic it cannot act on
          // (its subscriptions are ha-only anyway; this also covers
          // the demoted-active case, whose old subscriptions remain)
          if (ha_on && ha_handle_frame(d)) return;
          if (ha_on && ha_role_standby) return;
          if (type == "position_update" || type == "pos1") {
            // one heartbeat ingestion for both wires: flat JSON
            // position_update and the packed pos1 region beacon (which is
            // addressed by the bus frame's own `from`).  A MULTIPLEXED
            // client (analysis/fleetsim.py simulates thousands of agents
            // over one connection) puts the agent identity in an optional
            // envelope `peer_id` instead — it wins over `from` when
            // present; real per-process agents never set it.
            std::string peer;
            std::optional<Cell> p;
            bool has_busy = false;
            long long busy_tid = 0;
            if (type == "pos1") {
              auto p1 = codec::decode_pos1_b64(d["data"].as_str());
              if (!p1) return;
              peer = d.has("peer_id") ? d["peer_id"].as_str() : m.from;
              if (p1->pos >= 0 &&
                  p1->pos < static_cast<Cell>(grid.free.size()))
                p = p1->pos;
              has_busy = p1->has_task;
              busy_tid = p1->task_id;
              // busy-claim heartbeats carry their task's trace1 block:
              // per-hop one-way latency only (no event — beacon rate)
              if (tctx && p1->has_trace)
                hop_latency_ms(p1->trace.send_ms, "task.claim_hb");
            } else {
              peer = d["peer_id"].as_str();
              p = parse_point(d["position"]);
              has_busy = d.has("busy_task");
              busy_tid = d["busy_task"].as_int();
              if (tctx)
                if (auto t = tc_parse(d))
                  hop_latency_ms(t->send_ms, "task.claim_hb");
            }
            if (clean && known_left.count(peer)) return;
            if (!p) return;
            auto it = agents.find(peer);
            if (it == agents.end()) {
              // post-takeover restore (ISSUE 15): this agent's
              // in-flight task rode the replication stream — reattach
              // it instead of adopting the agent idle, which would
              // hand it a SECOND task while it works the first.  The
              // normal idle-but-busy reconciliation then re-sends the
              // task if the agent actually lost its copy.
              auto rst = ha_restore_task.find(peer);
              if (rst != ha_restore_task.end()) {
                AgentInfo a;
                a.pos = *p;
                a.last_seen_ms = mono_ms();
                a.dispatched_ms = mono_ms();
                a.task = rst->second;
                auto ph = ha_restore_phase.find(peer);
                a.phase = (ph != ha_restore_phase.end())
                              ? ph->second : Phase::ToPickup;
                auto cell = parse_point((*a.task)[
                    a.phase == Phase::ToDelivery ? "delivery"
                                                 : "pickup"]);
                a.goal = cell ? *cell : *p;
                agents[peer] = a;
                ha_restore_task.erase(rst);
                ha_restore_phase.erase(peer);
                metrics_count("manager.ha_restored_lanes");
                log_info("🔗 HA restore: %s re-attached to task %lld\n",
                         peer.c_str(),
                         (*agents[peer].task)["task_id"].as_int());
                try_assign_pending();
                return;
              }
              if (fed_on) {
                // ownership (ISSUE 14): adopt only agents standing in
                // OUR region; a foreign agent in the border strip
                // becomes a stationary mirror lane instead (boundary
                // planning correctness), anything further is not ours.
                // A peer in transfer limbo (unacked outbound handoff)
                // is never re-adopted — the neighbor owns it the moment
                // the record applies.
                if (handing_off.count(peer)) return;
                const int x = grid.x_of(*p), y = grid.y_of(*p);
                if (fed.region_of(grid.width, grid.height, x, y)
                    != region_id) {
                  claim_candidates.erase(peer);
                  if (FedMap::in_border(x, y, my_rect, fed_border))
                    mirror_touch(peer, *p);
                  else
                    mirrors.erase(peer);
                  return;
                }
                if (!fed_claimable(x, y)) {
                  // inside our rect but within a neighbor's hysteresis
                  // reach: possibly still the neighbor's.  Wait for its
                  // handoff — or the unclaimed grace — before adopting
                  // (mirror it meanwhile so planning routes around it).
                  const int64_t now2 = mono_ms();
                  auto [cit, fresh] =
                      claim_candidates.emplace(peer, now2);
                  if (fresh || now2 - cit->second < claim_grace_ms) {
                    mirror_touch(peer, *p);
                    return;
                  }
                  metrics_count("manager.fed_grace_adoptions");
                }
                claim_candidates.erase(peer);
                mirrors.erase(peer);
              }
              AgentInfo a;
              a.pos = a.goal = *p;
              a.last_seen_ms = mono_ms();
              agents[peer] = a;
              log_info("🔍 tracking agent %s (%zu)\n", peer.c_str(),
                       agents.size());
              try_assign_pending();
            } else {
              AgentInfo& a = it->second;
              a.pos = *p;
              a.last_seen_ms = mono_ms();
              if (fed_on) {
                // hysteresis escape: hand the lane (and its task) to
                // the region the agent now stands in — only once it is
                // MORE than the margin outside ours, so border
                // oscillation never thrashes ownership
                const int x = grid.x_of(*p), y = grid.y_of(*p);
                if (FedMap::escaped(x, y, my_rect, fed_hyst)) {
                  const int dst =
                      fed.region_of(grid.width, grid.height, x, y);
                  if (dst != region_id) {
                    send_handoff(peer, a, dst);
                    agents.erase(it);
                    return;
                  }
                }
              }
              if (!a.task) a.goal = *p;
              // idle-but-marked-busy reconciliation: the heartbeat carries
              // a busy_task field while the agent holds a task; still-idle
              // (or still on a DIFFERENT task — an exchanged-task
              // re-broadcast can be lost too) well past dispatch means the
              // Task publish was dropped — re-send the SAME task.  A lost
              // DONE instead heals via the agent's retransmit (which also
              // refuses this duplicate by task id).
              bool stale_assignment =
                  a.task
                  && (!has_busy
                      || busy_tid != (*a.task)["task_id"].as_int());
              if (stale_assignment
                  && mono_ms() - a.dispatched_ms > task_resend_ms) {
                log_info("↻ %s reports idle but task %lld is in flight; "
                         "re-sending\n", peer.c_str(),
                         static_cast<long long>(
                             (*a.task)["task_id"].as_int()));
                if (tctx) {
                  long long tid = (*a.task)["task_id"].as_int();
                  auto t = hops.next(tid);
                  a.task->set("tc", tc_json(t));
                  event_emit("task.resend", &t, tid, peer);
                }
                bus.publish("mapd", *a.task);
                a.dispatched_ms = mono_ms();
              }
            }
          } else if (type == "plan_response") {
            handle_plan_response(d);
          } else if (type == "plan_snapshot_request") {
            // solverd lost the delta chain (restart, dropped packet): the
            // next planning tick re-sends the full fleet state
            plan_enc.request_snapshot();
            metrics_count("manager.plan_snapshot_requests");
            log_info("🔁 solver daemon requested a plan snapshot "
                     "(its chain ends at seq %lld)\n",
                     static_cast<long long>(d["have_seq"].as_int()));
            if (dynamic_world && !world_state.empty()) {
              // world replay (ISSUE 10): a resyncing daemon may have
              // restarted with the ORIGINAL map — re-send every
              // accumulated toggle at the current epoch so its grid
              // (and world_seq, which it adopts from the frame)
              // reconverges with the planner of record
              std::vector<int32_t> cells, blocked;
              for (const auto& [c, b] : world_state) {
                cells.push_back(c);
                blocked.push_back(b);
              }
              publish_world_update(cells, blocked, /*to_mapd=*/false);
              metrics_count("manager.world_replays");
              log_info("🌍 replayed %zu accumulated world toggle(s) at "
                       "epoch %lld with the snapshot\n",
                       cells.size(), static_cast<long long>(world_seq));
            }
          } else if (type == "task_metric_received") {
            task_metrics.update_received(
                static_cast<uint64_t>(d["task_id"].as_int()),
                d["timestamp_ms"].as_int());
          } else if (type == "task_metric_started") {
            task_metrics.update_started(
                static_cast<uint64_t>(d["task_id"].as_int()),
                d["timestamp_ms"].as_int());
          } else if (type == "task_metric_completed") {
            const uint64_t tid = static_cast<uint64_t>(d["task_id"].as_int());
            task_metrics.update_completed(tid, d["timestamp_ms"].as_int());
            // live task-latency histogram for the fleet rollup (beacons)
            auto itm = task_metrics.metrics.find(tid);
            if (itm != task_metrics.metrics.end())
              if (auto t = itm->second.total_time())
                metrics_observe("task.total_time_ms",
                                static_cast<double>(*t));
          } else if (type == "world_update_request") {
            handle_world_request(d);
          } else if (type == "audit_drill_request") {
            handle_drill(d);
          } else if (type == "flight_dump") {
            // black-box query: dump the ring and answer with the path
            bus.publish("mapd",
                        flight_dump_answer("manager_centralized", my_id));
          } else if (fed_on && type.empty() && d.has("task_id")
                     && d.has("pickup") && d.has("delivery")
                     && !d["peer_id"].as_str().empty()) {
            // ownership-conflict arbitration (ISSUE 14): every manager
            // hears every task dispatch on "mapd".  One naming an
            // agent WE track, carrying a task our ledger cannot
            // explain, means another region claimed the agent (grace
            // adoption of a band-dweller, beacon races).  The agent's
            // POSITION arbitrates — both sides apply the same rule to
            // the same beacons, so exactly one yields: if it stands
            // outside our rectangle we RELEASE it (our in-flight task
            // re-queues locally; at-least-once, the done path dedups),
            // if inside we keep it and the dispatcher releases when it
            // hears OUR next dispatch/re-send.  Without this a
            // band-dwelling agent collects conflicting tasks from two
            // planners and both ledgers wedge (found live by the 2x2
            // ladder).
            const std::string peer = d["peer_id"].as_str();
            auto it = agents.find(peer);
            if (it == agents.end()) return;
            const long long tid = d["task_id"].as_int();
            bool known =
                it->second.task
                && (*it->second.task)["task_id"].as_int() == tid;
            if (!known)
              for (const auto& q : pending_tasks)
                if (q["task_id"].as_int() == tid) {
                  known = true;
                  break;
                }
            if (known || requeued_ids.count(tid)
                || completed_ids.count(tid))
              return;
            const int x = grid.x_of(it->second.pos);
            const int y = grid.y_of(it->second.pos);
            if (x >= my_rect.x0 && x < my_rect.x1 && y >= my_rect.y0 &&
                y < my_rect.y1)
              return;  // standing in OUR rect: the other side yields
            requeue_task(peer, it->second,
                         "ownership conflict, releasing");
            metrics_count("manager.fed_conflict_releases");
            agents.erase(it);
            try_assign_pending();
          } else if (type == "handoff1") {
            // ---- cross-region handoff, inbound (ISSUE 14) ----
            if (!fed_on || static_cast<int>(d["dst"].as_int()) != region_id)
              return;
            const int src = static_cast<int>(d["src"].as_int());
            const int64_t hseq = d["seq"].as_int();
            const int64_t hepoch = d["epoch"].as_int();
            Json ack;
            ack.set("type", "handoff_ack")
                .set("src", static_cast<int64_t>(src))
                .set("dst", static_cast<int64_t>(region_id))
                .set("seq", hseq)
                .set("epoch", hepoch)  // sender matches its own epoch
                .set("peer_id", d["peer_id"]);
            // per-epoch dedup sets (ISSUE 15): each sender incarnation
            // owns its own seq chain.  A promoted standby retransmits
            // its dead active's OLD-epoch records while minting new
            // ones under its own epoch — both must dedup against their
            // own chain (the old reset-on-newer-epoch rule would
            // strand the restored retransmits: dropped as stale, never
            // acked, agent in limbo forever).
            auto& epochs_seen = handoff_applied[src];
            auto& seen = epochs_seen[hepoch];
            while (epochs_seen.size() > 4) {
              // bound: keep the newest epochs, never evicting the one
              // this frame just landed in
              auto oldest = epochs_seen.begin();
              if (oldest->first == hepoch) ++oldest;
              epochs_seen.erase(oldest);
            }
            if (seen.count(hseq)) {
              // replayed/retransmitted record: ack again (its ack was
              // lost), NEVER re-apply — a duplicate handoff must not
              // double-admit the lane or double-dispatch its task
              metrics_count("manager.handoffs_dup_dropped");
              bus.publish(FedMap::fed_topic(src), ack);
              return;
            }
            auto pkt = codec::decode_b64(d["data"].as_str());
            std::optional<codec::HandoffRec> rec;
            if (pkt) rec = codec::decode_handoff(*pkt);
            const Cell cells = static_cast<Cell>(grid.free.size());
            if (!rec || rec->pos < 0 || rec->pos >= cells ||
                (rec->has_task &&
                 (rec->pickup < 0 || rec->pickup >= cells ||
                  rec->delivery < 0 || rec->delivery >= cells))) {
              // malformed record: counted, NOT acked — the sender keeps
              // retransmitting and the counter names the problem
              metrics_count("manager.bad_handoffs");
              return;
            }
            seen.insert(hseq);
            while (seen.size() > 8192) seen.erase(seen.begin());
            const std::string hpeer = rec->peer;
            AgentInfo a;
            a.pos = static_cast<Cell>(rec->pos);
            a.goal = (rec->goal >= 0 && rec->goal < cells)
                         ? static_cast<Cell>(rec->goal) : a.pos;
            a.last_seen_ms = mono_ms();
            a.dispatched_ms = mono_ms();
            if (rec->has_task) {
              Json t;
              t.set("pickup", point_json(static_cast<Cell>(rec->pickup)))
                  .set("delivery",
                       point_json(static_cast<Cell>(rec->delivery)))
                  .set("peer_id", hpeer)
                  .set("task_id", rec->task_id);
              a.task = t;
              a.phase = rec->phase == 2 ? Phase::ToDelivery
                                        : Phase::ToPickup;
              // the ledger entry moves WITH the lane: metrics and the
              // audit ledger digest now account for it here
              TaskMetric m;
              m.task_id = static_cast<uint64_t>(rec->task_id);
              m.peer_id = hpeer;
              m.sent_time = unix_ms();
              task_metrics.add_metric(m);
              if (rec->task_id >= 0)
                bump_task_id_past(static_cast<uint64_t>(rec->task_id));
            }
            known_left.erase(hpeer);  // --clean must re-track a handoff
            mirrors.erase(hpeer);
            claim_candidates.erase(hpeer);
            // ownership-race merge: we may already track this agent
            // (adopted from its beacons before the neighbor's record
            // arrived).  The LEDGER ENTRY is what must never be lost
            // or doubled:
            //  - incoming carries a DIFFERENT task: our local
            //    assignment RE-QUEUES (never silently clobbered — that
            //    loses it from every ledger; found by the smoke's
            //    exact-once accounting) and the neighbor's state wins;
            //  - incoming carries the SAME task, or NO task while we
            //    hold one: our record is fresher (pickup flips and
            //    goal exchanges happened HERE) — keep it, just ack.
            auto prev = agents.find(hpeer);
            if (prev != agents.end() && prev->second.task) {
              const long long ptid =
                  (*prev->second.task)["task_id"].as_int();
              if (rec->has_task && ptid != rec->task_id) {
                requeue_task(hpeer, prev->second, "handoff displaced");
              } else {
                prev->second.last_seen_ms = mono_ms();
                metrics_count("manager.handoffs_received");
                bus.publish(FedMap::fed_topic(src), ack);
                return;
              }
            }
            agents[hpeer] = a;
            handoff_admitted[hpeer] = mono_ms() + 10000;
            metrics_count("manager.handoffs_received");
            bus.publish(FedMap::fed_topic(src), ack);
            log_info("🛬 handoff %lld from region %d: adopted %s%s\n",
                     static_cast<long long>(hseq), src, hpeer.c_str(),
                     rec->has_task ? " (with task)" : "");
            try_assign_pending();
          } else if (type == "handoff_ack") {
            if (!fed_on || static_cast<int>(d["src"].as_int()) != region_id)
              return;
            auto key = std::make_pair(
                static_cast<int>(d["dst"].as_int()), d["seq"].as_int());
            auto hit = handoff_unacked.find(key);
            // the ack must echo the RECORD's own epoch — an ack for
            // another incarnation's record (same seq, different
            // lane/task) must not cancel this one.  Judged per record,
            // not against the process-global fed_epoch: a promoted
            // standby's restored outbox entries keep their ORIGINAL
            // epoch (ISSUE 15) and their acks must still land.
            if (hit != handoff_unacked.end()
                && d["epoch"].as_int()
                       != hit->second.frame["epoch"].as_int())
              return;
            if (hit != handoff_unacked.end()) {
              handing_off.erase(hit->second.peer);
              handoff_unacked.erase(hit);
              metrics_count("manager.handoffs_acked");
              metrics_gauge("manager.fed_pending_handoffs",
                            static_cast<double>(handoff_unacked.size()));
            }
          } else if (d["status"].as_str() == "done") {
            // same multiplexed-client accommodation as the heartbeat
            // path: an explicit payload peer_id outranks the frame from
            const std::string peer =
                d.has("peer_id") ? d["peer_id"].as_str() : m.from;
            const long long tid = d["task_id"].as_int();
            // post-takeover hold entries (ISSUE 15): a done for a task
            // still in the restore set completes it — the agent
            // finished during the outage without re-beaconing first.
            // The entry must leave the hold set (the hold expiry would
            // otherwise re-queue a completed task) but still counts as
            // ledger-known below.
            bool ha_restore_known = false;
            for (auto rit = ha_restore_task.begin();
                 rit != ha_restore_task.end(); ++rit) {
              if (rit->second["task_id"].as_int() == tid) {
                ha_restore_known = true;
                ha_restore_phase.erase(rit->first);
                ha_restore_task.erase(rit);
                break;
              }
            }
            if (fed_on) {
              // ownership (ISSUE 14): every region manager hears
              // "mapd", so only the region whose LEDGER knows the task
              // may count a done — anything else either acks without
              // counting (we track the reporter: quiet its retransmit;
              // the region of record dedups and counts) or ignores the
              // frame outright.  An agent mid-handoff keeps
              // retransmitting until the new owner applies the record
              // and answers — the retransmit heals the limbo window.
              // owner-first short-circuit: the common case is the
              // reporter's own region hearing its own done — one map
              // lookup.  The linear fallbacks below run only on
              // foreign frames and are bounded by max_agents (500) and
              // the pending deque; fine at done rates, and an
              // inflight-id index is the scaling follow-up if a
              // many-region profile ever shows them.
              auto rit = agents.find(peer);
              bool task_known = ha_restore_known
                  || (rit != agents.end() && rit->second.task
                      && (*rit->second.task)["task_id"].as_int() == tid)
                  || completed_ids.count(tid) || requeued_ids.count(tid);
              if (!task_known)
                for (const auto& q : pending_tasks)
                  if (q["task_id"].as_int() == tid) {
                    task_known = true;
                    break;
                  }
              if (!task_known)
                for (const auto& [ap, aa] : agents)
                  if (aa.task && (*aa.task)["task_id"].as_int() == tid) {
                    task_known = true;
                    break;
                  }
              // unknown task: IGNORE outright — even when we track the
              // reporter.  Acking here would clear the agent's
              // unacked_done and silence the retransmit that is the
              // region of record's only heal if ITS copy of the frame
              // was dropped (per-subscriber slow-consumer eviction);
              // the owner hears a later retransmit and acks it itself.
              if (!task_known) return;
            } else if (ha_promoted) {
              // exact-once across a takeover (ISSUE 15): only
              // ledger-known ids count.  An unknown id is a
              // pre-takeover completion whose ack died with the old
              // active — ACK it (quieting the agent's retransmit; we
              // ARE the region of record now, nobody else will) but
              // never count it, or the system-of-record completion
              // counter would read a double completion.
              bool known = ha_restore_known
                  || completed_ids.count(tid)
                  || requeued_ids.count(tid);
              if (!known) {
                auto kit = agents.find(peer);
                known = kit != agents.end() && kit->second.task
                    && (*kit->second.task)["task_id"].as_int() == tid;
              }
              if (!known)
                for (const auto& q : pending_tasks)
                  if (q["task_id"].as_int() == tid) {
                    known = true;
                    break;
                  }
              if (!known)
                for (const auto& [ap, aa] : agents)
                  if (aa.task
                      && (*aa.task)["task_id"].as_int() == tid) {
                    known = true;
                    break;
                  }
              if (!known) {
                Json ack;
                ack.set("type", "done_ack").set("peer_id", peer)
                    .set("task_id", Json(static_cast<int64_t>(tid)));
                bus.publish("mapd", ack);
                metrics_count("manager.ha_unknown_done_acked");
                return;
              }
            }
            auto done_tc = tc_parse(d);
            if (done_tc) {
              hops.seen(tid, *done_tc);
              event_emit("task.done", &*done_tc, tid, peer,
                         done_tc->send_ms);
            }
            // ack unconditionally: agents retransmit done until acked, and
            // a duplicate (its ack was lost) must still be acked
            Json ack;
            ack.set("type", "done_ack").set("peer_id", peer)
                .set("task_id", Json(static_cast<int64_t>(tid)));
            if (tctx && done_tc) {
              auto t = hops.next(tid);
              ack.set("tc", tc_json(t));
            }
            bus.publish("mapd", ack);
            auto it = agents.find(peer);
            if (it != agents.end() && it->second.task
                && (*it->second.task)["task_id"].as_int() == tid) {
              it->second.task.reset();
              it->second.phase = Phase::None;
              it->second.goal = it->second.pos;
            }
            if (completed_ids.count(tid)) {
              // second completion of a re-dispatched task (at-least-once
              // re-queue): counted once already — free the reporter and
              // keep it in the work loop, but don't count the duplicate
              log_warn("⚠️  duplicate done for task %lld (%s) ignored\n",
                       tid, peer.c_str());
              // only refill a task-FREE reporter: a late done for an old
              // task (original agent of a requeued task reporting after
              // re-dispatch) must not overwrite an in-flight assignment
              if (it != agents.end() && !it->second.task
                  && pending_tasks.empty() && !open_loop)
                assign_task(peer, make_task());
              try_assign_pending();
            } else {
              if (requeued_ids.erase(tid)) {
                // the presumed-dead agent finished after all: cancel the
                // queued duplicate if it is still pending.  The id goes
                // into completed_ids EITHER WAY — the task may have been
                // re-queued more than once (another copy already
                // dispatched, or re-queued again later), and any
                // subsequent done for it must dedupe.
                completed_ids.insert(tid);
                for (auto q = pending_tasks.begin();
                     q != pending_tasks.end(); ++q)
                  if ((*q)["task_id"].as_int() == tid) {
                    pending_tasks.erase(q);
                    log_info("♻️  task %lld done by its original agent; "
                             "queued duplicate cancelled\n", tid);
                    break;
                  }
              }
              log_info("🎉 %s finished task %lld\n", peer.c_str(), tid);
              // counted on the DEDUPED path only: a retransmitted or
              // double-completed done never inflates the fleet tasks/s
              metrics_count("manager.tasks_completed");
              // auto-reassign on completion (ref :908-950): queued tasks
              // (incl. ones re-queued from dead agents) drain before a fresh
              // task is generated, so orphans cannot starve behind auto-refill
              // guarded on !task for the same late-duplicate-done reason
              // as the branch above: never clobber an in-flight assignment
              if (it != agents.end() && !it->second.task
                  && pending_tasks.empty() && !open_loop)
                assign_task(peer, make_task());
              try_assign_pending();
            }
          }
                },
        [&](const Json& ev) {
          if (ev["op"].as_str() == "peer_left") {
            const std::string& peer = ev["peer_id"].as_str();
            known_left.insert(peer);
            mirrors.erase(peer);  // a dead foreign agent stops mirroring
            auto it = agents.find(peer);
            if (it != agents.end()) {
              // The task restarts from pickup on the next idle agent.
              requeue_task(peer, it->second, "agent died:");
              agents.erase(it);
              try_assign_pending();
                        }
          }
        });
    if (!alive) break;

    int64_t now = mono_ms();
    if (now - last_plan >= planning_ms) {  // planning tick (ref :675-724)
      Span sp("manager.plan_tick",
              "\"agents\":" + std::to_string(agents.size()));
      trace_count("manager.plan_ticks");
      auto tick_t0 = std::chrono::steady_clock::now();
      last_plan = now;
      // roll the move-target protection window (last two ticks)
      prev_move_targets = std::move(recent_move_targets);
      recent_move_targets.clear();
      if (fed_on) {
        // expire border mirrors whose beacons stopped (the agent left
        // the strip, died, or crossed in and got adopted)
        for (auto mit = mirrors.begin(); mit != mirrors.end();) {
          if (now - mit->second.last_seen > mirror_expire_ms ||
              agents.count(mit->first) || handing_off.count(mit->first))
            mit = mirrors.erase(mit);
          else
            ++mit;
        }
        metrics_gauge("manager.fed_mirrors",
                      static_cast<double>(mirrors.size()));
      }
      pickup_transitions();
      if (!agents.empty()) {
        if (solver == "tpu") {
          // keep requesting so a revived daemon ends the failover, but
          // plan natively while it is silent — the fleet must keep moving
          // (the reference has no comparable resilience path)
          plan_request_tpu();
          if (now - last_plan_response > solver_failover_ms) {
            if (!failed_over) {
              failed_over = true;
              trace_count("manager.solver_failovers");
              // always-on too: chip_smoke.py reads it off the beacon to
              // prove a "tpu" fleet never planned natively
              metrics_count("manager.solver_failovers");
              trace_instant("manager.solver_failover");
              log_warn("⚠️  solver daemon silent for %lld ms; planning "
                       "natively until it responds\n",
                       static_cast<long long>(now - last_plan_response));
            }
            plan_native();
          }
        } else {
          plan_native();
        }
      }
      // live tick accounting (registry, always on): p50/p95 vs the
      // planning budget in the fleet rollup.  In tpu mode this covers
      // only the host-side encode — the daemon's own tick_ms rides its
      // beacon — so the number is honest either way.
      double tick_ms_taken =
          std::chrono::duration<double, std::milli>(
              std::chrono::steady_clock::now() - tick_t0)
              .count();
      metrics_observe("tick_ms", tick_ms_taken);
      if (tick_ms_taken > static_cast<double>(planning_ms))
        metrics_count("tick.over_budget");
      metrics_gauge("tick.agents", static_cast<double>(agents.size()));
      // queue-depth gauge (ISSUE 16): dispatch is capacity-gated (a
      // task leaves pending_tasks only when an agent frees up), so the
      // dispatched/completed counter pair can never show an overload —
      // the backlog here is the fleet's actual pressure signal, the
      // one the health plane forecasts over
      metrics_gauge("manager.tasks_pending",
                    static_cast<double>(pending_tasks.size()));
    }
    if (audit_on && now - last_audit >= audit_interval_ms) {
      last_audit = now;
      publish_audit_beacon();
    }
    if (fed_on && now - last_handoff_retry >= handoff_retry_ms) {
      // retransmit-until-ack: a lost handoff (or lost ack) heals here;
      // the receiver's dedup guard makes the replay harmless
      last_handoff_retry = now;
      for (auto& [key, out] : handoff_unacked) {
        if (now - out.last_send_ms >= handoff_retry_ms) {
          bus.publish(FedMap::fed_topic(out.dst), out.frame);
          out.last_send_ms = now;
          metrics_count("manager.handoff_retransmits");
        }
      }
    }
    if (ha_on) {
      if (!ha_role_standby) {
        // active: renew the lease and ship the replication stream
        if (now - last_ha_lease >= ha_lease_ms) {
          last_ha_lease = now;
          ha_publish_lease();
        }
        // per-iteration write-ahead flush: a pending dispatch forces
        // it immediately; otherwise the (cheap, diff-only) replication
        // check runs on a short cadence
        if (!ha_task_outbox.empty() || now - last_ha_repl >= 50) {
          last_ha_repl = now;
          ha_flush();  // record first, then the deferred Task frames
        }
        if (ha_hold_until && now >= ha_hold_until) {
          // sweep-hold expiry: an in-flight entry whose agent never
          // reported inside one claim window re-queues AT-LEAST-ONCE
          // (its agent may still finish; the done path dedups by id)
          for (auto& [peer, tj] : ha_restore_task) {
            const long long tid = tj["task_id"].as_int();
            Json t = tj;
            t.set("peer_id", Json());
            requeued_ids.insert(tid);
            pending_tasks.push_front(std::move(t));
            metrics_count("manager.ha_hold_requeues");
            log_info("♻️  HA hold expired: task %lld of silent agent "
                     "%s re-queued\n", tid, peer.c_str());
          }
          ha_restore_task.clear();
          ha_restore_phase.clear();
          ha_hold_until = 0;
          try_assign_pending();
        }
        if (ha_drain_cmds) {
          // operator lines deferred while standby (replay taskat) run
          // now that we ARE the system of record
          ha_drain_cmds = false;
          std::deque<std::string> lines;
          lines.swap(ha_deferred_cmds);
          for (const auto& line : lines) handle_command(line);
        }
      } else {
        // standby: judge the active's lease by the auditor's
        // silent-peer rule; a cold start with NO active ever heard
        // promotes after a longer grace (nobody owns the region)
        if (ha_lease_last
            && ha::lease_expired(now, ha_lease_last,
                                 ha_lease_interval)) {
          metrics_count("manager.ha_lease_expiries");
          log_warn("⚠️  HA lease expired: active %s (incarnation %lld) "
                   "silent %lld ms — taking over\n",
                   ha_active_peer.c_str(),
                   static_cast<long long>(ha_active_inc),
                   static_cast<long long>(now - ha_lease_last));
          ha_promote("lease_expired");
        } else if (!ha_lease_last
                   && now - ha_started > 6 * ha_lease_ms + 3000) {
          log_warn("⚠️  HA cold start: no active ever announced — "
                   "claiming the region\n");
          ha_promote("cold_start");
        }
        if (ha_role_standby && ha_need_resync
            && now - ha_last_resync_req > 1000) {
          ha_last_resync_req = now;
          Json f;
          f.set("type", "ha_resync_request")
              .set("ns", audit_ns)
              .set("peer_id", my_id)
              .set("incarnation", ha_incarnation)
              .set("have_seq", ha_rep.seq);
          bus.publish(ha::kHaTopic, f, /*raw=*/true);
        }
      }
    }
    if (now - last_cleanup > cleanup_ms) {
      last_cleanup = now;
      // Stale age-out re-queues in-flight tasks just like peer_left does: a
      // live-but-silent agent never emits peer_left, and its task must not
      // be lost on this path either.  This is AT-LEAST-ONCE delivery: the
      // silent agent may still be alive (e.g. a transient bus stall) and
      // finish the task anyway — the done handler dedupes by task_id
      // (requeued_ids/completed_ids), cancelling the queued duplicate or
      // counting a double completion once.  The cap trim below deliberately
      // does NOT re-queue — it evicts agents that are still live and
      // working, so re-dispatching their task would run it twice.
      for (auto it = agents.begin(); it != agents.end();) {
        if (now - it->second.last_seen_ms > agent_stale_ms) {
          requeue_task(it->first, it->second, "evicting stale agent");
          it = agents.erase(it);
        } else {
          ++it;
        }
      }
      // Cap trim: only IDLE agents are eligible — evicting a live busy
      // agent would either lose its task (it re-registers task-less on the
      // next heartbeat and gets a second assignment) or duplicate it (if
      // re-queued while the agent keeps working).  If everyone is busy the
      // cap is soft: warn and keep them until tasks complete.
      while (agents.size() > max_agents) {
        auto oldest = agents.end();
        for (auto it = agents.begin(); it != agents.end(); ++it)
          if (!it->second.task
              && (oldest == agents.end()
                  || it->second.last_seen_ms < oldest->second.last_seen_ms))
            oldest = it;
        if (oldest == agents.end()) {
          log_warn("⚠️  %zu agents exceed cap %zu but all are busy; "
                   "deferring trim\n", agents.size(), max_agents);
          break;
        }
        agents.erase(oldest);
      }
      while (known_left.size() > max_known_peers)
        known_left.erase(known_left.begin());
      try_assign_pending();
      dc.trim(512);
      trace_flush();  // bounded ring: the 30 s cleanup cadence drains it
      log_info("🧹 [CLEANUP] agents=%zu pending=%zu\n", agents.size(),
               pending_tasks.size());
        }
  }

  if (const char* p = getenv("TASK_CSV_PATH"))
    save_csv(p, task_metrics.to_csv_string());
  if (const char* p = getenv("PATH_CSV_PATH"))
    save_csv(p, path_metrics.to_csv_string());
  log_info("%s\n", task_metrics.statistics().to_string().c_str());
  trace_flush();
  log_info("manager: bye\n");
  bus.close();
  return 0;
}
