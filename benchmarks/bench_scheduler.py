"""Scheduler micro-benchmarks: decision latency (us/call) vs queue depth.

The paper's constraint: action durations go down to ~1 ms, so the
scheduling window is tiny; Table 1 attributes <3% overhead to the
system.  This harness measures the Python control-plane directly:

* ``schedule_*``     — one cold full reschedule per call, measured for
  both the dense vectorized DPArrange (default) and the dict-based
  reference DP (``*_ref`` rows), plus a ``*_dense_speedup`` ratio;
* ``churn_*``        — steady-state churn against a WARM orchestrator
  (interleaved submissions + completions), incremental rounds vs full
  rescheduling, reporting per-event decision latency and the speedup;
* ``shard_churn_*``  — synchronized fleet churn (many pools dirty per
  round), the serial round loop vs the sharded plan/commit engine
  (``--shards N``): critical-path decision latency, speedup, and the
  launch-trace identity bit (``--suite shards`` + ``--check`` is the CI
  shard-smoke gate);
* ``remote_churn_*`` — the same fleet churn with the plan phase running
  in shard workers over the wire codecs (``--suite remote``):
  trace identity vs serial, the modeled critical path, and the
  serialization bill (encode+decode us/event, bytes/round) reported as
  its own rows — wire overhead is never folded into decision latency
  (``--check`` is the CI remote-smoke gate).

``main`` additionally writes ``BENCH_scheduler.json`` (per-scenario
ns/op + mean ACT, machine-readable for CI trending) and, with
``--check``, exits non-zero if the dense path is slower than the
reference on the queue-128 scenario — the CI smoke guard for the
fast path.
"""

from __future__ import annotations

import json
import statistics
import time
from typing import Dict, List, Optional

from benchmarks.common import emit
from repro.core import scenarios
from repro.core.action import Action, AmdahlElasticity, ResourceRequest, fixed
from repro.core.cluster import CpuNodeSpec
from repro.core.managers.base import ResourceManager
from repro.core.managers.cpu import CpuManager
from repro.core.orchestrator import Orchestrator
from repro.core.scheduler import ElasticScheduler


def _mk_waiting(n: int, scalable_frac: float = 0.3):
    out = []
    for i in range(n):
        if i % max(1, int(1 / max(scalable_frac, 1e-9))) == 0:
            out.append(
                Action(
                    name="reward",
                    cost={"cpu": ResourceRequest("cpu", (1, 2, 4, 8, 16, 32))},
                    key_resource="cpu",
                    elasticity=AmdahlElasticity(0.05),
                    base_duration=10.0 + i,
                    trajectory_id=f"t{i}",
                )
            )
        else:
            out.append(
                Action(name="tool", cost={"cpu": fixed("cpu", 1)},
                       base_duration=1.0, trajectory_id=f"t{i}")
            )
    return out


def run(scale: float = 1.0) -> List[Dict[str, object]]:
    rows = []
    for depth in (1, 2, 3):
        for n in (8, 32, 128):
            waiting = _mk_waiting(n)
            timings: Dict[str, float] = {}
            for variant in ("dense", "ref"):
                mgr = {"cpu": CpuManager([CpuNodeSpec("n0", cores=256)])}
                sched = ElasticScheduler(depth=depth)
                sched.use_dense = variant == "dense"
                iters = max(3, int(30 * scale))
                t0 = time.perf_counter()
                for _ in range(iters):
                    sched.schedule(waiting, [], mgr, 0.0)
                us = (time.perf_counter() - t0) / iters * 1e6
                timings[variant] = us
                suffix = "" if variant == "dense" else "_ref"
                rows.append(
                    {
                        "name": f"schedule_depth{depth}_queue{n}{suffix}",
                        "us_per_call": us,
                        "derived": f"depth={depth};queue={n};dp={variant}",
                    }
                )
            rows.append(
                {
                    "name": f"schedule_depth{depth}_queue{n}_dense_speedup",
                    "us_per_call": timings["ref"] / max(1e-9, timings["dense"]),
                    "derived": f"depth={depth};queue={n};x_ref_over_dense",
                }
            )
    return rows


# The churn workload (DeepSearch-style rate-limited services plus local
# utilities — agentic workloads multiplex MANY resource types, which is
# what per-type queue partitioning exploits) is declared as a
# ScenarioSpec in repro.core.scenarios (``churn_spec``).  The frozen
# pre-factory Python generator it replaced is pinned in
# tests/test_scenarios.py, where an equivalence test proves the spec
# reproduces its traces bit-identically.
CHURN_APIS = scenarios.CHURN_APIS


class _SeedOrchestrator(Orchestrator):
    """The seed Tangram control plane, reconstructed for comparison: ONE
    global FCFS queue (no resource partitioning) and a full reschedule of
    the entire problem on every event — the pre-refactor
    ``Tangram._tick`` decision path."""

    @staticmethod
    def _partition_of(action: Action) -> str:
        return "*"


def _run_churn(mode: str, queue: int, events: int):
    """Warm orchestrator under steady-state churn: the queue is primed to
    ``queue`` depth against pools smaller than demand, then every
    completion triggers one replacement submission, holding depth
    roughly constant while ``events`` actions flow through.  Each event
    touches ONE resource partition — the scenario the incremental engine
    (dirty tracking + admission cursor + DP memo) is built for.

    ``mode``: "seed" (global queue, full reschedule per event),
    "full" (partitioned queues, every partition rescheduled per event),
    or "incremental" (dirty tracking + caches)."""
    from repro.core.simulator import EventLoop

    spec = scenarios.churn_spec(queue=queue, events=events)
    loop = EventLoop()
    managers = scenarios.build_managers(spec, loop)
    cls = _SeedOrchestrator if mode == "seed" else Orchestrator
    orch = cls(
        managers,
        loop=loop,
        policy=ElasticScheduler(),
        incremental=(mode == "incremental"),
    )
    # closed-loop wave arrivals (paper §6: rollout batches land
    # together): every ``wave`` completions trigger one same-timestamp
    # submission burst, so the queue repeatedly sees freed capacity
    # against deep backlog — the regime where a full reschedule rebuilds
    # the whole window/DP and the incremental path reuses it.
    scenarios.install_scenario(spec, orch)
    # warm-up: let the priming burst enqueue and the first launches land,
    # so the measurement covers only steady-state churn rounds.
    orch.run(until=0.001 * queue + 0.05)
    warm_records = len(orch.telemetry.records)
    orch.telemetry.sched_wall_s = 0.0
    warm_stats = dict(orch.stats)
    t0 = time.perf_counter()
    orch.run()
    wall = time.perf_counter() - t0
    n_events = len(orch.telemetry.records) - warm_records
    return {
        "wall_s": wall,
        "sched_us_per_event": orch.telemetry.sched_wall_s / max(1, n_events) * 1e6,
        "events": n_events,
        "rounds": orch.stats["rounds"] - warm_stats["rounds"],
        "partition_runs": orch.stats["partition_runs"] - warm_stats["partition_runs"],
        # decision QUALITY: the seed's global FCFS head-of-line blocking
        # makes its rounds cheap precisely because it schedules less —
        # mean ACT exposes that pathology alongside the latency numbers.
        "mean_act": orch.telemetry.mean_act(),
    }


def run_churn(scale: float = 1.0) -> List[Dict[str, object]]:
    rows: List[Dict[str, object]] = []
    for queue in (32, 128):
        events = max(64, int(256 * scale))
        results = {
            mode: _run_churn(mode, queue=queue, events=events)
            for mode in ("seed", "full", "incremental")
        }
        inc_us = max(1e-9, results["incremental"]["sched_us_per_event"])
        for mode, r in results.items():
            rows.append(
                {
                    "name": f"churn_queue{queue}_{mode}",
                    "us_per_call": r["sched_us_per_event"],
                    "mean_act": r["mean_act"],
                    "derived": (
                        f"queue={queue};events={r['events']};rounds={r['rounds']};"
                        f"partition_runs={r['partition_runs']}"
                    ),
                }
            )
        rows.append(
            {
                "name": f"churn_queue{queue}_speedup_vs_seed",
                "us_per_call": results["seed"]["sched_us_per_event"] / inc_us,
                "mean_act": "",
                "derived": f"queue={queue};x_seed_over_incremental",
            }
        )
        rows.append(
            {
                "name": f"churn_queue{queue}_speedup_vs_full",
                "us_per_call": results["full"]["sched_us_per_event"] / inc_us,
                "mean_act": "",
                "derived": f"queue={queue};x_full_over_incremental",
            }
        )
    return rows


# ---------------------------------------------------------------------------
# Sharded-rounds scenario: synchronized fleet churn (the control-plane
# scale wall the plan/commit engine removes)
# ---------------------------------------------------------------------------

#: Independent external resource pools in the fleet-churn scenario.  The
#: fleet is symmetric — every wave lands the same action multiset on
#: every pool at the same virtual instant, so completions coalesce
#: across pools and (nearly) every scheduling round re-plans many dirty
#: partitions: the regime where the serial round loop's decision latency
#: grows with fleet size and the sharded engine's critical path stays
#: flat.
SHARD_POOLS = 8


def _run_shard_churn(
    shards: Optional[int], queue: int = 128, waves: int = 16,
    cores: int = 8, period_s: float = 4.0,
    plan_mode: str = "inline", transport="loopback",
    wire_codec: str = "json", commit_mode: str = "client", pre_run=None,
):
    """Steady-state churn over ``SHARD_POOLS`` independent pools, each
    smaller than its demand so a deep backlog persists: every wave
    submits ``queue / SHARD_POOLS`` actions per pool at one timestamp,
    and the symmetric workload keeps cross-pool completions coalesced —
    every round is a genuinely multi-partition round.  ``shards=None``
    is the serial round loop; ``shards=N`` the plan/commit engine, whose
    charged decision latency is the critical path (max per-shard plan +
    serialized commit — see repro.core.shards).  ``plan_mode="remote"``
    sends the plan phase through the wire codecs to shard workers
    (``transport``: "loopback" = in-process workers behind the full
    encode/decode path, "process" = real worker OS processes, or a
    ``shard_idx -> ShardTransport`` factory for socket fleets).
    ``commit_mode="worker"`` moves the commit phase worker-side too
    (two-phase prepare/ack over fused ``plan_commit`` frames).
    ``pre_run(orch)`` runs before the clock starts — the chaos suite's
    hook for scheduling virtual-time worker kills."""
    from repro.core.simulator import EventLoop

    spec = scenarios.fleet_churn_spec(
        queue=queue, waves=waves, cores=cores, period_s=period_s,
        pools=SHARD_POOLS,
    )
    loop = EventLoop()
    managers = scenarios.build_managers(spec, loop)
    orch = Orchestrator(
        managers, loop=loop, policy=ElasticScheduler(), incremental=True,
        shards=shards, plan_mode=plan_mode, transport=transport,
        wire_codec=wire_codec, commit_mode=commit_mode,
    )
    if pre_run is not None:
        pre_run(orch)
    scenarios.install_scenario(spec, orch)
    # warm-up: the first wave primes queues, caches, and pool state;
    # reset EVERY shard counter so the reported latency, wall, balance,
    # and conflict figures all cover the same post-warm-up window
    orch.run(until=period_s - 0.1)
    warm_records = len(orch.telemetry.records)
    orch.telemetry.sched_wall_s = 0.0
    orch.telemetry.plan_wall_s = 0.0
    orch.telemetry.plan_critical_s = 0.0
    orch.telemetry.commit_conflicts = 0
    orch.telemetry.shards = {}
    orch.telemetry.reset_wire()
    orch.run()
    n_events = len(orch.telemetry.records) - warm_records
    trace = sorted(
        (r.name, r.trajectory_id, round(r.submit, 9), round(r.start, 9),
         round(r.finish, 9), tuple(sorted(r.units.items())), r.failed)
        for r in orch.telemetry.records
    )
    orch.close()
    return {
        "sched_us_per_event": orch.telemetry.sched_wall_s / max(1, n_events) * 1e6,
        "events": n_events,
        "rounds": orch.stats["rounds"],
        "sharded_rounds": orch.stats["sharded_rounds"],
        "mean_act": orch.telemetry.mean_act(),
        "trace": trace,
        "summary": orch.telemetry.shard_summary(),
        "wire": orch.telemetry.wire_summary(),
        "commit_wall_s": orch.telemetry.commit_wall_s,
    }


def run_shards(scale: float = 1.0, shards: int = 4) -> List[Dict[str, object]]:
    """Sharded-round rows: serial vs ``--shards N`` decision latency on
    the queue-128 fleet churn, the speedup, trace identity, and shard
    balance.  The sharded latency is the modeled critical path (max
    per-shard plan + commit — what a fleet of per-shard workers pays);
    the real in-process plan wall is reported alongside, never
    conflated."""
    queue = 128
    waves = max(6, int(16 * scale))
    serial = _run_shard_churn(None, queue=queue, waves=waves)
    sharded = _run_shard_churn(shards, queue=queue, waves=waves)
    identical = serial["trace"] == sharded["trace"]
    speedup = serial["sched_us_per_event"] / max(
        1e-9, sharded["sched_us_per_event"]
    )
    summ = sharded["summary"]
    rows: List[Dict[str, object]] = [
        {
            "name": f"shard_churn_queue{queue}_serial",
            "us_per_call": serial["sched_us_per_event"],
            "mean_act": serial["mean_act"],
            "derived": f"queue={queue};events={serial['events']};rounds={serial['rounds']}",
        },
        {
            "name": f"shard_churn_queue{queue}_shards{shards}",
            "us_per_call": sharded["sched_us_per_event"],
            "mean_act": sharded["mean_act"],
            "derived": (
                f"queue={queue};events={sharded['events']};"
                f"sharded_rounds={sharded['sharded_rounds']};"
                f"plan_wall_s={summ.get('plan_wall_s', 0.0):.4f};"
                f"imbalance={summ.get('imbalance', 1.0):.3f};"
                f"conflicts={summ.get('commit_conflicts', 0.0):.0f}"
            ),
        },
        {
            "name": f"shard_churn_queue{queue}_speedup",
            "us_per_call": speedup,
            "mean_act": "",
            "derived": f"x_serial_over_shards{shards};critical-path model",
        },
        {
            "name": f"shard_churn_queue{queue}_traces_identical",
            "us_per_call": 1.0 if identical else 0.0,
            "mean_act": "",
            "derived": "1=launch traces bit-identical to the serial round loop",
        },
    ]
    return rows


#: Committed bytes-per-round baseline for the queue-128 fleet-churn
#: remote suite (deltas + interning + list deltas).  The CI remote-smoke
#: gate fails a regression above this — the pre-delta protocol shipped
#: ~174KB/round, so the ceiling also enforces the >=5x reduction (it sits
#: at ~10x).  Measured steady state: ~13.1KB/round with the json codec,
#: ~8.4KB with binary; the headroom absorbs machine noise in round
#: coalescing, not protocol regressions.
REMOTE_BYTES_PER_ROUND_BASELINE = 18_000

#: CI ceiling on the remote suite's SERIALIZED wire overhead (client
#: encode + client decode + worker codec, summed as if nothing
#: overlapped) relative to the modeled critical-path decision latency.
#: Measured: ~5x with the json codec (down from ~23x before the
#: delta/interning protocol) — the denominator shrank again when
#: resident worker plan state made per-shard plans cheaper, so the
#: serialized ratio reads worse even as both sides got faster.  The 7x
#: ceiling is the regression rail on raw codec cost.
REMOTE_WIRE_LATENCY_RATIO = 7.0

#: CI ceiling on the PIPELINED wire overhead — the overlap-aware
#: critical path (head request encode + slowest worker codec + response
#: decode; everything else hides behind worker compute and other
#: shards' encodes) — relative to the same decision latency.  This is
#: the honest "what the wire adds to a round" figure once dispatch is
#: pipelined, and it must stay comparable to decision cost, never a
#: multiple of it.  Measured: ~1.5x with the json codec.
REMOTE_WIRE_PIPELINED_RATIO = 3.0

#: CI floor on the client encode-memo hit rate (act-cache, queue-cache,
#: and byte-segment consultations per round).  Steady-state churn sits
#: near ~0.89; a drop below 0.80 means encode work started tracking
#: state size again instead of state *change*.
REMOTE_MEMO_HIT_RATE_FLOOR = 0.80

#: CI collapse-bound on the commit-offload ratio: the client-serial
#: commit wall divided by what commit costs the round in worker-owned
#: mode (max per-worker commit wall + whatever residual serial commit
#: the client still pays on non-fused rounds).  Structurally this
#: tracks the shard count (workers commit their partitions in parallel;
#: the serial walk sums them), measured ~1.5x at full scale with 4
#: shards over 8 pools — but at smoke scale the worker's post-commit
#: fingerprint bill is a fixed cost the tiny walk cannot amortize, so
#: the ratio hovers near 1.0-1.3x and a *win* floor would flake.  The
#: smoke gate only refuses collapse (worker-owned commit grossly
#: slower than the serial walk it replaces); the "commit actually left
#: the client's critical path" proof is the residual share below.
REMOTE_COMMIT_OFFLOAD_FLOOR = 0.9

#: CI ceiling on the residual client-serial commit wall in worker-owned
#: mode, as a share of the client-serial run's commit wall.  Fused
#: rounds never touch the client's serial commit walk, so the residual
#: is only what non-fused (single-partition / declined) rounds still
#: pay — measured ~0.0 on the symmetric churn.  A climb means rounds
#: quietly stopped fusing.
REMOTE_COMMIT_RESIDUAL_SHARE = 0.2


def run_remote(
    scale: float = 1.0, shards: int = 4, transport: str = "loopback",
    wire_codec: str = "json",
) -> List[Dict[str, object]]:
    """Remote-plan rows on the queue-128 fleet churn: plan-over-wire vs
    the serial loop, trace identity, and the wire bill.  Serialization
    overhead is charged to its own rows, never into the modeled
    critical-path decision latency — the two costs answer different
    questions (what a worker fleet's decisions cost vs what shipping
    them costs).  The wire bill is reported per component — client
    encode, client decode, worker codec (the worker's own parse+encode
    bill), transport wall, bytes/round — so the two sides' codec costs
    are separate rows and never conflated (the old single row summed
    client codec AND the worker-reported codec bill, which is how
    1.1ms/event of client codec read as 2.07ms/event of 'wire')."""
    queue = 128
    waves = max(6, int(16 * scale))
    serial = _run_shard_churn(None, queue=queue, waves=waves)
    remote = _run_shard_churn(
        shards, queue=queue, waves=waves, plan_mode="remote",
        transport=transport, wire_codec=wire_codec,
    )
    worker = _run_shard_churn(
        shards, queue=queue, waves=waves, plan_mode="remote",
        transport=transport, wire_codec=wire_codec, commit_mode="worker",
    )
    identical = serial["trace"] == remote["trace"]
    worker_identical = serial["trace"] == worker["trace"]
    wire = remote["wire"] or {
        "rounds": 0.0, "encode_s": 0.0, "decode_s": 0.0,
        "worker_codec_s": 0.0, "transport_s": 0.0, "bytes": 0.0,
        "fallbacks": 0.0,
    }
    events = max(1, remote["events"])
    encode_us = wire["encode_s"] / events * 1e6
    decode_us = wire["decode_s"] / events * 1e6
    worker_codec_us = wire.get("worker_codec_s", 0.0) / events * 1e6
    transport_us = wire["transport_s"] / events * 1e6
    wire_us_per_event = encode_us + decode_us + worker_codec_us
    pipelined_us = wire.get("overlap_s", 0.0) / events * 1e6
    bytes_per_round = wire["bytes"] / max(1.0, wire["rounds"])
    memo_hits = wire.get("memo_hits", 0.0)
    memo_misses = wire.get("memo_misses", 0.0)
    memo_rate = memo_hits / max(1.0, memo_hits + memo_misses)
    resident_patches = wire.get("worker_resident_patches", 0.0)
    resident_rebuilds = wire.get("worker_resident_rebuilds", 0.0)
    resident_hits = wire.get("worker_resident_hits", 0.0)
    rows: List[Dict[str, object]] = [
        {
            "name": f"remote_churn_queue{queue}_serial",
            "us_per_call": serial["sched_us_per_event"],
            "mean_act": serial["mean_act"],
            "derived": f"queue={queue};events={serial['events']};rounds={serial['rounds']}",
        },
        {
            "name": f"remote_churn_queue{queue}_shards{shards}_{transport}",
            "us_per_call": remote["sched_us_per_event"],
            "mean_act": remote["mean_act"],
            "derived": (
                f"queue={queue};events={remote['events']};"
                f"sharded_rounds={remote['sharded_rounds']};"
                f"wire_rounds={wire['rounds']:.0f};critical-path model "
                f"(wire overhead charged separately)"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_wire_overhead",
            "us_per_call": wire_us_per_event,
            "mean_act": "",
            "derived": (
                f"us/event of client encode+decode plus worker codec,"
                f" serialized-sum model (no overlap credited);"
                f"codec={wire_codec};"
                f"bytes_per_round={bytes_per_round:.0f};"
                f"fallbacks={wire.get('fallbacks', 0.0):.0f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_wire_overhead_pipelined",
            "us_per_call": pipelined_us,
            "mean_act": "",
            "derived": (
                "us/event on the overlap-aware critical path: head"
                " request encode + slowest worker codec + response"
                " decode (the rest hides behind worker compute under"
                " pipelined dispatch);"
                f"frames={wire.get('frames', 0.0):.0f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_wire_memo_hit_rate",
            "us_per_call": memo_rate,
            "mean_act": "",
            "derived": (
                f"client encode-memo consultations;hits={memo_hits:.0f};"
                f"misses={memo_misses:.0f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_worker_resident_state",
            "us_per_call": wire.get("worker_reset_s", 0.0) / events * 1e6,
            "mean_act": "",
            "derived": (
                "us/event of in-place state refresh + copy-on-plan;"
                f"hits={resident_hits:.0f};patches={resident_patches:.0f};"
                f"rebuilds={resident_rebuilds:.0f};"
                f"rebuild_s={wire.get('worker_rebuild_s', 0.0):.4f};"
                f"intern_patches={wire.get('worker_intern_patches', 0.0):.0f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_wire_client_encode",
            "us_per_call": encode_us,
            "mean_act": "",
            "derived": "us/event; client-side request serialization",
        },
        {
            "name": f"remote_churn_queue{queue}_wire_client_decode",
            "us_per_call": decode_us,
            "mean_act": "",
            "derived": "us/event; client-side response parse + plan re-bind",
        },
        {
            "name": f"remote_churn_queue{queue}_wire_worker_codec",
            "us_per_call": worker_codec_us,
            "mean_act": "",
            "derived": "us/event; worker-reported parse+encode bill",
        },
        {
            "name": f"remote_churn_queue{queue}_wire_transport",
            "us_per_call": transport_us,
            "mean_act": "",
            "derived": (
                f"us/event; dispatch->gather wall (worker compute+IPC,"
                f" overlapped);transport_wall_s={wire['transport_s']:.4f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_traces_identical",
            "us_per_call": 1.0 if identical else 0.0,
            "mean_act": "",
            "derived": "1=remote-plan launch traces bit-identical to serial",
        },
    ]

    # -- commit-phase split: client-serial vs worker-owned two-phase --
    wwire = worker["wire"] or {}
    wevents = max(1, worker["events"])
    serial_commit_us = remote["commit_wall_s"] / events * 1e6
    worker_commit_us = wwire.get("commit_critical_s", 0.0) / wevents * 1e6
    residual_us = worker["commit_wall_s"] / wevents * 1e6
    apply_us = wwire.get("commit_apply_s", 0.0) / wevents * 1e6
    offload = serial_commit_us / max(1e-9, worker_commit_us + residual_us)
    rows += [
        {
            "name": f"remote_churn_queue{queue}_commit_worker",
            "us_per_call": worker["sched_us_per_event"],
            "mean_act": worker["mean_act"],
            "derived": (
                f"critical-path model, commit_mode=worker;"
                f"prepares={wwire.get('prepares', 0.0):.0f};"
                f"acks={wwire.get('commit_acks', 0.0):.0f};"
                f"aborts={wwire.get('commit_aborts', 0.0):.0f};"
                f"inline={wwire.get('commit_inline_rounds', 0.0):.0f};"
                f"resends={wwire.get('fallbacks', 0.0):.0f};"
                f"diverged={wwire.get('commit_diverged', 0.0):.0f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_commit_traces_identical",
            "us_per_call": 1.0 if worker_identical else 0.0,
            "mean_act": "",
            "derived": "1=worker-owned commit launch traces bit-identical to serial",
        },
        {
            "name": f"remote_churn_queue{queue}_commit_serial_wall",
            "us_per_call": serial_commit_us,
            "mean_act": "",
            "derived": (
                "us/event the client pays walking every partition's commit"
                " serially (client-serial commit mode, serialized model)"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_commit_worker_critical",
            "us_per_call": worker_commit_us,
            "mean_act": "",
            "derived": (
                "us/event of the worker-parallel commit critical path (max"
                " per-worker commit wall, pipelined model);"
                f"residual_serial_us={residual_us:.2f};"
                f"client_apply_us={apply_us:.2f}"
            ),
        },
        {
            "name": f"remote_churn_queue{queue}_commit_offload_speedup",
            "us_per_call": offload,
            "mean_act": "",
            "derived": (
                "x_serial_commit_wall_over_worker_critical_plus_residual;"
                f"floor={REMOTE_COMMIT_OFFLOAD_FLOOR}"
            ),
        },
    ]
    return rows


def check_remote(rows: List[Dict[str, object]]) -> None:
    """CI remote-smoke gates on the queue-128 fleet churn: (a) remote-
    plan launch traces bit-identical to the serial round loop; (b) the
    wire was actually exercised (a refactor that silently stops
    sharding rounds must not pass vacuously); (c) the serialized wire
    overhead stays within REMOTE_WIRE_LATENCY_RATIO of the modeled
    critical-path decision latency, and the pipelined (overlap-aware)
    overhead within the tighter REMOTE_WIRE_PIPELINED_RATIO; (d)
    bytes/round stays under the committed
    REMOTE_BYTES_PER_ROUND_BASELINE; (e) the client encode-memo hit
    rate stays above REMOTE_MEMO_HIT_RATE_FLOOR; (f) steady-state runs
    take zero full-content fallbacks (recovery is for faults, not for a
    protocol that forgets its own state); (g) the commit-mode matrix:
    worker-owned commit's launch trace is bit-identical to serial, its
    steady-state run takes zero fallbacks and zero aborts, the two-phase
    rail was really exercised (prepares > 0), the commit-offload ratio
    has not collapsed (REMOTE_COMMIT_OFFLOAD_FLOOR), and the residual
    client-serial commit wall stays a sliver of the serial walk
    (REMOTE_COMMIT_RESIDUAL_SHARE — commit left the client's critical
    path)."""
    by_name = {str(r["name"]): r for r in rows}
    identical_row = by_name["remote_churn_queue128_traces_identical"]
    identical = float(identical_row["us_per_call"])  # type: ignore[arg-type]
    overhead_row = by_name["remote_churn_queue128_wire_overhead"]
    wire_us = float(overhead_row["us_per_call"])  # type: ignore[arg-type]
    pipelined_row = by_name["remote_churn_queue128_wire_overhead_pipelined"]
    pipelined_us = float(pipelined_row["us_per_call"])  # type: ignore[arg-type]
    memo_row = by_name["remote_churn_queue128_wire_memo_hit_rate"]
    memo_rate = float(memo_row["us_per_call"])  # type: ignore[arg-type]
    critical_us = 0.0
    wire_rounds = 0.0
    bytes_per_round = 0.0
    fallbacks = 0.0
    for r in rows:
        derived = str(r.get("derived", ""))
        if "wire_rounds=" in derived:
            wire_rounds = float(derived.split("wire_rounds=")[1].split(";")[0])
            critical_us = float(r["us_per_call"])  # type: ignore[arg-type]
        if "bytes_per_round=" in derived:
            bytes_per_round = float(
                derived.split("bytes_per_round=")[1].split(";")[0]
            )
        if "fallbacks=" in derived:
            fallbacks = float(derived.split("fallbacks=")[1].split(";")[0])
    print(
        f"# remote check: traces_identical={identical:.0f} "
        f"wire_rounds={wire_rounds:.0f} "
        f"wire_overhead={wire_us:.1f}us/event "
        f"pipelined={pipelined_us:.1f}us/event "
        f"critical={critical_us:.1f}us/event "
        f"bytes_per_round={bytes_per_round:.0f} "
        f"memo_hit_rate={memo_rate:.3f} fallbacks={fallbacks:.0f}"
    )
    if identical != 1.0:
        raise SystemExit("remote-plan fleet-churn launch trace diverged from serial")
    if wire_rounds <= 0:
        raise SystemExit("remote suite never exercised the wire (no sharded rounds)")
    if wire_us > REMOTE_WIRE_LATENCY_RATIO * critical_us:
        raise SystemExit(
            f"serialized wire overhead {wire_us:.1f}us/event exceeds "
            f"{REMOTE_WIRE_LATENCY_RATIO:.0f}x the critical-path decision "
            f"latency {critical_us:.1f}us/event"
        )
    if pipelined_us > REMOTE_WIRE_PIPELINED_RATIO * critical_us:
        raise SystemExit(
            f"pipelined wire overhead {pipelined_us:.1f}us/event exceeds "
            f"{REMOTE_WIRE_PIPELINED_RATIO:.0f}x the critical-path decision "
            f"latency {critical_us:.1f}us/event"
        )
    if bytes_per_round > REMOTE_BYTES_PER_ROUND_BASELINE:
        raise SystemExit(
            f"bytes/round {bytes_per_round:.0f} regressed above the committed "
            f"baseline {REMOTE_BYTES_PER_ROUND_BASELINE}"
        )
    if memo_rate < REMOTE_MEMO_HIT_RATE_FLOOR:
        raise SystemExit(
            f"encode-memo hit rate {memo_rate:.3f} fell below the floor "
            f"{REMOTE_MEMO_HIT_RATE_FLOOR}"
        )
    if fallbacks > 0:
        raise SystemExit(
            f"{fallbacks:.0f} full-content fallback(s) in a steady-state run "
            "(cache budgets or mirror determinism regressed)"
        )

    # -- commit-mode matrix gates (worker-owned vs client-serial) --
    commit_flag = float(
        by_name["remote_churn_queue128_commit_traces_identical"]["us_per_call"]  # type: ignore[arg-type]
    )
    wk_derived = str(by_name["remote_churn_queue128_commit_worker"]["derived"])

    def _field(key: str) -> float:
        return float(wk_derived.split(f"{key}=")[1].split(";")[0])

    prepares = _field("prepares")
    resends = _field("resends")
    aborts = _field("aborts")
    diverged = _field("diverged")
    offload = float(
        by_name["remote_churn_queue128_commit_offload_speedup"]["us_per_call"]  # type: ignore[arg-type]
    )
    serial_wall_us = float(
        by_name["remote_churn_queue128_commit_serial_wall"]["us_per_call"]  # type: ignore[arg-type]
    )
    crit_derived = str(
        by_name["remote_churn_queue128_commit_worker_critical"]["derived"]
    )
    residual_us = float(crit_derived.split("residual_serial_us=")[1].split(";")[0])
    print(
        f"# commit check: traces_identical={commit_flag:.0f} "
        f"prepares={prepares:.0f} resends={resends:.0f} aborts={aborts:.0f} "
        f"offload={offload:.2f}x residual={residual_us:.2f}us"
    )
    if commit_flag != 1.0:
        raise SystemExit("worker-owned commit launch trace diverged from serial")
    if prepares <= 0:
        raise SystemExit(
            "worker-owned commit never sent a prepare (two-phase rail idle "
            "— every round fell back to client-serial commit)"
        )
    if resends > 0:
        raise SystemExit(
            f"{resends:.0f} full-content fallback(s) in the steady-state "
            "worker-owned commit run"
        )
    if aborts > 0 or diverged > 0:
        raise SystemExit(
            f"steady-state worker-owned commit took {aborts:.0f} abort(s) / "
            f"{diverged:.0f} divergence(s) — conflict-free churn must "
            "prepare-and-confirm cleanly"
        )
    if offload < REMOTE_COMMIT_OFFLOAD_FLOOR:
        raise SystemExit(
            f"commit-offload ratio {offload:.2f}x collapsed below "
            f"{REMOTE_COMMIT_OFFLOAD_FLOOR}x — worker-owned commit costs "
            "grossly more than the serial walk it replaces"
        )
    if residual_us > REMOTE_COMMIT_RESIDUAL_SHARE * serial_wall_us:
        raise SystemExit(
            f"residual client-serial commit wall {residual_us:.2f}us/event "
            f"exceeds {REMOTE_COMMIT_RESIDUAL_SHARE:.0%} of the serial "
            f"commit wall {serial_wall_us:.2f}us/event — rounds stopped "
            "fusing their commits"
        )


def check_shards(rows: List[Dict[str, object]], shards: int = 4) -> None:
    """CI shard-smoke gates on the queue-128 fleet churn: (a) sharded
    launch traces bit-identical to the serial round loop (the workload
    is conflict-free by construction); (b) critical-path decision
    latency >= 1.5x better than serial."""
    by_name = {r["name"]: float(r["us_per_call"]) for r in rows}  # type: ignore[arg-type]
    speedup = by_name["shard_churn_queue128_speedup"]
    identical = by_name["shard_churn_queue128_traces_identical"]
    print(f"# shard check: speedup={speedup:.2f}x traces_identical={identical:.0f}")
    if identical != 1.0:
        raise SystemExit("sharded fleet-churn launch trace diverged from serial")
    if speedup < 1.5:
        raise SystemExit(
            f"sharded decision latency only {speedup:.2f}x better than serial (< 1.5x)"
        )


# ---------------------------------------------------------------------------
# Chaos suite: the fleet churn over real TCP sockets under kill/restart
# storms and packet-level fault schedules (--suite chaos is the CI
# chaos-smoke gate)
# ---------------------------------------------------------------------------

#: Virtual times of the kill-storm's server-side connection drops.  All
#: after the warm-up window (the wire counters reset at ~4s) so every
#: loss lands in the measured figures; the horizon filter in run_chaos
#: keeps low --scale runs meaningful.
# The chaos fault schedules live in their ScenarioSpecs
# (repro.core.scenarios.chaos_*_spec): kill times all land after the
# warm-up window; the packet-fault indices start at 3 so no fault burns
# inside the window where the telemetry is reset.  The amnesia plan is
# separate: silent worker replacement exercises the stale-ref storm
# (typed protocol errors + full re-send), not the transport-loss rail,
# and the gate checks the two stay distinguishable.
CHAOS_KILL_TIMES = scenarios.chaos_storm_spec().kill_times()
CHAOS_FAULT_PLAN = scenarios.chaos_packet_spec().packet_plan()
CHAOS_AMNESIA_PLAN = scenarios.chaos_amnesia_spec().packet_plan()


def run_chaos(scale: float = 1.0, shards: int = 4) -> List[Dict[str, object]]:
    """Chaos rows: the queue-128 fleet churn planned over real TCP
    socket workers while the harness kills connections (worker death +
    reconnect-to-a-blank-worker) and injects packet-level faults
    (dropped requests/responses, mid-frame truncation, silent worker
    amnesia).  Every scenario's launch trace must stay bit-identical to
    the serial round loop — fault tolerance is allowed to cost wire
    time, never correctness."""
    from repro.core.transport import (
        SocketTransport,
        WorkerServer,
        chaos_fleet,
        socket_fleet,
    )

    queue = 128
    waves = max(6, int(16 * scale))
    horizon = waves * 4.0
    serial = _run_shard_churn(None, queue=queue, waves=waves)

    # (a) kill/restart storm: server-side connection drops at fixed
    # virtual times; the endpoint stays up so clients reconnect
    with WorkerServer() as srv:
        kill_times = [t for t in CHAOS_KILL_TIMES if t < horizon]

        def schedule_kills(orch: Orchestrator) -> None:
            for t in kill_times:
                orch.loop.call_after(t, srv.kill_connections)

        storm = _run_shard_churn(
            shards, queue=queue, waves=waves, plan_mode="remote",
            transport=socket_fleet([srv.addr]), pre_run=schedule_kills,
        )

    # (b) mixed packet faults: deterministic per-shard schedules
    with WorkerServer() as srv:
        fault_fac = chaos_fleet(
            lambda i: SocketTransport(srv.addr), CHAOS_FAULT_PLAN
        )
        faulted = _run_shard_churn(
            shards, queue=queue, waves=waves, plan_mode="remote",
            transport=fault_fac,
        )
        faults_fired = sum(p.faults_fired for p in fault_fac.plans.values())

    # (c) stale-ref storm: pure amnesia — silent worker replacement must
    # surface as typed protocol errors absorbed by full re-sends, with
    # ZERO transport losses (the rails must not blur together)
    with WorkerServer() as srv:
        amn_fac = chaos_fleet(
            lambda i: SocketTransport(srv.addr), CHAOS_AMNESIA_PLAN
        )
        amnesia = _run_shard_churn(
            shards, queue=queue, waves=waves, plan_mode="remote",
            transport=amn_fac,
        )
        amnesia_fired = sum(p.faults_fired for p in amn_fac.plans.values())

    def _flag(run) -> float:
        return 1.0 if run["trace"] == serial["trace"] else 0.0

    storm_wire = storm["wire"]
    fault_wire = faulted["wire"]
    amn_wire = amnesia["wire"]
    rows: List[Dict[str, object]] = [
        {
            "name": "chaos_kill_storm_traces_identical",
            "us_per_call": _flag(storm),
            "mean_act": storm["mean_act"],
            "derived": (
                f"kills={len(kill_times)};events={storm['events']};"
                f"serial_events={serial['events']};"
                "1=launch trace bit-identical to serial under the storm"
            ),
        },
        {
            "name": "chaos_kill_storm_worker_losses",
            "us_per_call": storm_wire.get("worker_losses", 0.0),
            "mean_act": "",
            "derived": (
                f"reconnects={storm_wire.get('reconnects', 0.0):.0f};"
                f"inline_parts={storm_wire.get('inline_parts', 0.0):.0f};"
                "losses must be > 0 or the storm was vacuous"
            ),
        },
        {
            "name": "chaos_packet_faults_traces_identical",
            "us_per_call": _flag(faulted),
            "mean_act": faulted["mean_act"],
            "derived": (
                f"faults_fired={faults_fired};"
                f"losses={fault_wire.get('worker_losses', 0.0):.0f};"
                f"resends={fault_wire.get('fallbacks', 0.0):.0f};"
                "drops+truncation+amnesia on scheduled request indices"
            ),
        },
        {
            "name": "chaos_amnesia_traces_identical",
            "us_per_call": _flag(amnesia),
            "mean_act": amnesia["mean_act"],
            "derived": (
                f"faults_fired={amnesia_fired};"
                f"resends={amn_wire.get('fallbacks', 0.0):.0f};"
                f"losses={amn_wire.get('worker_losses', 0.0):.0f};"
                "silent worker swaps -> typed stale-ref + full re-send"
            ),
        },
        {
            "name": "chaos_amnesia_full_resends",
            "us_per_call": amn_wire.get("fallbacks", 0.0),
            "mean_act": "",
            "derived": "full-content recovery rounds absorbed by the client",
        },
    ]
    return rows


def check_chaos(rows: List[Dict[str, object]]) -> None:
    """CI chaos-smoke gates: (a) every chaos scenario's launch trace is
    bit-identical to serial (which also proves zero lost / doubled
    launches — the trace is the complete launch ledger); (b) the storm
    really stormed (worker losses > 0); (c) the amnesia run really
    exercised the stale-ref rail (full re-sends > 0) WITHOUT transport
    losses (the two recovery rails stay distinguishable)."""
    by_name = {str(r["name"]): r for r in rows}
    for flag_name in (
        "chaos_kill_storm_traces_identical",
        "chaos_packet_faults_traces_identical",
        "chaos_amnesia_traces_identical",
    ):
        row = by_name[flag_name]
        if float(row["us_per_call"]) != 1.0:  # type: ignore[arg-type]
            raise SystemExit(f"{flag_name}: launch trace diverged from serial")
    losses = float(by_name["chaos_kill_storm_worker_losses"]["us_per_call"])  # type: ignore[arg-type]
    resends = float(by_name["chaos_amnesia_full_resends"]["us_per_call"])  # type: ignore[arg-type]
    amn_derived = str(by_name["chaos_amnesia_traces_identical"]["derived"])
    amn_losses = float(amn_derived.split("losses=")[1].split(";")[0])
    print(
        f"# chaos check: all traces identical; kill-storm losses={losses:.0f} "
        f"amnesia resends={resends:.0f} amnesia losses={amn_losses:.0f}"
    )
    if losses <= 0:
        raise SystemExit("kill storm recorded no worker losses (vacuous storm)")
    if resends <= 0:
        raise SystemExit("amnesia storm drove no full re-sends (stale-ref rail idle)")
    if amn_losses > 0:
        raise SystemExit(
            "amnesia storm surfaced as transport losses — the stale-ref rail "
            "and the loss rail blurred together"
        )


# ---------------------------------------------------------------------------
# Nightly-scale chaos (`--suite chaos --scale large`): 8 real worker OS
# processes, O(100k) actions, periodic worker-process kill/respawn — in
# BOTH commit modes.  Non-blocking (scheduled/manual workflow), so the
# 2-4-worker CI-scale gates above stay fast.
# ---------------------------------------------------------------------------

#: 784 waves x 128 actions/wave ~= 100k actions (the ROADMAP scale
#: target for the storm harness).
CHAOS_LARGE_WAVES = 784
CHAOS_LARGE_WORKERS = 8

#: One worker-process kill/respawn every this many virtual seconds
#: (round-robin over the fleet) — ~85 full process deaths per run.
CHAOS_LARGE_KILL_PERIOD_S = 37.0


def _spawn_worker_proc(port: int = 0):
    """One real shard-worker OS process (``tools/shard_worker.py``);
    returns ``(proc, port)`` once the endpoint is listening (the
    entrypoint prints ``PORT <n>`` when bound)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    proc = subprocess.Popen(
        [sys.executable, str(root / "tools" / "shard_worker.py"),
         "--port", str(port)],
        stdout=subprocess.PIPE, env=env, text=True,
    )
    line = (proc.stdout.readline() or "").strip()
    if not line.startswith("PORT "):
        proc.kill()
        raise RuntimeError(f"shard worker failed to start: {line!r}")
    return proc, int(line.split()[1])


def run_chaos_large(
    waves: int = CHAOS_LARGE_WAVES, workers: int = CHAOS_LARGE_WORKERS,
) -> List[Dict[str, object]]:
    """The storm at fleet scale: the queue-128 churn over ``workers``
    real worker OS processes, with a worker process hard-killed and
    respawned on its port every ``CHAOS_LARGE_KILL_PERIOD_S`` virtual
    seconds (round-robin), run once under client-serial commit and once
    under worker-owned two-phase commit.  A killed process takes its
    entire resident state — plan caches, intern tables, authoritative
    manager replicas and their leases — so every respawn exercises the
    loss rail AND the blank-worker re-grant rail at full depth.  Both
    storms' launch traces must stay bit-identical to the serial loop."""
    from repro.core.transport import socket_fleet

    queue = 128
    horizon = waves * 4.0
    serial = _run_shard_churn(None, queue=queue, waves=waves)
    expected = serial["events"]

    def storm(commit_mode: str):
        procs: List[object] = []
        ports: List[int] = []
        try:
            for _ in range(workers):
                p, port = _spawn_worker_proc()
                procs.append(p)
                ports.append(port)
            kill_times = []
            t = 5.0
            while t < horizon:
                kill_times.append(t)
                t += CHAOS_LARGE_KILL_PERIOD_S
            counter = [0]

            def _kill_next() -> None:
                idx = counter[0] % workers
                counter[0] += 1
                procs[idx].kill()
                procs[idx].wait()
                try:
                    procs[idx].stdout.close()
                except OSError:
                    pass
                procs[idx], _ = _spawn_worker_proc(ports[idx])

            def pre(orch: Orchestrator) -> None:
                for kt in kill_times:
                    orch.loop.call_after(kt, _kill_next)

            run = _run_shard_churn(
                workers, queue=queue, waves=waves, plan_mode="remote",
                transport=socket_fleet([("127.0.0.1", pt) for pt in ports]),
                commit_mode=commit_mode, pre_run=pre,
            )
            return run, len(kill_times)
        finally:
            for p in procs:
                try:
                    p.kill()
                    p.wait()
                    p.stdout.close()
                except OSError:
                    pass

    client, kills = storm("client")
    owned, _ = storm("worker")
    cwire = client["wire"] or {}
    owire = owned["wire"] or {}
    rows: List[Dict[str, object]] = [
        {
            "name": "chaos_large_client_traces_identical",
            "us_per_call": 1.0 if client["trace"] == serial["trace"] else 0.0,
            "mean_act": client["mean_act"],
            "derived": (
                f"workers={workers};kills={kills};events={client['events']};"
                f"expected={expected};"
                "client-serial commit over real worker processes"
            ),
        },
        {
            "name": "chaos_large_worker_traces_identical",
            "us_per_call": 1.0 if owned["trace"] == serial["trace"] else 0.0,
            "mean_act": owned["mean_act"],
            "derived": (
                f"workers={workers};kills={kills};events={owned['events']};"
                f"expected={expected};"
                f"prepares={owire.get('prepares', 0.0):.0f};"
                f"regrants={owire.get('lease_regrants', 0.0):.0f};"
                f"adoptions={owire.get('lease_adoptions', 0.0):.0f};"
                "worker-owned two-phase commit over real worker processes"
            ),
        },
        {
            "name": "chaos_large_worker_losses",
            "us_per_call": (
                cwire.get("worker_losses", 0.0)
                + owire.get("worker_losses", 0.0)
            ),
            "mean_act": "",
            "derived": (
                f"client_losses={cwire.get('worker_losses', 0.0):.0f};"
                f"owned_losses={owire.get('worker_losses', 0.0):.0f};"
                f"reconnects={cwire.get('reconnects', 0.0) + owire.get('reconnects', 0.0):.0f};"
                "process deaths absorbed across both storms"
            ),
        },
        {
            "name": "chaos_large_sched_us_worker_commit",
            "us_per_call": owned["sched_us_per_event"],
            "mean_act": "",
            "derived": (
                f"critical-path model under the storm;"
                f"serial={serial['sched_us_per_event']:.1f}us/event;"
                f"client_commit={client['sched_us_per_event']:.1f}us/event"
            ),
        },
    ]
    return rows


def check_chaos_large(rows: List[Dict[str, object]]) -> None:
    """Nightly gates: both storms' traces bit-identical to serial at
    O(100k)-action scale; the storms really killed worker processes;
    the two-phase rail carried real prepare traffic; the run covered
    the full workload (no silently truncated horizon)."""
    by_name = {str(r["name"]): r for r in rows}

    def _field(row: str, key: str) -> float:
        return float(str(by_name[row]["derived"]).split(f"{key}=")[1].split(";")[0])

    for flag_name in (
        "chaos_large_client_traces_identical",
        "chaos_large_worker_traces_identical",
    ):
        if float(by_name[flag_name]["us_per_call"]) != 1.0:  # type: ignore[arg-type]
            raise SystemExit(f"{flag_name}: launch trace diverged from serial")
        events = _field(flag_name, "events")
        expected = _field(flag_name, "expected")
        if events < expected:
            raise SystemExit(
                f"{flag_name}: run covered {events:.0f}/{expected:.0f} events"
            )
    losses = float(by_name["chaos_large_worker_losses"]["us_per_call"])  # type: ignore[arg-type]
    prepares = _field("chaos_large_worker_traces_identical", "prepares")
    kills = _field("chaos_large_worker_traces_identical", "kills")
    print(
        f"# chaos-large check: traces identical; kills={kills:.0f}/storm "
        f"losses={losses:.0f} prepares={prepares:.0f}"
    )
    if losses <= 0:
        raise SystemExit("large storm recorded no worker losses (vacuous)")
    if prepares <= 0:
        raise SystemExit("large storm never exercised the two-phase rail")


# ---------------------------------------------------------------------------
# Telemetry-driven rebalance on an asymmetric fleet (rows ride in the
# remote suite's BENCH_remote.json; the gate is part of --suite remote)
# ---------------------------------------------------------------------------

#: The rebalanced run's mean ACT must beat the no-rebalance run by at
#: least this factor on the skewed fleet (measured ~3x; the floor
#: absorbs workload-shape drift, not policy regressions).
REBALANCE_ACT_WIN_FLOOR = 1.2


def _run_rebalance_fleet(
    rebalance: bool, pools: int = 4, cores: int = 2, n: int = 96,
    duration: float = 2.0, period_s: float = 1.0,
) -> Dict[str, float]:
    """A replica fleet with every submission keyed to pool0 — the
    asymmetric worst case the cadence exists for.  Virtual-time ACT and
    makespan, plus the migration bill, with and without the policy."""
    from repro.core.fairqueue import FairSharePolicy
    from repro.core.simulator import EventLoop

    loop = EventLoop()
    managers = {f"pool{k}": ResourceManager(f"pool{k}", cores) for k in range(pools)}
    fair = FairSharePolicy(weights={"a": 2.0, "b": 1.0, "c": 1.0, "d": 1.0})
    orch = Orchestrator(managers, loop=loop, fair_share=fair)
    if rebalance:
        orch.enable_rebalance(sorted(managers), period_s=period_s)
    for i in range(n):
        orch.submit(Action(
            name=f"w{i}", cost={"pool0": fixed("pool0", 1)},
            base_duration=duration, task_id="abcd"[i % 4],
            trajectory_id=f"t{i}",
        ))
    orch.run()
    recs = orch.telemetry.records
    out = {
        "act": sum(r.finish - r.submit for r in recs) / max(1, len(recs)),
        "makespan": max((r.finish for r in recs), default=0.0),
        "ticks": float(orch.telemetry.rebalance_ticks),
        "moves": float(orch.telemetry.rebalance_moves),
        "migrated": float(orch.telemetry.migrated_actions),
        "migration_wall_s": orch.telemetry.migration_wall_s,
    }
    orch.close()
    return out


def run_rebalance(scale: float = 1.0) -> List[Dict[str, object]]:
    """Rebalance rows: mean ACT on the skewed 4-pool fleet with the
    cadence off vs on, the win factor, and the migration bill (moves,
    migrated actions, detach/merge wall) so the cost side of the trade
    is committed next to the win."""
    n = max(48, int(96 * scale))
    off = _run_rebalance_fleet(False, n=n)
    on = _run_rebalance_fleet(True, n=n)
    win = off["act"] / max(1e-9, on["act"])
    return [
        {
            "name": "rebalance_fleet4_act_off",
            "us_per_call": off["act"],
            "mean_act": off["act"],
            "derived": (
                f"virtual-s mean ACT, all load keyed to pool0, no policy;"
                f"makespan={off['makespan']:.2f}"
            ),
        },
        {
            "name": "rebalance_fleet4_act_on",
            "us_per_call": on["act"],
            "mean_act": on["act"],
            "derived": (
                f"virtual-s mean ACT under the telemetry cadence;"
                f"makespan={on['makespan']:.2f};ticks={on['ticks']:.0f};"
                f"moves={on['moves']:.0f};migrated={on['migrated']:.0f};"
                f"migration_wall_s={on['migration_wall_s']:.4f}"
            ),
        },
        {
            "name": "rebalance_fleet4_act_speedup",
            "us_per_call": win,
            "mean_act": "",
            "derived": (
                f"x_no_rebalance_over_rebalanced;floor={REBALANCE_ACT_WIN_FLOOR}"
            ),
        },
    ]


def check_rebalance(rows: List[Dict[str, object]]) -> None:
    """Remote-suite gate: the cadence must buy a real ACT win on the
    skewed fleet (>= REBALANCE_ACT_WIN_FLOOR) through actual migrations
    — zero moves with a passing ratio would mean the scenario stopped
    exercising the policy."""
    by_name = {str(r["name"]): r for r in rows}
    win = float(by_name["rebalance_fleet4_act_speedup"]["us_per_call"])  # type: ignore[arg-type]
    derived = str(by_name["rebalance_fleet4_act_on"]["derived"])
    moves = float(derived.split("moves=")[1].split(";")[0])
    print(f"# rebalance check: act_win={win:.2f}x moves={moves:.0f}")
    if moves <= 0:
        raise SystemExit("rebalance scenario made no migrations (vacuous)")
    if win < REBALANCE_ACT_WIN_FLOOR:
        raise SystemExit(
            f"rebalance ACT win {win:.2f}x fell below the floor "
            f"{REBALANCE_ACT_WIN_FLOOR}x"
        )


# ---------------------------------------------------------------------------
# Multi-tenant fairness scenario (2 heavy + 2 light tasks, wave arrivals)
# ---------------------------------------------------------------------------

#: Configured fair-share weights; targets are w_i / sum(w).
FAIRNESS_WEIGHTS = scenarios.FAIRNESS_WEIGHTS
FAIRNESS_HORIZON_S = 90.0  # saturated measurement window (virtual seconds)


# The tenant mix (heavy tasks bursting long scalable reward jobs +
# TP-scalable GPU scoring, light tasks streaming short rigid tool calls
# — the exact shape where cross-task FCFS starves the light tenants
# behind a heavy wave) is declared in ``scenarios.fairness_spec``; the
# frozen pre-factory generator is pinned in tests/test_scenarios.py
# with a trace-equivalence test.


def _run_fairness(fair: bool, horizon: float, tasks=None):
    """Saturated multi-tenant churn: every task keeps a queued backlog
    through ``horizon`` via wave refills (each task's completions refill
    in same-timestamp bursts — the paper's rollout-batch arrival shape)."""
    from repro.core.simulator import EventLoop

    spec = scenarios.fairness_spec(horizon_s=horizon, tasks=tasks)
    loop = EventLoop()
    managers = scenarios.build_managers(spec, loop)
    fs = scenarios.build_fair_share(spec) if fair else None
    orch = Orchestrator(managers, loop=loop, policy=ElasticScheduler(), fair_share=fs)
    scenarios.install_scenario(spec, orch)
    orch.run(until=horizon * 2)
    return orch


def _fairness_trace(orch: Orchestrator):
    return sorted(
        (r.name, r.task_id, r.trajectory_id, round(r.submit, 9), round(r.start, 9),
         round(r.finish, 9), tuple(sorted(r.units.items())), r.failed)
        for r in orch.telemetry.records
    )


def run_fairness(scale: float = 1.0) -> List[Dict[str, object]]:
    """Multi-tenant fairness rows: weighted-share tracking error, light-
    tenant interference vs the FCFS ablation, and the single-task
    launch-trace equivalence bit.  The DES wall cost is negligible, so
    ``scale`` only ever lengthens the saturated window (never shortens
    it below the share-quantum granularity the 10% gate needs)."""
    horizon = FAIRNESS_HORIZON_S * max(1.0, scale)
    fair = _run_fairness(True, horizon)
    fcfs = _run_fairness(False, horizon)

    wsum = sum(FAIRNESS_WEIGHTS.values())
    share = fair.telemetry.task_share("cpu", until=horizon)
    rows: List[Dict[str, object]] = []
    max_err = 0.0
    for task, w in FAIRNESS_WEIGHTS.items():
        target = w / wsum
        got = share.get(task, 0.0)
        max_err = max(max_err, abs(got - target) / target)
        rows.append(
            {
                "name": f"fairness_share_cpu_{task}",
                "us_per_call": got,
                "mean_act": fair.telemetry.mean_act(task),
                "derived": f"target={target:.4f};weight={w}",
            }
        )
    rows.append(
        {
            "name": "fairness_share_maxerr",
            "us_per_call": max_err,
            "mean_act": "",
            "derived": "max relative |share-target|/target over tasks",
        }
    )

    light_fair = statistics.fmean(
        fair.telemetry.mean_act(t) for t in ("light0", "light1")
    )
    light_fcfs = statistics.fmean(
        fcfs.telemetry.mean_act(t) for t in ("light0", "light1")
    )
    rows.append(
        {"name": "fairness_light_act_wfq", "us_per_call": light_fair,
         "mean_act": light_fair, "derived": "light-tenant mean ACT, WFQ"}
    )
    rows.append(
        {"name": "fairness_light_act_fcfs", "us_per_call": light_fcfs,
         "mean_act": light_fcfs, "derived": "light-tenant mean ACT, FCFS ablation"}
    )
    rows.append(
        {
            "name": "fairness_interference_speedup",
            "us_per_call": light_fcfs / max(1e-9, light_fair),
            "mean_act": "",
            "derived": "x_fcfs_light_act_over_wfq",
        }
    )

    # single-task equivalence: the fairness layer must be a bit-identical
    # no-op when only one tenant exists (WFQ order == FCFS order).
    single_fair = _run_fairness(True, horizon / 3, tasks=["heavy0"])
    single_fcfs = _run_fairness(False, horizon / 3, tasks=["heavy0"])
    identical = _fairness_trace(single_fair) == _fairness_trace(single_fcfs)
    rows.append(
        {
            "name": "fairness_single_task_equivalent",
            "us_per_call": 1.0 if identical else 0.0,
            "mean_act": "",
            "derived": "1=launch traces identical to the FCFS path",
        }
    )
    return rows


def check_fairness(rows: List[Dict[str, object]]) -> None:
    """CI fairness-smoke gates: (a) weighted shares within 10% of target
    under saturation; (b) single-task launch traces identical to the
    FCFS path.  The DES is deterministic, so these are hard gates."""
    by_name = {r["name"]: float(r["us_per_call"]) for r in rows}  # type: ignore[arg-type]
    err = by_name["fairness_share_maxerr"]
    speedup = by_name["fairness_interference_speedup"]
    equiv = by_name["fairness_single_task_equivalent"]
    print(f"# fairness check: share_maxerr={err:.3f} "
          f"light_interference_speedup={speedup:.2f}x single_task_equiv={equiv:.0f}")
    if err > 0.10:
        raise SystemExit(f"weighted shares off target by {err:.1%} (> 10%)")
    if equiv != 1.0:
        raise SystemExit("single-task fairness run diverged from the FCFS path")


# ---------------------------------------------------------------------------
# Generated suite: spec-driven scenarios from the scenario factory
# (repro.core.scenarios), the differential replay rail, and the
# wave-forming gate result
# ---------------------------------------------------------------------------

#: Wave-forming gate floors (CI).  Measured on the generated
#: deep-congestion scenario (24-deep burst of near-linear scalable
#: actions, DoP up to 32, against 48 cores): the gated config
#: (``estimate_units="dp_avg"`` + ``eviction_search="exhaustive"`` +
#: ``dop_floor=8``) wins ~1.21x mean ACT, while on the mid-congestion
#: control (3-deep, absorbable near max DoP) it is exactly a no-op
#: (1.000x) — the separation EXPERIMENTS.md's hand-written scenarios
#: could not produce.  The DES is deterministic, so the floors sit just
#: under the measured values.
GEN_GATE_DEEP_FLOOR = 1.12
GEN_GATE_MID_BAND = (0.95, 1.08)
GEN_GATE_SEPARATION_FLOOR = 1.10

#: Live-mode compression: the live smoke runs the virtual scenario at a
#: quarter of its virtual timescale (real seconds of kernel work).
GEN_LIVE_TIME_SCALE = 0.25


def _run_spec_sim(spec, gated: bool = False, time_scale: float = 1.0,
                  compiled=None):
    """One DES run of a scenario spec on the generic spec-driven path
    (managers, fair share, and the optionally-gated scheduler all built
    from the spec)."""
    from repro.core.simulator import EventLoop

    compiled = compiled or scenarios.compile_scenario(
        spec, time_scale=time_scale)
    loop = EventLoop()
    orch = Orchestrator(
        scenarios.build_managers(spec, loop),
        loop=loop,
        policy=scenarios.build_policy(spec, gated=gated),
        fair_share=scenarios.build_fair_share(spec),
        incremental=True,
    )
    scenarios.install_scenario(compiled, orch)
    horizon = spec.arrival.horizon_s
    orch.run(until=horizon * 2 * time_scale if horizon else None)
    return orch


def _spec_rows(spec, prefix: str) -> List[Dict[str, object]]:
    """Rows for one externally-supplied spec file (``--spec``): the
    deterministic stream fingerprint, the run, and — when the spec
    carries scheduler-knob overrides — the gated-vs-baseline ACT win."""
    compiled = scenarios.compile_scenario(spec)
    base = _run_spec_sim(spec, compiled=compiled)
    acts = [r.finish - r.submit for r in base.telemetry.records]
    acts.sort()
    p99 = acts[int(0.99 * (len(acts) - 1))] if acts else 0.0
    rows: List[Dict[str, object]] = [
        {
            "name": f"{prefix}_events",
            "us_per_call": float(len(base.telemetry.records)),
            "mean_act": base.telemetry.mean_act(),
            "derived": (
                f"fingerprint={compiled.fingerprint()[:12]};"
                f"p99_act={p99:.3f};seed={spec.seed}"
            ),
        },
    ]
    if spec.policy:
        gated = _run_spec_sim(spec, gated=True, compiled=compiled)
        rows.append(
            {
                "name": f"{prefix}_gate_win",
                "us_per_call": base.telemetry.mean_act()
                / max(1e-9, gated.telemetry.mean_act()),
                "mean_act": gated.telemetry.mean_act(),
                "derived": f"policy={sorted(spec.policy)};"
                           "x_baseline_act_over_gated",
            }
        )
    return rows


def run_generated(scale: float = 1.0, spec_path: Optional[str] = None,
                  live: bool = False) -> List[Dict[str, object]]:
    """Generated-suite rows.

    Default set (the committed ``BENCH_generated.json`` baseline):

    * ``generated_stream_bitidentical`` — the replay rail: every
      registered scenario compiled twice produces byte-identical event
      streams, and survives the wire-dict codec round trip;
    * ``generated_fleet_us_per_event`` — decision latency on the
      spec-driven fleet churn (the latency trend row);
    * ``generated_gate_win_deep`` / ``_mid`` / ``_separation`` — the
      wave-forming gate result on the generated deep-congestion
      scenario vs its mid-congestion control;
    * ``generated_heavy_tail`` / ``generated_diurnal`` — the
      production-shaped open-loop scenarios (Pareto tool latencies,
      sinusoid-modulated Poisson arrivals), reported informationally;
    * ``generated_live_structural_identical`` (``--live``) — the same
      compiled stream run in sim and in live mode (real JAX kernel
      work on emulated XLA host devices), per-pool launch order
      compared structurally, live timing reported in ``derived`` only.

    ``--spec FILE`` appends rows for an externally-supplied scenario
    file instead of requiring a new Python function."""
    rows: List[Dict[str, object]] = []

    # (a) the bit-identical replay rail, over every registered builder
    stable = True
    fp = ""
    for name, builder in sorted(scenarios.SCENARIO_BUILDERS.items()):
        spec = builder()
        c1 = scenarios.compile_scenario(spec)
        c2 = scenarios.compile_scenario(spec)
        rt = scenarios.decode_scenario(scenarios.encode_scenario(spec))
        c3 = scenarios.compile_scenario(rt)
        if not (c1.stream_bytes() == c2.stream_bytes() == c3.stream_bytes()):
            stable = False
        if name == "deep_congestion":
            fp = c1.fingerprint()[:12]
    rows.append(
        {
            "name": "generated_stream_bitidentical",
            "us_per_call": 1.0 if stable else 0.0,
            "mean_act": "",
            "derived": (
                f"builders={len(scenarios.SCENARIO_BUILDERS)};"
                f"deep_fingerprint={fp};"
                "1=same spec+seed -> byte-identical stream, codec-stable"
            ),
        }
    )

    # (b) decision latency on the spec-driven fleet churn
    waves = max(6, int(16 * scale))
    fleet = _run_shard_churn(None, queue=128, waves=waves)
    rows.append(
        {
            "name": "generated_fleet_us_per_event",
            "us_per_call": fleet["sched_us_per_event"],
            "mean_act": fleet["mean_act"],
            "derived": f"spec=fleet_churn;queue=128;waves={waves};"
                       f"events={fleet['events']}",
        }
    )

    # (c) the wave-forming gate: deep vs mid congestion
    wins = {}
    for label, mk in (("deep", scenarios.deep_congestion_spec),
                      ("mid", scenarios.mid_congestion_spec)):
        spec = mk()
        base = _run_spec_sim(spec)
        gated = _run_spec_sim(spec, gated=True)
        win = base.telemetry.mean_act() / max(1e-9, gated.telemetry.mean_act())
        wins[label] = win
        rows.append(
            {
                "name": f"generated_gate_win_{label}",
                "us_per_call": win,
                "mean_act": gated.telemetry.mean_act(),
                "derived": (
                    f"baseline_act={base.telemetry.mean_act():.2f};"
                    f"gated_act={gated.telemetry.mean_act():.2f};"
                    "x_baseline_act_over_gated"
                ),
            }
        )
    rows.append(
        {
            "name": "generated_gate_separation",
            "us_per_call": wins["deep"] / max(1e-9, wins["mid"]),
            "mean_act": "",
            "derived": "x_deep_win_over_mid_win;"
                       "the gate engages under deep congestion only",
        }
    )

    # (d) production-shaped open-loop scenarios (informational rows)
    for name, mk in (("heavy_tail", scenarios.heavy_tail_spec),
                     ("diurnal", scenarios.diurnal_spec)):
        rows += _spec_rows(mk(), f"generated_{name}")

    # (e) the sim-vs-live differential rail
    if live:
        from repro.core.live import run_live_scenario

        spec = scenarios.live_smoke_spec()
        compiled = scenarios.compile_scenario(
            spec, time_scale=GEN_LIVE_TIME_SCALE)
        sim = _run_spec_sim(spec, compiled=compiled)
        sim_trace = scenarios.structural_trace(sim.telemetry.records)
        t0 = time.perf_counter()
        live_orch = run_live_scenario(compiled)
        wall = time.perf_counter() - t0
        live_trace = scenarios.structural_trace(live_orch.telemetry.records)
        acts = [r.finish - r.submit for r in live_orch.telemetry.records]
        live_act = statistics.fmean(acts) if acts else 0.0
        rows.append(
            {
                "name": "generated_live_structural_identical",
                "us_per_call": 1.0 if sim_trace == live_trace else 0.0,
                "mean_act": sim.telemetry.mean_act(),
                "derived": (
                    f"live_mean_act_s={live_act:.3f};live_wall_s={wall:.1f};"
                    f"records={len(live_orch.telemetry.records)};"
                    f"time_scale={GEN_LIVE_TIME_SCALE};"
                    "1=per-pool launch order identical sim vs live "
                    "(real kernel work; live timing never compared)"
                ),
            }
        )

    # (f) an externally-supplied spec file
    if spec_path:
        spec = scenarios.load_scenario(spec_path)
        rows += _spec_rows(spec, f"generated_spec_{spec.name}")
    return rows


def check_generated(rows: List[Dict[str, object]],
                    live: bool = False) -> None:
    """CI scenario-smoke gates: the replay rail holds bit-identically,
    the wave-forming gate wins under deep congestion, stays a no-op
    under mid congestion, separates the two regimes — and, with
    ``--live``, the live run's launch order matches the sim's."""
    by_name = {r["name"]: float(r["us_per_call"]) for r in rows}  # type: ignore[arg-type]
    deep = by_name["generated_gate_win_deep"]
    mid = by_name["generated_gate_win_mid"]
    sep = by_name["generated_gate_separation"]
    print(f"# generated check: bitidentical="
          f"{by_name['generated_stream_bitidentical']:.0f} "
          f"gate_deep={deep:.3f}x gate_mid={mid:.3f}x sep={sep:.3f}x")
    if by_name["generated_stream_bitidentical"] != 1.0:
        raise SystemExit("scenario compilation is not byte-deterministic")
    if deep < GEN_GATE_DEEP_FLOOR:
        raise SystemExit(
            f"wave-forming gate win {deep:.3f}x under deep congestion "
            f"(< {GEN_GATE_DEEP_FLOOR}x floor)")
    lo, hi = GEN_GATE_MID_BAND
    if not (lo <= mid <= hi):
        raise SystemExit(
            f"gate not a no-op under mid congestion: {mid:.3f}x outside "
            f"[{lo}, {hi}]")
    if sep < GEN_GATE_SEPARATION_FLOOR:
        raise SystemExit(
            f"deep/mid separation {sep:.3f}x < "
            f"{GEN_GATE_SEPARATION_FLOOR}x floor")
    if live:
        flag = by_name.get("generated_live_structural_identical")
        if flag != 1.0:
            raise SystemExit(
                "live-mode launch order diverged from the sim "
                f"(flag={flag})")


CHECK_SCENARIO = "schedule_depth2_queue128"


def write_json(rows: List[Dict[str, object]], path: str) -> None:
    """Machine-readable per-scenario results: ns/op + mean ACT."""
    scenarios: Dict[str, Dict[str, object]] = {}
    for r in rows:
        us = float(r["us_per_call"])  # type: ignore[arg-type]
        name = str(r["name"])
        # fairness_* rows and flag rows carry dimensionless metrics
        # (shares, flags, ratios), not latencies — keep them out of the
        # ns_per_op trend.
        # chaos_* rows are flags/counts and rebalance_* rows virtual-time
        # ACTs — none of them are wall-clock latencies either.
        # generated_* rows are flags/ratios/virtual figures too, except
        # the explicit us_per_event latency trend row.
        is_ratio = (
            "speedup" in name
            or name.startswith("fairness_")
            or name.startswith("chaos_")
            or name.startswith("rebalance_")
            or (name.startswith("generated_") and "us_per" not in name)
            or name.endswith("_traces_identical")
        )
        scenarios[name] = {
            "ns_per_op": None if is_ratio else us * 1e3,
            "us_per_call": None if is_ratio else us,
            "ratio": us if is_ratio else None,
            "mean_act": (
                float(r["mean_act"])  # type: ignore[arg-type]
                if r.get("mean_act") not in (None, "")
                else None
            ),
            "derived": r.get("derived"),
        }
    with open(path, "w") as f:
        json.dump({"scenarios": scenarios}, f, indent=2, sort_keys=True)
        f.write("\n")


def check_dense_fast_path(rows: List[Dict[str, object]]) -> None:
    """CI guard: the dense DP must not be slower than the reference on
    the queue-128 scenario (the acceptance target is >= 3x, but a smoke
    run at low scale is noisy, so the hard gate is parity)."""
    by_name = {r["name"]: float(r["us_per_call"]) for r in rows}  # type: ignore[arg-type]
    dense = by_name[CHECK_SCENARIO]
    ref = by_name[f"{CHECK_SCENARIO}_ref"]
    speedup = ref / max(1e-9, dense)
    print(f"# dense-DP check: {CHECK_SCENARIO} dense={dense:.0f}us "
          f"ref={ref:.0f}us speedup={speedup:.2f}x")
    if dense > ref:
        raise SystemExit(
            f"dense DP slower than reference on {CHECK_SCENARIO}: "
            f"{dense:.0f}us > {ref:.0f}us"
        )


_SUITE_JSON = {
    "latency": "BENCH_scheduler.json",
    "fairness": "BENCH_fairness.json",
    "shards": "BENCH_shards.json",
    "remote": "BENCH_remote.json",
    "chaos": "BENCH_chaos.json",
    "generated": "BENCH_generated.json",
}


def main(
    scale: float = 1.0,
    json_path: Optional[str] = None,
    check: bool = False,
    suite: str = "latency",
    shards: int = 4,
    transport: str = "loopback",
    spec: Optional[str] = None,
    live: bool = False,
) -> None:
    if scale == "large" and suite != "chaos":
        raise SystemExit("--scale large is only meaningful with --suite chaos")
    if json_path is None:
        json_path = (
            "BENCH_chaos_large.json" if scale == "large"
            else _SUITE_JSON[suite]
        )
    if suite == "chaos" and scale == "large":
        large_rows = run_chaos_large()
        emit(large_rows,
             "nightly-scale chaos: 8 worker processes, O(100k) actions")
        if json_path:
            write_json(large_rows, json_path)
        if check:
            check_chaos_large(large_rows)
        return
    if suite == "remote":
        remote_rows = run_remote(scale, shards=shards, transport=transport)
        remote_rows += run_rebalance(scale)
        emit(remote_rows, "remote plan-over-wire vs the serial round loop")
        if json_path:
            write_json(remote_rows, json_path)
        if check:
            check_remote(remote_rows)
            check_rebalance(remote_rows)
        return
    if suite == "chaos":
        chaos_rows = run_chaos(scale, shards=shards)
        emit(chaos_rows, "fleet churn over TCP under kill storms and packet faults")
        if json_path:
            write_json(chaos_rows, json_path)
        if check:
            check_chaos(chaos_rows)
        return
    if suite == "generated":
        gen_rows = run_generated(scale, spec_path=spec, live=live)
        emit(gen_rows,
             "generated scenarios: replay rail, wave-forming gate, live mode")
        if json_path:
            write_json(gen_rows, json_path)
        if check:
            check_generated(gen_rows, live=live)
        return
    if suite == "fairness":
        fairness_rows = run_fairness(scale)
        emit(fairness_rows, "multi-tenant fairness (WFQ vs FCFS ablation)")
        if json_path:
            write_json(fairness_rows, json_path)
        if check:
            check_fairness(fairness_rows)
        return
    if suite == "shards":
        shard_rows = run_shards(scale, shards=shards)
        emit(shard_rows, "sharded plan/commit rounds vs the serial round loop")
        if json_path:
            write_json(shard_rows, json_path)
        if check:
            check_shards(shard_rows, shards=shards)
        return
    sched_rows = run(scale)
    emit(sched_rows, "scheduler decision latency (dense vs reference DP)")
    churn_rows = run_churn(scale)
    emit(churn_rows, "steady-state churn decision latency (warm orchestrator)")
    shard_rows = run_shards(scale, shards=shards)
    emit(shard_rows, "sharded plan/commit rounds vs the serial round loop")
    if json_path:
        write_json(sched_rows + churn_rows + shard_rows, json_path)
    if check:
        check_dense_fast_path(sched_rows)


if __name__ == "__main__":
    import argparse

    def _scale_arg(v: str):
        # float multiplier, or the literal "large": the chaos suite's
        # nightly scale (8 worker processes, O(100k) actions)
        return v if v == "large" else float(v)

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=_scale_arg, default=1.0,
                    help="workload multiplier, or 'large' with --suite "
                         "chaos for the nightly 8-process O(100k)-action "
                         "storm (writes BENCH_chaos_large.json)")
    ap.add_argument("--json", default=None,
                    help="output path for machine-readable results ('' = skip; "
                         "default: BENCH_scheduler.json for the latency suite, "
                         "BENCH_fairness.json for the fairness suite)")
    ap.add_argument("--check", action="store_true",
                    help="fail the suite's CI gate: dense-DP parity on "
                         f"{CHECK_SCENARIO} (latency suite), the weighted-"
                         "share / single-task-equivalence gates (fairness), "
                         "the >=1.5x-speedup / trace-identity gates "
                         "(shards), or the trace-identity / wire-exercised "
                         "gates (remote)")
    ap.add_argument("--suite",
                    choices=("latency", "fairness", "shards", "remote",
                             "chaos", "generated"),
                    default="latency",
                    help="latency = decision-latency scenarios (default); "
                         "fairness = multi-tenant weighted-share scenario; "
                         "shards = sharded plan/commit rounds vs serial; "
                         "remote = plan-over-wire shard workers vs serial "
                         "(plus the asymmetric-fleet rebalance rows), with "
                         "serialization overhead reported separately; "
                         "chaos = socket-fleet churn under kill/restart "
                         "storms and packet-level fault injection; "
                         "generated = spec-driven scenarios from the "
                         "scenario factory (replay rail, wave-forming "
                         "gate, optional --live kernel runs)")
    ap.add_argument("--spec", default=None,
                    help="generated suite: path to a scenario spec file "
                         "(JSON envelope, see docs/scenarios.md) to bench "
                         "in addition to the registered scenarios — a new "
                         "workload is a spec file, not a Python function")
    ap.add_argument("--live", action="store_true",
                    help="generated suite: also run the live-mode smoke "
                         "(real JAX kernel work on emulated XLA host "
                         "devices under RealClock) and gate sim-vs-live "
                         "launch-order equivalence")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count for the fleet-churn scenario (the "
                         "plan/commit engine's parallel planners)")
    ap.add_argument("--transport", choices=("loopback", "process"),
                    default="loopback",
                    help="remote suite: loopback = in-process workers behind "
                         "the full wire codec path (deterministic, the CI "
                         "gate); process = real worker OS processes")
    args = ap.parse_args()
    if args.json is None:
        # per-suite defaults keep any suite from overwriting another
        # suite's tracked baseline (the nightly large storm writes its
        # own file — it has no committed CI-scale baseline to protect)
        args.json = ("BENCH_chaos_large.json" if args.scale == "large"
                     else _SUITE_JSON[args.suite])
    if args.live and args.suite == "generated":
        # set the emulated-device flag before ANY jax import (the core
        # import chain is jax-free, so this is still early enough here)
        from repro.core.live import live_devices
        from repro.launch.compilation import enable_compile_cache

        live_devices(len(scenarios.live_smoke_spec().pools))
        enable_compile_cache()
    main(args.scale, args.json, args.check, args.suite, args.shards,
         args.transport, args.spec, args.live)
