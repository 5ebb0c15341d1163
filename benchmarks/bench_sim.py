"""Simulator benchmark: per-scenario simulated step times -> BENCH_sim.json.

The paper's target-application scenarios at a phi sweep, a multi-tenant +
fabric-contention cell (per-tenant slowdown at 1:1 vs 4:1
oversubscription), the online-scheduler SLO cell (FIFO vs rack-aware
packing p99 JCT + energy-per-job), the preemption-checkpointing cell
(reset vs spill/restore preemption wasted work on the pinned urgent-job
stream), the gang-scheduled pipeline cell (1F1B/GPipe bubble fraction
vs the (p-1)/(m+p-1) analytic, whole-gang preempt wasted work under
reset vs spill, backend trace identity), the engine-scale events/sec
cell (array vs legacy hot-loop backends on the pinned 64-node
pipelined-shuffle-waves workload), the engine-xscale cell (the
256-node ~100k-task timed-queue/solver matrix: calendar vs heap event
queues, numpy vs jax.jit water-fill, per-phase timing shares), plus
the closed-form cross-validation:

    PYTHONPATH=src python -m benchmarks.bench_sim           # full sweep
    PYTHONPATH=src python -m benchmarks.bench_sim --smoke   # CI lane
    PYTHONPATH=src python -m benchmarks.bench_sim \
        --smoke --cell engine_scale                         # one cell

Every scenario records its event count and events/sec (per-scenario
wall times are `time.perf_counter` deltas); the ``engine_scale``
scenario additionally runs both engine backends and records
``alloc_speedup`` (array events/sec over legacy events/sec) and
``bit_identical``, which the ``engine-perf`` CI job gates on.

Training replays a dry-run trace from artifacts/dryrun when present,
falling back to a synthetic llama-scale trace so the benchmark runs on a
clean checkout.

BENCH_sim.json is an **append-only history**: every invocation appends
one run stamped with the git SHA and ``SCHEMA_VERSION``; when the
on-disk schema version differs the writer refuses with a clear error
instead of silently mixing shapes (move the old file aside to start a
new history).  Readers take ``runs[-1]`` for the latest numbers.
"""
import argparse
import json
import pathlib
import time

from repro.core import costmodel as cm
from repro.core.cluster import WorkloadProfile
from repro.sim import (Fabric, append_bench_run, compare_allocators,
                       compare_backends, compare_engine_variants,
                       compare_policies, cross_validate_bigquery,
                       lovelock_cluster, measure_interference,
                       multi_tenant, perf_digest,
                       pipeline_bubble_report,
                       pipelined_shuffle_waves,
                       reference_tenants, scatter_gather,
                       recorder_overhead, simulate_mu,
                       skewed_analytics_mix, summarize,
                       synthetic_trace, trace_from_record,
                       traditional_cluster, training_from_trace)
from repro.sim.sched import (ClusterScheduler, analytics_template,
                             energy_report, gang_summary,
                             pipeline_template, reference_job_stream,
                             reference_preempt_stream, trace_stream)

ROOT = pathlib.Path(__file__).resolve().parents[1]
ART = ROOT / "artifacts" / "dryrun"

# bump when the per-run dict shape changes incompatibly; the writer
# refuses to append to a history with a different version
# (v3: per-scenario n_events/events_per_sec, engine_scale cell,
# perf_counter wall times; v4: engine_scale carries a ``recorder``
# digest — flight-recorder overhead on the same pinned cell; v5: the
# engine_xscale cell — 256-node ~100k-task timed-queue/solver matrix
# with per-phase timing shares and jit/legacy anchor sub-cells)
SCHEMA_VERSION = 5

# physical-ish rates for the training scenario (bytes/s)
NIC_BW = 25e9          # 200 Gb/s NIC
ICI_BW = 45e9


def _bigquery_profile():
    return WorkloadProfile(cpu_fraction=cm.BIGQUERY_CPU_FRACTION,
                           network_fraction=cm.BIGQUERY_NETWORK_FRACTION)


def scenario_shuffle(phis, n_servers):
    out = {"n_events": 0}
    prof = _bigquery_profile()
    for phi in phis:
        r = simulate_mu(prof, phi, n_servers=n_servers)
        out[str(phi)] = {"mu": r["mu"],
                         "t_traditional_s": r["t_traditional"],
                         "t_lovelock_s": r["t_lovelock"]}
        out["n_events"] += sum(r["n_events"].values())
    return out


def scenario_scatter_gather(phis, n_servers):
    """Fan-out query: the incast at the root is NIC-bound, so phi helps
    only the scatter/compute legs — a case the closed form cannot see."""
    kw = dict(request_bytes_total=0.2, response_bytes_total=2.0,
              cpu_work_per_worker=0.5)
    base = traditional_cluster(n_servers, cpu_rate=cm.MILAN_SYSTEM_SPEEDUP)
    res0 = base.engine().run(scatter_gather(base, **kw))
    t0 = res0.makespan
    out = {"n_events": len(res0.events)}
    for phi in phis:
        topo = lovelock_cluster(n_servers, phi)
        res1 = topo.engine().run(scatter_gather(topo, **kw))
        out[str(phi)] = {"mu": res1.makespan / t0, "t_traditional_s": t0,
                         "t_lovelock_s": res1.makespan}
        out["n_events"] += len(res1.events)
    return out


def _load_trace():
    if ART.exists():
        for f in sorted(ART.glob("*__single.json")):
            rec = json.loads(f.read_text())
            if rec.get("status") == "ok":
                return f.stem, trace_from_record(rec)
    return "synthetic", synthetic_trace()


def scenario_training(phis, n_servers, steps):
    name, trace = _load_trace()
    out = {"trace": name, "n_events": 0}
    for phi in phis:
        # accel_rate=1: the trace is per device group and each node runs
        # one; phi changes node count (and aggregate DCN load), not
        # accelerator speed
        topo = lovelock_cluster(n_servers, phi, nic_bw=NIC_BW,
                                ici_bw=ICI_BW, accel_rate=1.0)
        res = topo.engine().run(
            training_from_trace(topo, trace, steps=steps))
        s = summarize(res, name=f"training@phi={phi}")
        out[str(phi)] = {"step_time_s": res.makespan / steps,
                         "makespan_s": res.makespan,
                         "utilization": s["utilization"]}
        out["n_events"] += len(res.events)
    # failure scenario at phi=1: checkpoint/replay recovery cost
    topo = lovelock_cluster(n_servers, 1, nic_bw=NIC_BW, ici_bw=ICI_BW,
                            accel_rate=1.0)
    fail = topo.engine().run(training_from_trace(
        topo, trace, steps=steps, failures=[("nic0", steps // 2)]))
    out["n_events"] += len(fail.events)
    out["failure_recovery_overhead_s"] = (
        fail.makespan - out["1"]["makespan_s"])
    return out


def scenario_multi_tenant(n_servers):
    """Co-located shuffle + training + storage replay on a finite fabric:
    per-tenant slowdown vs isolated runs at 1:1 and 4:1 oversubscription
    — the disaggregation-claim stressor (§1/§5.2) the single-tenant
    scenarios cannot see."""
    tenants = reference_tenants(n_servers)
    out = {"n_events": 0}
    rack = max(2, n_servers // 2)
    for oversub in (1.0, 4.0):
        rep = measure_interference(
            lambda: lovelock_cluster(
                n_servers, 1, accel_rate=1.0, storage_nodes=2,
                fabric=Fabric(rack_size=rack, oversubscription=oversub)),
            tenants)
        out["n_events"] += rep["n_events"]
        out[f"{oversub:g}:1"] = {
            "slowdown": {k: round(v, 4) for k, v in
                         rep["slowdown"].items()},
            "isolated_s": rep["isolated"],
            "colocated_makespan_s": rep["makespan"],
        }
    return out


def scenario_analytics_skew():
    """Skewed incast+shuffle on a 2:1 fabric core — the allocator
    regression cell: a hot-joiner analytics DAG co-located with a
    balanced background shuffle, makespan under progressive filling vs
    max-min water-filling.  Water-filling reclaims the core share the
    rx-pinned incast flows leave stranded; a future allocator regression
    shows up as speedup sliding back toward 1.0.

    The cell is pinned at 8 nodes / 2 racks so the tracked number is
    identical between --smoke and the full sweep."""
    n_servers = 8

    def make_topo():
        return lovelock_cluster(
            n_servers, 1, accel_rate=1.0,
            fabric=Fabric(rack_size=4, oversubscription=2.0,
                          core_oversubscription=2.0))

    skew = 0.8
    tenants = skewed_analytics_mix(skew)

    def build(topo):
        return list(multi_tenant(topo, tenants).tasks)

    cmp = compare_allocators(make_topo, build)
    rep = measure_interference(make_topo, tenants)
    s = summarize(cmp["results"]["waterfill"], name="analytics_skew")
    return {
        "fabric": "2:1 core",
        "skew": skew,
        "n_events": (sum(len(r.events) for r in cmp["results"].values())
                     + rep["n_events"]),
        "progressive_makespan_s": cmp["progressive"],
        "waterfill_makespan_s": cmp["waterfill"],
        "waterfill_speedup": round(cmp["speedup"], 4),
        "interference_slowdown": {k: round(v, 4)
                                  for k, v in rep["slowdown"].items()},
        "utilization_busy": s["utilization"],
        "utilization_utilized": s["utilized"],
    }


def scenario_scheduler_slo():
    """Online-scheduler SLO cell: the pinned `reference_job_stream`
    (mixed-footprint skewed analytics + shuffles, Poisson arrivals) on
    an 8-node 2-rack 2:1-core fabric, scheduled FIFO vs rack-aware
    packing.  Packing keeps every job inside one ToR while first-fit
    FIFO fragments placements across the oversubscribed core, so
    ``packing_p99_speedup`` (FIFO p99 JCT / packing p99 JCT) must stay
    above 1.0 — CI gates on it.  Energy-per-job comes from the
    `sched.metrics` utilized_time x `core.costmodel` power join.

    Pinned at 8 nodes / 2 racks / seed 0 so the tracked numbers are
    identical between --smoke and the full sweep."""
    n_servers = 8

    def make_topo():
        return lovelock_cluster(
            n_servers, 1, accel_rate=1.0,
            fabric=Fabric(rack_size=4, oversubscription=2.0,
                          core_oversubscription=2.0))

    rate = 0.45
    jobs = reference_job_stream(rate=rate)
    cmp = compare_policies(make_topo, jobs, policies=("fifo", "pack"))
    energy = energy_report(cmp["scheds"]["pack"])
    return {
        "fabric": "2:1 core",
        "arrival_rate_jobs_per_s": rate,
        "n_jobs": len(jobs),
        "n_events": sum(len(sr.result.events)
                        for sr in cmp["scheds"].values()),
        "fifo": {k: v for k, v in cmp["slo"]["fifo"].items()
                 if k != "policy"},
        "pack": {k: v for k, v in cmp["slo"]["pack"].items()
                 if k != "policy"},
        "packing_p99_speedup": round(cmp["p99_speedup"], 4),
        "pack_energy_per_job": round(energy["energy_per_job"], 4),
        "pack_active_energy_per_job": round(
            energy["active_energy_per_job"], 4),
    }


def scenario_preempt_ckpt():
    """Preemption-checkpointing cell: the pinned `reference_preempt_stream`
    (reference mix + two urgent mid-stream arrivals that must preempt)
    on an 8-node 2-rack 2:1-core fabric with two storage nodes,
    scheduled under reset-semantics priority preemption (``preempt``)
    vs spill/restore checkpointing preemption (``preempt-ckpt``).

    ``spill_wasted_work_ratio`` (spill wasted work / reset wasted work)
    must stay strictly below 1.0 — spilling a victim's state to storage
    and restoring it at resume replays strictly less progress than
    resetting it — and every spilled byte is charged to the fabric:
    the storage nodes' ``utilized_time`` is nonzero exactly because of
    the checkpoint traffic.  CI gates on both.

    Pinned at 8 nodes / 2 racks / 2 storage / seed 0 so the tracked
    numbers are identical between --smoke and the full sweep."""
    n_servers = 8

    def make_topo():
        # rack_size=5: nic0-4 | nic5-7 + both storage nodes — the
        # 8 compute nodes span exactly 2 racks
        return lovelock_cluster(
            n_servers, 1, accel_rate=1.0, storage_nodes=2,
            fabric=Fabric(rack_size=5, oversubscription=2.0,
                          core_oversubscription=2.0))

    jobs = reference_preempt_stream()
    cmp = compare_policies(make_topo, jobs,
                           policies=("preempt", "preempt-ckpt"))
    keep = ("p50_jct_s", "p99_jct_s", "preemptions",
            "spill_preemptions", "wasted_work", "spilled_bytes",
            "restored_bytes", "storage_residency_byte_s", "complete")
    spill_sr = cmp["scheds"]["preempt-ckpt+pack"]
    storage_util = {
        u: round(max(secs for rname, secs in
                     spill_sr.result.utilized_time.items()
                     if rname.startswith(f"{u}:")), 4)
        for u in spill_sr.topo.storage_node_names}
    return {
        "fabric": "2:1 core",
        "n_jobs": len(jobs),
        "n_events": sum(len(sr.result.events)
                        for sr in cmp["scheds"].values()),
        "reset": {k: cmp["slo"]["preempt+pack"][k] for k in keep},
        "spill": {k: cmp["slo"]["preempt-ckpt+pack"][k] for k in keep},
        "spill_wasted_work_ratio": round(cmp["wasted_work_ratio"], 4),
        "spill_p99_speedup": round(cmp["p99_speedup"], 4),
        "storage_utilized_time_s": storage_util,
    }


def scenario_engine_scale(smoke=False, trace_out=None):
    """Engine events/sec cell: the pinned 64-node / 4x16-rack / 2:1
    fabric `pipelined_shuffle_waves` workload (per-task deterministic
    work jitter, so completions spread into distinct events) run under
    both hot-loop backends.  ``alloc_speedup`` is array events/sec over
    legacy events/sec — the incremental-vectorized-core headline the
    ``engine-perf`` CI job gates on (>= 5x in CI for runner headroom;
    >= 10x on the full cell locally) — and ``bit_identical`` must stay
    true: a perf number from a drifted trace is invalid.

    The full cell is waves=5 (~5.8k tasks); --smoke drops to waves=2
    (~2.3k tasks) to keep the CI lane short without changing the
    topology or the per-event working set.

    The ``recorder`` digest prices the observability layer on the same
    pinned cell (array backend): events/sec with a
    `repro.sim.obs.FlightRecorder` attached, the on/off
    ``overhead_ratio`` the ``obs`` CI lane gates on, and
    ``identical_events`` — the recorder must be read-only.  With
    ``trace_out`` set the recorder's Perfetto export is written there
    (the ``--trace-out`` CLI flag; load at https://ui.perfetto.dev)."""
    waves = 2 if smoke else 5

    def make_topo():
        return lovelock_cluster(
            64, 1, fabric=Fabric(rack_size=16, oversubscription=2.0))

    def build(topo):
        return pipelined_shuffle_waves(topo, waves=waves,
                                       tasks_per_node=2,
                                       jitter=0.35, seed=7)

    cmp = compare_backends(make_topo, build)
    cmp.pop("results")
    out = {
        "n_nodes": 64,
        "racks": "4x16",
        "fabric": "2:1",
        "waves": waves,
        "n_tasks": cmp["legacy"]["n_events"],
        "n_events": (cmp["legacy"]["n_events"]
                     + cmp["array"]["n_events"]),
        "legacy": cmp["legacy"],
        "array": cmp["array"],
        "alloc_speedup": round(cmp["speedup"], 3),
        "bit_identical": cmp["bit_identical"],
    }
    for side in ("legacy", "array"):
        out[side] = dict(out[side],
                         wall_s=round(out[side]["wall_s"], 3),
                         events_per_sec=round(
                             out[side]["events_per_sec"], 1))
    ovh = recorder_overhead(make_topo, build)
    recorder = ovh.pop("recorder")
    ovh.pop("results")
    out["recorder"] = {
        "wall_s": round(ovh["on"]["wall_s"], 3),
        "events_per_sec": round(ovh["on"]["events_per_sec"], 1),
        "overhead_ratio": round(ovh["overhead_ratio"], 4),
        "identical_events": ovh["identical_events"],
        "n_spans": ovh["n_spans"],
    }
    if trace_out is not None:
        from repro.sim.obs import to_json
        pathlib.Path(trace_out).write_text(to_json(recorder))
    return out


def scenario_engine_xscale(smoke=False):
    """Engine *extreme*-scale cell: 256 nodes / 16x16 racks / 2:1
    fabric, ~101k `pipelined_shuffle_waves` tasks (waves=22; --smoke
    drops to waves=14, ~64.5k tasks — same topology, same per-event
    working set), run as a timed-queue/solver matrix on the array
    backend.  The final wave arrives as eight staggered deferred
    `submit` batches, a node fails and recovers mid-run, and 32 control
    callbacks fire on a fixed cadence — so the cell exercises the timed
    event queue (push/pop/peek under live rewinds), not just the
    numeric core.

    Tracked numbers the ``engine-perf`` CI job gates on:

      * ``bit_identical`` — the calendar-queue run must replay the
        heap-queue reference trace byte-for-byte (correctness first; a
        perf number from a drifted trace is invalid);
      * ``calendar`` events/sec — an absolute floor, so the default
        configuration can't quietly get slower;
      * ``calendar_speedup`` — best-of-``repeats`` calendar wall over
        heap wall.  The queue's own push/pop/peek work is a sub-1%
        share of this cell's wall (``phases`` shows solve dominating;
        the ``events`` phase is mostly completion handling, identical
        on both sides), so the true ratio is ~1.0 and the CI floor
        sits at 0.95 to absorb shared-runner noise — the gate catches
        a queue that *regresses the engine*, while
        `tests/test_sim_calq` pins the queue's own semantics.

    Two anchor sub-cells complete the matrix honestly rather than
    cheaply: ``jit`` runs the jax.jit water-fill solver on a pinned
    waves=2 slice (~9.2k tasks) of the *same* 256-node topology —
    bit-identical by construction, recorded non-gating because on CPU
    XLA the compiled round loop loses to numpy's (scatter + per-round
    sync dominate; see README) — and ``legacy_anchor`` (full sweep
    only) prices the dict core on a waves=1 slice, where its O(n)
    per-event min_dt already costs minutes; running it on the 100k
    cell would take hours, which *is* the tentpole's motivation."""
    waves = 14 if smoke else 22
    n_nodes, rack = 256, 16

    def make_topo():
        return lovelock_cluster(
            n_nodes, 1,
            fabric=Fabric(rack_size=rack, oversubscription=2.0))

    def tasks_of(topo, w):
        return list(pipelined_shuffle_waves(topo, waves=w,
                                            tasks_per_node=2,
                                            jitter=0.35, seed=7))

    def harness(w):
        """build()/prepare() pair: all waves but the last at t=0, the
        last wave as deferred contiguous batches (emission order is
        dependency order, so a batch's deps live in earlier batches)."""
        def split(topo):
            tasks = tasks_of(topo, w)
            n_defer = len(tasks) // w
            return tasks[:len(tasks) - n_defer], tasks[len(tasks) - n_defer:]

        def build(topo):
            return split(topo)[0]

        def prepare(eng, topo):
            defer = split(topo)[1]
            chunk = (len(defer) + 7) // 8
            for i in range(8):
                batch = defer[i * chunk:(i + 1) * chunk]
                if batch:
                    eng.submit(batch, at=1.0 + 0.5 * i)
            eng.inject_failure("nic3", at=0.8, recover_at=1.3)
            for i in range(32):
                eng.call_at(0.25 + 0.25 * i, lambda ctl: None)

        return build, prepare

    build, prepare = harness(waves)
    cmp = compare_engine_variants(
        make_topo, build,
        {"heap": dict(backend="array", timed_queue="heap"),
         "calendar": dict(backend="array", timed_queue="calendar")},
        repeats=2 if smoke else 3, prepare=prepare)
    cmp.pop("results")
    n_tasks = len(tasks_of(make_topo(), waves))
    out = {
        "n_nodes": n_nodes,
        "racks": f"{n_nodes // rack}x{rack}",
        "fabric": "2:1",
        "waves": waves,
        "n_tasks": n_tasks,
        "n_events": (cmp["heap"]["n_events"]
                     + cmp["calendar"]["n_events"]),
        "bit_identical": cmp["bit_identical"]["calendar"],
        "calendar_speedup": round(cmp["speedup"]["calendar"], 4),
    }
    for name in ("heap", "calendar"):
        v = cmp[name]
        out[name] = {
            "wall_s": round(v["wall_s"], 3),
            "events_per_sec": round(v["events_per_sec"], 1),
            "queue_resizes": v["alloc_stats"]["queue_resizes"],
            "mindt_evals": v["alloc_stats"]["mindt_evals"],
            "mindt_skips": v["alloc_stats"]["mindt_skips"],
            "phases": v["phases"],
        }

    # jit anchor: same topology, pinned waves=2 slice, numpy reference
    jb, jp = harness(2)
    jcmp = compare_engine_variants(
        make_topo, jb,
        {"numpy": dict(backend="array"),
         "jit": dict(backend="array", solver="jit")},
        repeats=1, prepare=jp)
    jcmp.pop("results")
    out["n_events"] += jcmp["numpy"]["n_events"] + jcmp["jit"]["n_events"]
    out["jit"] = {
        "waves": 2,
        "n_tasks": len(tasks_of(make_topo(), 2)),
        "bit_identical": jcmp["bit_identical"]["jit"],
        "speedup_vs_numpy": round(jcmp["speedup"]["jit"], 4),
        "events_per_sec": round(jcmp["jit"]["events_per_sec"], 1),
        "n_solves": jcmp["jit"]["alloc_stats"]["n_solves"],
    }

    if not smoke:
        lb, lp = harness(1)
        lcmp = compare_engine_variants(
            make_topo, lb,
            {"array": dict(backend="array"),
             "legacy": dict(backend="legacy")},
            repeats=1, prepare=lp)
        lcmp.pop("results")
        out["n_events"] += (lcmp["array"]["n_events"]
                            + lcmp["legacy"]["n_events"])
        out["legacy_anchor"] = {
            "waves": 1,
            "n_tasks": len(tasks_of(make_topo(), 1)),
            "bit_identical": lcmp["bit_identical"]["legacy"],
            "array_speedup": round(1.0 / lcmp["speedup"]["legacy"], 2),
            "legacy_events_per_sec": round(
                lcmp["legacy"]["events_per_sec"], 1),
        }
    return out


def scenario_pipeline_gang():
    """Gang-scheduled pipeline cell: a 4-stage 1F1B x 8-microbatch
    pipeline-parallel training job (one gang) on an 8-node 2-rack
    2:1-core fabric with two storage nodes, hit mid-run by an urgent
    arrival that preempts it.

    Three tracked numbers.  ``bubble_fraction`` per schedule must sit
    within 5% of the analytic (p-1)/(m+p-1) = 3/11 on the bubble-only
    cell (equal fwd/bwd cost, no transfers) — the engine's
    idle-while-peer-busy gang accounting reproducing the pipeline
    textbook figure.  ``gang_wasted_work_ratio`` (preempt-ckpt wasted
    work / reset-preempt wasted work on the same stream) must stay
    strictly below 1.0: spilling every stage's state and holding the
    gang at the restore barrier replays strictly less progress than
    resetting all stages.  ``bit_identical`` must stay true: the
    gang-preempted scheduled run produces byte-identical event traces
    across the array and legacy engine backends.

    Pinned at 8 nodes / 2 racks / 2 storage / p=4 / m=8 / urgent at
    t=8 so the tracked numbers are identical between --smoke and the
    full sweep."""
    n_servers = 8

    def make_topo():
        # same pinned layout as preempt_ckpt: nic0-4 | nic5-7 + both
        # storage nodes span exactly 2 racks on a 2:1 core
        return lovelock_cluster(
            n_servers, 1, accel_rate=1.0, storage_nodes=2,
            fabric=Fabric(rack_size=5, oversubscription=2.0,
                          core_oversubscription=2.0))

    p, m = 4, 8
    bubbles = pipeline_bubble_report(make_topo, stages=p,
                                     microbatches=m)
    n_events = 0

    jobs = trace_stream([
        (0.0, pipeline_template(p, microbatches=m)),
        (8.0, analytics_template(6, priority=5, name="urgent")),
    ])
    cmp = compare_policies(make_topo, jobs,
                           policies=("preempt", "preempt-ckpt"))
    n_events += sum(len(sr.result.events)
                    for sr in cmp["scheds"].values())
    gangs = {name: gang_summary(sr)
             for name, sr in cmp["scheds"].items()}

    # backend identity on the gang-preempted stream: spill, restore
    # barrier and urgent arrival all replayed on both numeric cores
    traces = {}
    for backend in ("legacy", "array"):
        sr = ClusterScheduler(make_topo(), "preempt-ckpt",
                              backend=backend).run(jobs)
        traces[backend] = sr.result
        n_events += len(sr.result.events)
    bit_identical = (
        traces["legacy"].events == traces["array"].events
        and traces["legacy"].finish_times == traces["array"].finish_times)

    keep = ("p99_jct_s", "preemptions", "spill_preemptions",
            "wasted_work", "spilled_bytes", "restored_bytes", "complete")
    return {
        "fabric": "2:1 core",
        "stages": p,
        "microbatches": m,
        "n_events": n_events,
        "bubble_analytic": round(bubbles["analytic"], 6),
        "bubble_fraction": {
            s: round(r["bubble_fraction"], 6)
            for s, r in bubbles["schedules"].items()},
        "reset": {k: cmp["slo"]["preempt+pack"][k] for k in keep},
        "spill": {k: cmp["slo"]["preempt-ckpt+pack"][k] for k in keep},
        "gangs": {name: {g: {k: round(v, 4) if isinstance(v, float)
                             else v for k, v in row.items()}
                         for g, row in gg.items()}
                  for name, gg in gangs.items()},
        "gang_wasted_work_ratio": round(cmp["wasted_work_ratio"], 4),
        "bit_identical": bit_identical,
    }


SCENARIOS = ("shuffle", "scatter_gather", "training", "multi_tenant",
             "analytics_skew", "scheduler_slo", "preempt_ckpt",
             "pipeline_gang", "engine_scale", "engine_xscale")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep for the CI lane")
    ap.add_argument("--cell", choices=SCENARIOS, default=None,
                    help="run a single scenario (the run still appends "
                         "to the history; 'cells' records coverage)")
    ap.add_argument("--out", default=str(ROOT / "BENCH_sim.json"))
    ap.add_argument("--trace-out", default=None,
                    help="write the engine_scale cell's flight-recorder "
                         "Perfetto trace_event JSON here")
    args = ap.parse_args()

    phis = (1, 2, 3) if args.smoke else (1, 2, 3, 4, 6, 8)
    n_servers = 4 if args.smoke else 16
    steps = 4 if args.smoke else 16

    runners = {
        "shuffle": lambda: scenario_shuffle(phis, n_servers),
        "scatter_gather":
            lambda: scenario_scatter_gather(phis, n_servers),
        "training": lambda: scenario_training(phis, n_servers, steps),
        "multi_tenant": lambda: scenario_multi_tenant(n_servers),
        "analytics_skew": scenario_analytics_skew,
        "scheduler_slo": scenario_scheduler_slo,
        "preempt_ckpt": scenario_preempt_ckpt,
        "pipeline_gang": scenario_pipeline_gang,
        "engine_scale": lambda: scenario_engine_scale(
            args.smoke, trace_out=args.trace_out),
        "engine_xscale": lambda: scenario_engine_xscale(args.smoke),
    }
    cells = (args.cell,) if args.cell else SCENARIOS

    t0 = time.perf_counter()
    bench = {
        "bench": "sim",
        "smoke": args.smoke,
        "cells": list(cells),
        "n_servers": n_servers,
        "scenarios": {},
    }
    if args.cell is None:
        bench["cross_validation"] = cross_validate_bigquery(
            n_servers=max(n_servers, 4))
    for name in cells:
        t1 = time.perf_counter()
        scn = runners[name]()
        scn["perf"] = perf_digest(scn.pop("n_events", 0),
                                  time.perf_counter() - t1)
        bench["scenarios"][name] = scn
    bench["wall_s"] = round(time.perf_counter() - t0, 3)
    append_bench_run(args.out, bench, schema_version=SCHEMA_VERSION)
    print(json.dumps(bench, indent=1))
    scns = bench["scenarios"]
    digest = [f"wall {bench['wall_s']}s"]
    if "cross_validation" in bench:
        digest.append(f"cross-validation worst rel_err "
                      f"{max(r['rel_err'] for r in bench['cross_validation']):.2e}")
    if "analytics_skew" in scns:
        digest.append(f"water-filling speedup on skewed cell "
                      f"{scns['analytics_skew']['waterfill_speedup']}x")
    if "scheduler_slo" in scns:
        digest.append(f"packing p99-JCT speedup "
                      f"{scns['scheduler_slo']['packing_p99_speedup']}x")
    if "preempt_ckpt" in scns:
        digest.append(f"spill wasted-work ratio "
                      f"{scns['preempt_ckpt']['spill_wasted_work_ratio']}")
    if "pipeline_gang" in scns:
        pg = scns["pipeline_gang"]
        digest.append(
            f"pipeline bubble {pg['bubble_fraction']['1f1b']} "
            f"(analytic {pg['bubble_analytic']}), gang wasted-work "
            f"ratio {pg['gang_wasted_work_ratio']}, "
            f"bit_identical={pg['bit_identical']}")
    if "engine_scale" in scns:
        es = scns["engine_scale"]
        digest.append(
            f"engine alloc_speedup {es['alloc_speedup']}x "
            f"({es['array']['events_per_sec']:.0f} ev/s array vs "
            f"{es['legacy']['events_per_sec']:.0f} legacy, "
            f"bit_identical={es['bit_identical']})")
        digest.append(
            f"recorder overhead {es['recorder']['overhead_ratio']}x "
            f"({es['recorder']['events_per_sec']:.0f} ev/s, "
            f"read_only={es['recorder']['identical_events']})")
    if "engine_xscale" in scns:
        ex = scns["engine_xscale"]
        digest.append(
            f"xscale {ex['n_tasks']} tasks: calendar "
            f"{ex['calendar']['events_per_sec']:.0f} ev/s "
            f"({ex['calendar_speedup']}x vs heap, "
            f"bit_identical={ex['bit_identical']}), jit anchor "
            f"{ex['jit']['speedup_vs_numpy']}x "
            f"(active={ex['jit']['active']}, "
            f"bit_identical={ex['jit']['bit_identical']})")
    print(f"\nappended to {args.out}  ({', '.join(digest)})")


if __name__ == "__main__":
    main()
