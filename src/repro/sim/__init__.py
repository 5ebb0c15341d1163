"""repro.sim — discrete-event, trace-driven Lovelock cluster simulator.

Unifies the analytical pieces in `repro.core` (cost model, bandwidth
contention, collective traffic, failure/recovery) as pluggable components
of one event engine, so phi planning can be scored against *simulated*
slowdown — with queueing, incast, and failures — instead of only the
closed-form §5.2 projection (which it is cross-validated against in
`validate.cross_validate_bigquery`).

Beyond single-tenant replay the stack models the effects that stress the
paper's §1 disaggregation claim: a finite-capacity fabric (`Fabric`:
per-rack uplinks + core at a configurable oversubscription ratio)
shared by true per-flow max-min water-filling (`Engine`'s allocator;
`compare_allocators` scores it against the old progressive filling),
multi-stage analytics DAGs with a configurable hot joiner
(`analytics_dag`), storage-node traffic (`storage_replay` against
`NodeRole.STORAGE` nodes), multi-tenant co-location (`multi_tenant` +
`measure_interference`), and straggler-driven eviction
(`training_with_stragglers` feeds simulated step times to
`core.elastic.StragglerDetector` and injects its evictions back into
the timeline).

Workloads are built on a shared staged-program IR (`program`:
`Stage`/`Instr`/`Program` lowered to engine tasks by `lower`), which
also carries gang semantics: `pipeline_training` (1F1B / GPipe
instruction schedules over accelerator stages) and `rlhf_dataflow`
(generation fan-out feeding a co-scheduled trainer) tag their tasks
with a ``gang_id`` so the engine books pipeline-bubble time and
preempts/resumes the gang as a unit.

The `sched` subpackage adds the online control plane: job streams
arriving over time (Poisson or trace-driven), queueing and rack/role-
aware placement with priority preemption, incremental admission through
`Engine.submit`, and SLO/energy accounting (queueing delay, p50/p99
JCT, goodput, energy-per-job) — `compare_policies` scores policies
against each other the way `compare_allocators` scores allocators.

The `obs` subpackage is the observability layer: an opt-in
`obs.FlightRecorder` (``Engine(recorder=...)`` /
``ClusterScheduler(recorder=...)``) records task spans, scheduler
decisions, and exact per-resource rate curves at zero cost when
disabled; `obs.job_attribution` decomposes each job's JCT into
queue/compute/fabric/spill-restore/bubble seconds along the critical
path; `obs.to_json` exports a versioned Chrome/Perfetto trace
(`recorder_overhead` prices the whole layer for the obs CI lane).

Quickstart::

    from repro.core.cluster import WorkloadProfile
    from repro.sim import simulate_plan
    p = simulate_plan(WorkloadProfile(cpu_fraction=0.386,
                                      network_fraction=0.614),
                      n_servers=64, mu_max=1.0)
    print(p.phi, p.mu, p.cost_ratio)
"""
from repro.sim.engine import (ALLOCATORS, Engine, EventKind, Resource,
                              SimEvent, SimResult, SimulationStalled,
                              Task, progressive_fill_rates,
                              water_filling_rates)
from repro.sim.alloc import BACKENDS, SOLVERS
from repro.sim.calq import (TIMED_QUEUES, CalendarTimedQueue,
                            HeapTimedQueue, make_timed_queue)
from repro.sim.topology import (Fabric, NodeModel, Topology,
                                lovelock_cluster, topology_from_plan,
                                traditional_cluster)
from repro.sim.program import Instr, Program, Stage, lower
from repro.sim.workloads import (PIPELINE_SCHEDULES,
                                 MultiTenantWorkload, analytics_dag,
                                 multi_tenant, pipeline_training,
                                 pipelined_shuffle_waves,
                                 reference_tenants, rlhf_dataflow,
                                 scatter_gather, shuffle,
                                 skewed_analytics_mix, storage_replay,
                                 synthetic_trace, trace_from_record,
                                 training_from_trace,
                                 training_with_stragglers)
from repro.sim.validate import (compare_allocators, compare_backends,
                                compare_engine_variants,
                                compare_policies,
                                cross_validate_bigquery,
                                measure_interference,
                                pipeline_bubble_report, phase_shares,
                                recorder_overhead, simulate_mu,
                                simulate_plan)
from repro.sim.report import (append_bench_run, attach_attribution,
                              attach_scores, attach_slo,
                              attach_tenants, load_bench_history,
                              per_tenant, perf_digest, render,
                              summarize)
from repro.sim import obs, sched

__all__ = [
    "ALLOCATORS", "BACKENDS", "SOLVERS", "TIMED_QUEUES",
    "CalendarTimedQueue", "HeapTimedQueue", "make_timed_queue",
    "Engine", "EventKind", "Resource", "SimEvent",
    "SimResult", "SimulationStalled", "Task",
    "progressive_fill_rates", "water_filling_rates",
    "Fabric", "NodeModel", "Topology", "lovelock_cluster",
    "topology_from_plan", "traditional_cluster",
    "Instr", "Program", "Stage", "lower",
    "PIPELINE_SCHEDULES", "MultiTenantWorkload", "analytics_dag",
    "multi_tenant", "pipeline_training", "pipelined_shuffle_waves",
    "reference_tenants", "rlhf_dataflow", "scatter_gather", "shuffle",
    "skewed_analytics_mix",
    "storage_replay", "synthetic_trace", "trace_from_record",
    "training_from_trace", "training_with_stragglers",
    "compare_allocators", "compare_backends",
    "compare_engine_variants", "compare_policies",
    "cross_validate_bigquery",
    "measure_interference", "phase_shares", "pipeline_bubble_report",
    "recorder_overhead", "simulate_mu",
    "simulate_plan", "append_bench_run", "attach_attribution",
    "attach_scores", "attach_slo",
    "attach_tenants", "load_bench_history", "per_tenant", "perf_digest",
    "render", "summarize", "obs", "sched",
]
