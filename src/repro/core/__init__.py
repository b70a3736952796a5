"""Core library: the paper's node-level call-scheduling method.

Public API:
  - policies: FIFO, SEPT, EECT, RECT, FairChoice (make_policy)
  - RuntimeEstimator: last-10 processing-time estimator (+ FC counters)
  - NodeScheduler: slot-based non-preemptive scheduler
  - ContainerPool: warm/prewarm/cold pool with both admission disciplines
  - simulator / cluster: discrete-event reproduction of the paper's setup
  - workload: SeBS Table-I profiles + Gatling-style burst generator
  - metrics: response-time / stretch summaries

Simulation backends (``simulate_single_node(..., backend=...)`` and the
``SweepSpec(backends=...)`` axis):
  - ``"reference"`` -- the discrete-event loop; supports every scenario and
    defines the semantics.
  - ``"vectorized"`` -- array fast path for the ours-mode single node
    (``core/fastpath.py``); **exact** (bit-identical metrics to the
    reference), including cold starts and tight-memory eviction.
  - ``"scan"`` -- batched ``jax.lax.scan`` variant; a whole grid runs as one
    scan over a padded request tensor (``run_cells_scan``).  Covers every
    ours-mode regime: always-warm cells (``scan_eligible``) in float32
    (~1e-6 agreement), ``warm=False`` cells with per-(node, fn) container
    tensors, and clusters with **time-varying capacity** (autoscaling via
    ``ClusterDynamics``, failure injection) plus straggler hedging inside
    the same kernel under float64 with bit-identical lost/backup/steal
    counts and realized ``CapacityTimeline``\\s.
  - ``"auto"`` -- the best supported engine per ``supports()`` capability
    matrix, reference elsewhere (the stock baseline and the documented
    duplicate-hedging x failures x push rejection).
  - ``SweepSpec(validate="cross-check")`` runs sampled eligible cells on
    both backends and raises :class:`~repro.core.sweep.BackendMismatchError`
    if any reported metric drifts beyond 1%.
"""

from .containers import AcquireResult, Container, ContainerPool
from .estimator import RuntimeEstimator
from .fastpath import (
    ScanBackend,
    ScanRejected,
    VectorizedBackend,
    cluster_scan_eligible,
    scan_bucket_timings,
    scan_cache_clear,
    scan_cache_stats,
    scan_eligible,
    scan_phase_totals,
    scan_timings_clear,
    simulate_cells_scan,
    simulate_cluster_cells_scan,
    simulate_cluster_scan,
    simulate_ours_vectorized,
)
from .metrics import Summary, merge_summaries, summarize, summarize_arrays
from .policies import EECT, FIFO, FairChoice, Policy, RECT, SEPT, make_policy
from .queues import PriorityQueue
from .request import CallRecord, Request
from .resilience import (
    AdmissionPolicy,
    ResilienceSpec,
    RetryPolicy,
    TimeoutSpec,
    retry_jitter_u,
)
from .flight import (
    CANONICAL_KINDS,
    DivergenceReport,
    FlightRecorder,
    SimTrace,
    TraceEvent,
    first_divergence,
    run_manifest,
    trace_from_requests,
    trace_from_result,
    write_manifest,
)
from .scheduler import NodeScheduler, StartDecision
from .simulator import (
    BaselineNodeSim,
    EventLoop,
    OursNodeSim,
    ReferenceBackend,
    SimBackend,
    SimResult,
    available_backends,
    get_backend,
    register_backend,
    simulate_single_node,
)
from .cluster import (
    CapacityTimeline,
    Cluster,
    ClusterConfig,
    ClusterDynamics,
    home_invoker_index,
    least_loaded_index,
    most_free_index,
    simulate_baseline_cluster,
    simulate_cluster,
)
from .stragglers import (
    HedgingSpec,
    NodeSpeedProfile,
    rolling_restart,
)
from .sweep import (
    BACKEND_CHOICES,
    BackendMismatchError,
    CellResult,
    ProgressReporter,
    SweepCell,
    SweepResult,
    SweepSpec,
    run_cell,
    run_cells_scan,
    run_sweep,
    triage_cell,
)
from .traces import (
    generate_trace_requests,
    load_azure_trace,
    requests_from_trace,
    stable_hash,
    tile_trace,
)
from .workload import (
    ARRIVAL_KINDS,
    FUNCTIONS,
    MEAN_IDLE_RESPONSE_S,
    PROFILES,
    SEBS_TABLE_I,
    STRETCH_REFERENCE_S,
    diurnal_arrivals,
    generate_burst,
    generate_fairness_burst,
    generate_trace_burst,
    mmpp_arrivals,
    poisson_arrivals,
    ramp_arrivals,
)

__all__ = [
    "ARRIVAL_KINDS",
    "AcquireResult",
    "AdmissionPolicy",
    "BACKEND_CHOICES",
    "BackendMismatchError",
    "BaselineNodeSim",
    "CANONICAL_KINDS",
    "CallRecord",
    "CapacityTimeline",
    "CellResult",
    "DivergenceReport",
    "FlightRecorder",
    "Cluster",
    "ClusterConfig",
    "ClusterDynamics",
    "Container",
    "ContainerPool",
    "EECT",
    "EventLoop",
    "FIFO",
    "FUNCTIONS",
    "FairChoice",
    "HedgingSpec",
    "MEAN_IDLE_RESPONSE_S",
    "NodeScheduler",
    "NodeSpeedProfile",
    "OursNodeSim",
    "PROFILES",
    "Policy",
    "PriorityQueue",
    "ProgressReporter",
    "RECT",
    "ResilienceSpec",
    "RetryPolicy",
    "ReferenceBackend",
    "Request",
    "RuntimeEstimator",
    "SEBS_TABLE_I",
    "SEPT",
    "STRETCH_REFERENCE_S",
    "ScanBackend",
    "ScanRejected",
    "SimBackend",
    "SimResult",
    "SimTrace",
    "StartDecision",
    "Summary",
    "SweepCell",
    "SweepResult",
    "SweepSpec",
    "TimeoutSpec",
    "VectorizedBackend",
    "available_backends",
    "cluster_scan_eligible",
    "diurnal_arrivals",
    "generate_burst",
    "generate_fairness_burst",
    "generate_trace_burst",
    "generate_trace_requests",
    "get_backend",
    "home_invoker_index",
    "least_loaded_index",
    "load_azure_trace",
    "make_policy",
    "merge_summaries",
    "mmpp_arrivals",
    "most_free_index",
    "poisson_arrivals",
    "ramp_arrivals",
    "TraceEvent",
    "first_divergence",
    "register_backend",
    "requests_from_trace",
    "retry_jitter_u",
    "rolling_restart",
    "run_cell",
    "run_cells_scan",
    "run_manifest",
    "run_sweep",
    "scan_bucket_timings",
    "scan_cache_clear",
    "scan_cache_stats",
    "scan_phase_totals",
    "scan_timings_clear",
    "scan_eligible",
    "simulate_baseline_cluster",
    "simulate_cells_scan",
    "simulate_cluster",
    "simulate_cluster_cells_scan",
    "simulate_cluster_scan",
    "simulate_ours_vectorized",
    "simulate_single_node",
    "stable_hash",
    "summarize",
    "summarize_arrays",
    "tile_trace",
    "trace_from_requests",
    "trace_from_result",
    "triage_cell",
    "write_manifest",
]
