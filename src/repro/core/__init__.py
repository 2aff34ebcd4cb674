"""The paper's primary contribution: an object-sharing caching system
("On a Caching System with Object Sharing", Kesidis et al., 2019).

Layers:

* :mod:`~repro.core.shared_lru` — Section III: J LRU-lists over one
  physical cache with per-object length apportionment and the
  ripple-eviction operator loop.
* :mod:`~repro.core.slru` — Section VII: Segmented-LRU (HOT/WARM/COLD).
* :mod:`~repro.core.fastsim` — the array-based Monte-Carlo engine:
  struct-of-arrays linked lists + whole-trace drivers (Python / C / XLA
  backends), event-equivalent to the reference classes above and 2-3
  orders of magnitude faster; use :func:`~repro.core.fastsim.
  simulate_trace` for anything that drives millions of IRM requests.
* :mod:`~repro.core.workingset` — Section IV: working-set approximation
  of hit probabilities (JAX fixed-point solver; L1/Lstar/L2/full).
* :mod:`~repro.core.admission` — Section IV-C: overbooking + admission.
* :mod:`~repro.core.rre` — Section IV-D: ripple-eviction reduction.
* :mod:`~repro.core.mcdos` — Section VI: the MCD-OS server semantics.
* :mod:`~repro.core.cluster` — fault-tolerant K-node MCD-OS cluster:
  consistent-hash ring with virtual nodes, seeded fault injection
  (fail/recover/add/remove), failover routing, graceful degradation.
* :mod:`~repro.core.baselines` — not-shared and pooled-LRU baselines.
* :mod:`~repro.core.irm` — IRM/Zipf traces and popularity estimation.

The device-side counterpart (paged KV pool + Pallas kernels) lives in
:mod:`repro.cacheblocks` and :mod:`repro.kernels`; the serving engine
that glues them together is :mod:`repro.serving`.
"""

from .shared_lru import (  # noqa: F401
    EvictionEvent,
    GetResult,
    RequestStats,
    SharedLRUCache,
)
from .slru import SegmentedSharedLRUCache  # noqa: F401
from .fastsim import (  # noqa: F401
    FastSegmentedSharedLRU,
    FastSharedLRU,
    SimParams,
    SimResult,
    SparseOccupancy,
    simulate_chunks,
    simulate_trace,
)
from .baselines import NotSharedSystem, PooledLRU, SimpleLRU  # noqa: F401
from .irm import (  # noqa: F401
    IRMTrace,
    PopularityEstimator,
    rate_matrix,
    sample_trace,
    sample_trace_chunks,
    zipf_popularities,
)
from .workingset import (  # noqa: F401
    WorkingSetSolution,
    attribution_matrix,
    expected_inverse_one_plus,
    hit_probabilities,
    solve_workingset,
    solve_workingset_unshared,
    virtual_footprint,
)
from .admission import (  # noqa: F401
    AdmissionController,
    AdmissionDecision,
    Tenant,
    virtual_allocations,
)
from .rre import RRECache, RREConfig, compare_ripple  # noqa: F401
from .cluster import (  # noqa: F401
    FaultEvent,
    FaultSpec,
    HashRing,
    default_ring,
    key_position,
    key_positions,
    simulate_cluster,
)
from .mcdos import MCDOSServer, MCDServer, consistent_route, run_trace  # noqa: F401
from .metrics import HitRecorder, LatencyRecorder, RippleStats, table_rows  # noqa: F401
