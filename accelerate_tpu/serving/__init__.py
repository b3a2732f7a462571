"""Continuous-batching inference: paged KV cache, scheduler, engine, fleet.

The inference side of the stack (see docs/serving.md): one fixed-shape
jitted decode step stays hot while requests of any prompt length multiplex
through preallocated cache slots — zero steady-state recompiles, per-step
admission, immediate slot reuse on EOS. Above the single engine sits the
fleet layer (``router.py`` / ``fleet.py``): a health-aware
:class:`ServingRouter` spreads load over N engine replicas behind the same
``submit/cancel/step/run`` surface, fails requests over when a replica dies,
and folds the degradation ladder (shed → deadline-expire → quarantine)
fleet-wide. With per-replica ``roles=`` the fleet disaggregates into
prefill and decode pools: prompts prefill on one pool, the live KV hands
off page-by-page to the other (transactional, chaos-drilled, falling back
to re-prefill), and TTFT stops competing with decode steps for the same
chips. ``speculative.py`` adds draft-model speculative decoding on top of
the paged engine: a small draft proposes k tokens against its own paged KV
pool (sharing the engine's page tables), the target verifies the whole
window in ONE decode step, and tree mode forks shared prefix pages by
refcount to race several candidate branches. ``autoscale.py`` closes the loop
on fleet SHAPE: a :class:`RoleRebalancer` the router steps on a cadence
reads the signals the fleet already publishes and flips replicas between
starved and idle pools through the drain-safe machinery — with hysteresis
against thrash and a fail-static rung when its own signals degrade. Later
serving work (multi-host serve meshes) builds on these pieces.
"""

from .autoscale import AutoscalePolicy, RoleRebalancer, fleet_signals
from .engine import (
    ServingEngine,
    ServingResult,
    StepWatchdog,
    params_from_streamed,
    quantized_resident_params,
)
from .fleet import (
    REPLICA_ROLES,
    EngineReplica,
    HandoffLost,
    HealthPolicy,
    ReplicaLost,
    ReplicaState,
)
from .kv_cache import (
    SlotAllocator,
    bucket_for,
    kv_cache_bytes,
    paged_kv_cache_bytes,
    recurrent_state_bytes,
    prefill_buckets,
)
from .loadgen import (
    make_burst_trace,
    make_diurnal_trace,
    make_mixed_prompts,
    make_prompts,
    run_offered_load,
)
from .paging import PageAllocator, PagedKVCache, PrefixCache, pages_for
from .router import RoutedRequest, ServingRouter
from .scheduler import ContinuousBatchingScheduler, QueueFull, Request
from .speculative import SpeculativeConfig

__all__ = [
    "AutoscalePolicy",
    "ContinuousBatchingScheduler",
    "EngineReplica",
    "HandoffLost",
    "HealthPolicy",
    "REPLICA_ROLES",
    "PageAllocator",
    "PagedKVCache",
    "PrefixCache",
    "QueueFull",
    "ReplicaLost",
    "ReplicaState",
    "Request",
    "RoleRebalancer",
    "RoutedRequest",
    "ServingEngine",
    "ServingResult",
    "ServingRouter",
    "SlotAllocator",
    "SpeculativeConfig",
    "StepWatchdog",
    "bucket_for",
    "fleet_signals",
    "kv_cache_bytes",
    "make_burst_trace",
    "make_diurnal_trace",
    "make_mixed_prompts",
    "make_prompts",
    "paged_kv_cache_bytes",
    "recurrent_state_bytes",
    "pages_for",
    "params_from_streamed",
    "quantized_resident_params",
    "prefill_buckets",
    "run_offered_load",
]
