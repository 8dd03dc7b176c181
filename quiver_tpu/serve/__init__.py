"""quiver_tpu.serve — online inference engine.

Turns individual node-prediction requests into efficient fixed-shape device
work: dynamic micro-batching (bucketed pad-to-fixed shapes, one compiled
program per bucket, pre-traceable via `ServeEngine.warmup`), cross-request
coalescing (identical seeds within a flush window share one
sample/gather/forward), a params-versioned embedding cache (hot nodes
served from host memory; `update_params` fences in-flight work, then
invalidates), and pipelined dispatch (flushes run as assemble -> dispatch
-> resolve stages under a bounded `max_in_flight` window; the sampler key
stream and replay log stay deterministic in dispatch-index order). See
`engine.py` for the design and docs/api.md "Online serving" for the
contract.

`dist.py` scales the engine past one host: `DistServeEngine` routes
requests by seed ownership over the `HostRankTable` exchange (seed ids
out, logits back) to per-owner `ServeEngine`s serving from ~1/H topology
+ feature shards — docs/api.md "Distributed serving".

Round 15 makes the fleet production-shaped (docs/api.md "Fleet serving"):
hot-set replication (`DistServeEngine.refresh_replicas` mirrors the Zipf
head locally so head traffic never crosses the exchange), hedged/failover
dispatch (per-owner deadlines, re-route to replica/full-graph fallback,
flush-indexed ejection backoff, per-request error isolation), per-tenant
admission (`submit(node, tenant=)`: weighted flush quotas, deterministic
queue-depth shedding, per-tenant latency tails), and the deterministic
`faults.FaultInjector` that proves all of it replayable.

Round 17 makes the GRAPH live (docs/api.md "Streaming graphs"):
`ServeEngine.update_graph(delta)` / `DistServeEngine.update_graph(delta)`
commit edge arrivals behind the `update_params` fence — in-place pad-lane
tile writes + batched device tile swaps over a bound
`quiver_tpu.stream.StreamingTiledGraph` (gather-only sampling untouched,
sealed AOT executables rebind arguments, never recompile), with the three
consumers the round-10 fence never had: closure-touched cache
invalidation at every grain, stale hot-set replicas dropped + rebuilt,
and an immediate tier re-placement pass for delta-hot subgraphs. Owner
shards extend their halo closures INCREMENTALLY (union-homomorphic BFS
from the arrivals only; rows entering a closure install into reserved
tile/feature capacity). Frozen-graph replay == delta-replay with an empty
delta, and an appended edge is visible to the next sample after the
commit returns. `trace_gen.delta_interleaved_trace` drives churn
deterministically.

Round 16 makes the fleet ELASTIC (docs/api.md "Elastic fleet"):
`DistServeEngine.scale(hosts=H±k)` / `rebalance()` migrate seed
ownership one bounded contiguous range at a time — the range's
halo-closure shard + feature rows build outside any fence while the old
owner keeps serving, then a per-range fence flips routing, bumps the
ownership epoch, and invalidates exactly the migrated seeds' cached
state. `replay_fleet_oracle` understands ownership epochs (retired
engines vouch for the rows they served), telemetry drives the triggers
(`maybe_rebalance` off `OwnerLoadStats` imbalance, the drift-gated
background replica refresh, `scaling.fleet_table` pricing
add-a-host vs replicate-the-head), owner engines apply tenant quotas
end-to-end, and `FaultSpec(at="migration")` proves mid-migration kills
roll the in-flight range back or forward deterministically.
"""

from .cache import EmbeddingCache
from .dist import (
    ClosureFeature,
    DistServeConfig,
    DistServeEngine,
    DistServeStats,
    OwnerTimeout,
    REPLICA_HOST,
    closure_masks,
    contiguous_partition,
    plan_migration_ranges,
    replay_fleet_oracle,
    replay_shard_oracle,
    resolve_exchange_mode,
    shard_from_mask,
    shard_topology_by_owner,
    shard_topology_for_seeds,
)
from .engine import (
    DEFAULT_TENANT,
    DrainTimeout,
    ServeConfig,
    ServeEngine,
    ServeResult,
    ServeStats,
    ShedError,
    default_buckets,
)
from .faults import FaultInjector, FaultSpec, OwnerFault, OwnerKilled
from .trace_gen import (
    DeltaTrace,
    LPTrace,
    TemporalTrace,
    delta_interleaved_trace,
    lp_trace,
    poisson_arrivals,
    temporal_trace,
    trace_skew_stats,
    zipfian_trace,
)

__all__ = [
    "ClosureFeature",
    "DEFAULT_TENANT",
    "DeltaTrace",
    "LPTrace",
    "TemporalTrace",
    "delta_interleaved_trace",
    "lp_trace",
    "temporal_trace",
    "DistServeConfig",
    "DistServeEngine",
    "DistServeStats",
    "DrainTimeout",
    "EmbeddingCache",
    "FaultInjector",
    "FaultSpec",
    "OwnerFault",
    "OwnerKilled",
    "OwnerTimeout",
    "REPLICA_HOST",
    "ServeConfig",
    "ServeEngine",
    "ServeResult",
    "ServeStats",
    "ShedError",
    "closure_masks",
    "contiguous_partition",
    "default_buckets",
    "plan_migration_ranges",
    "poisson_arrivals",
    "replay_fleet_oracle",
    "replay_shard_oracle",
    "resolve_exchange_mode",
    "shard_from_mask",
    "shard_topology_by_owner",
    "shard_topology_for_seeds",
    "trace_skew_stats",
    "zipfian_trace",
]
