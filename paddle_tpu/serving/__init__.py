"""TPU serving engine: continuous batching over a paged KV cache.

The three layers, bottom-up:

  * ``kv_cache``  — page pools, block tables, the HBM capacity plan
                    (``plan_capacity``: pages-per-chip before a chip
                    is touched);
  * ``scheduler`` — continuous (in-flight) batching: chunked prefill,
                    per-step admission, completion/eviction and
                    preemption at step boundaries, fixed compiled
                    shapes;
  * ``engine``    — ``LLMEngine``: ``add_request()`` / ``step()`` /
                    streaming ``on_token`` callbacks, one jitted
                    step of the model the configuration names
                    (``cfg.serving``: ``models.llama``, ``models.jamba``,
                    ``models.phi4flash``)
                    on one cache pytree, plus
                    the resilience layer: bounded admission with typed
                    retriable shedding, per-request deadlines/SLOs,
                    cooperative cancellation, and step-failure
                    recovery (pool rebuild + replay, poison-request
                    bisection quarantine);
  * ``router``    — ``Router``: the multi-replica front door — load-
                    and cache-locality-aware placement, heartbeat
                    liveness, drain-on-SIGTERM, and dead-replica
                    failover with idempotent bit-identical replay;
  * ``errors``    — the typed failure taxonomy callers branch on
                    (``retriable`` or terminal).

Fleet planning rides on top: ``workloads`` (seeded synthetic arrival
processes shared by pod_report and tools/fleet_sim.py)
and ``autoscale`` (the per-replica ServiceModel, multi-window SLO
burn-rate gauges, and the recommend-only AutoscalePolicy the Router
surfaces).  Both are stdlib-only, like ``stats`` — the jax-free slice
the discrete-event fleet simulator loads standalone.

The attention primitive underneath is
``ops.pallas_ops.ragged_paged_attention`` — one Pallas kernel for the
whole mixed prefill+decode batch, jnp reference off-TPU.  See
docs/serving.md and docs/robustness.md ("Serving resilience").
"""
from . import autoscale, workloads  # noqa: F401
from .autoscale import (AutoscalePolicy, Recommendation,  # noqa: F401
                        ServiceModel, fleet_stats, recommend_fleet,
                        replicas_for, reset_fleet_stats)
from .engine import (LLMEngine, SLOConfig, reset_stats,  # noqa: F401
                     serving_stats, summary_lines)
from .errors import (AdmissionRejected, DeadlineExceeded,  # noqa: F401
                     ReplicaUnavailable, RequestQuarantined,
                     RetriableError, ServingError)
from .kv_cache import (KV_DTYPE_BYTES, BlockAllocator,  # noqa: F401
                       PagedKVCache, kv_bytes_per_token, plan_capacity)
from .prefix_cache import PrefixCache, PrefixStats  # noqa: F401
from .router import (EngineReplica, ReplicaState, Router,  # noqa: F401
                     RouterRequest)
from .scheduler import (AdmissionGate, Request,  # noqa: F401
                        RequestState, ScheduledSeq, Scheduler,
                        StepPlan)
from .spec_decode import (DraftModel, SpecDecodeConfig,  # noqa: F401
                          greedy_accept)

__all__ = ["LLMEngine", "SLOConfig", "serving_stats", "reset_stats",
           "summary_lines",
           "BlockAllocator", "PagedKVCache", "kv_bytes_per_token",
           "plan_capacity", "KV_DTYPE_BYTES",
           "AdmissionGate", "Request", "RequestState", "Scheduler",
           "StepPlan", "ScheduledSeq",
           "workloads", "autoscale", "AutoscalePolicy",
           "Recommendation", "ServiceModel", "fleet_stats",
           "reset_fleet_stats", "recommend_fleet", "replicas_for",
           "PrefixCache", "PrefixStats",
           "SpecDecodeConfig", "DraftModel", "greedy_accept",
           "Router", "RouterRequest", "ReplicaState", "EngineReplica",
           "ServingError", "RetriableError", "AdmissionRejected",
           "DeadlineExceeded", "RequestQuarantined",
           "ReplicaUnavailable"]
