"""Process-wide serving stats — deliberately stdlib-only.

One mutable dict, updated by every engine and router in the process,
backing the Profiler "Serving" section.  It lives apart from
``engine.py`` so the router (and the jax-free tools built on top of
it, ``tools/fleet_sim.py`` in particular) can bump the shared
counters without importing the engine's jax stack.  ``engine.py``
re-exports ``serving_stats``/``reset_stats`` unchanged, so callers of
``paddle_tpu.serving.serving_stats()`` see no difference.
"""
from __future__ import annotations

from typing import Dict

__all__ = ["STATS", "stats_zero", "serving_stats", "reset_stats"]


def stats_zero() -> Dict[str, float]:
    return {
        "engines": 0, "requests_added": 0, "requests_finished": 0,
        "requests_preempted": 0, "steps": 0, "prefill_tokens": 0,
        "decode_tokens": 0, "peak_running": 0, "pool_bytes": 0,
        "compiled_buckets": 0,
        # block-table entries the attention kernel walked (the live pages of
        # rows with tokens to feed) of those the steps' tables held
        "kv_pages": 0, "table_pages": 0,
        # positions the steps' programs computed (fed tokens over them is the
        # steps' fill), and prefill rows the mixed step's token budget left
        # out of a step
        "slot_tokens": 0, "deferred_rows": 0,
        # recurrent state beside the pages: bytes held by live engines, and
        # rows whose state a step zeroed (a request's first chunk in a slot)
        "state_bytes": 0, "state_resets": 0,
        # one step in flight: steps dispatched while another was on the
        # device (of ``steps``), steps fetched before the next dispatch
        # (in all and by reason), and sampled tokens of rows fed past their
        # request's end
        "pipelined_steps": 0, "pipeline_drains": 0,
        "pipeline_drains.spec": 0, "pipeline_drains.prefix_cache": 0,
        "pipeline_drains.page_pressure": 0, "discarded_tokens": 0,
        # work reuse (prefix cache + speculative decoding)
        "prefix_hit_tokens": 0, "prefix_evicted_pages": 0,
        "spec_proposed": 0, "spec_accepted": 0,
        # resilience counters (engine.py + router.py)
        "shed": 0, "admission_waits": 0, "callback_errors": 0,
        "recoveries": 0, "quarantined": 0, "deadline_expired": 0,
        "cancelled": 0, "failovers": 0, "replicas_dead": 0, "drains": 0,
    }


STATS: Dict[str, float] = stats_zero()


def serving_stats() -> Dict[str, float]:
    return dict(STATS)


def reset_stats() -> None:
    STATS.clear()
    STATS.update(stats_zero())
