"""Self time of the device operations whose scope path passes through
``rematted_computation`` in the traced slice: forward work that the
backward pass runs again (``jax.checkpoint``), which ``train_mfu_pct`` does
not count. In ms per train step in the slice."""
from benchmark import spans


def read(run):
    return spans.self_ms_per_step(
        run, lambda e: spans.REMAT in spans.scope_of(e), "trace_steps")
