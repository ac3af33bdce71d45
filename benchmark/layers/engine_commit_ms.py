"""Median length, in ms on the profiler's clock, of the engine's
``serve/commit`` spans wholly inside the traced slice: acceptance,
``scheduler.apply``, the ``on_token`` callbacks and the step's stats."""
from benchmark import spans


def read(run):
    return spans.span_median_ms(run, "serve/commit")
