"""Median length, in ms on the profiler's clock, of the engine's
``serve/schedule`` spans wholly inside the traced slice: deadlines,
``scheduler.schedule()``, admission stamps and COW page copies."""
from benchmark import spans


def read(run):
    return spans.span_median_ms(run, "serve/schedule")
