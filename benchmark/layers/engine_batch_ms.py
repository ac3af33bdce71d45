"""Median length, in ms on the profiler's clock, of the engine's
``serve/batch`` spans wholly inside the traced slice: ``_batch_arrays`` and
the four uploads of the step's inputs."""
from benchmark import spans


def read(run):
    return spans.span_median_ms(run, "serve/batch")
