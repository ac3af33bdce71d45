"""Median length, in ms on the profiler's clock, of the ``serve/fetch`` spans
wholly inside the traced slice: how long the host, its own work for the next
step done, waited for the device to finish the step in flight. That is the
room the host has left before it shows in a step again (``idle_ms_per_step.*``
can only say that it is hidden); an engine that fetches before it dispatches
reads the device's whole step here and ``pipelined_step_pct`` says why."""
from benchmark import spans


def read(run):
    return spans.span_median_ms(run, "serve/fetch")
