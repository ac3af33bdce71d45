"""Device idle time in the traced slice while a ``serve/step`` span (the
guarded forward) was open on the host: the dispatch before a step's first
operation, the fetch after its last. In ms per engine step in the slice;
the three ``idle_ms_per_step.*`` add up to ``host_gap_ms_per_step``."""
from benchmark import spans


def read(run):
    return spans.idle_ms_per_step(run, "forward")
