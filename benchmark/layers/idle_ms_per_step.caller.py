"""Device idle time in the traced slice while no program span was open on
the host: the caller's loop between two engine steps. In ms per engine
step in the slice."""
from benchmark import spans


def read(run):
    return spans.idle_ms_per_step(run, "caller")
