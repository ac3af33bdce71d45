"""Device idle time in the traced slice while any ``serve/*`` span other
than ``serve/step`` was open on the host: the rest of ``LLMEngine.step``
(schedule, batch, commit). In ms per engine step in the slice."""
from benchmark import spans


def read(run):
    return spans.idle_ms_per_step(run, "engine_host")
