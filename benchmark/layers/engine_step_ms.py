"""Median of the engine's own step wall times (``LLMEngine._step_wall_s``,
all buckets together) over the window, in ms."""
import statistics


def read(run):
    steps = [s for v in run["samples"].get("engine_step_s", {}).values()
             for s in v]
    return statistics.median(steps) * 1e3 if steps else None
