"""Median fenced step time over the window (host clock), in ms."""
import statistics


def read(run):
    steps = run["samples"].get("step_s")
    return statistics.median(steps) * 1e3 if steps else None
