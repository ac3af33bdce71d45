"""95th percentile of arrival-to-admission waits (``LLMEngine._queue_s``)
of the requests that got their first token inside the window, in ms."""
from benchmark import stats


def read(run):
    waits = run["samples"].get("queue_s")
    return stats.percentile(waits, 95) * 1e3 if waits else None
