"""Device idle time per engine step in the traced slice: the slice's length
less the device's busy time, over the engine steps inside it, in ms."""


def read(run):
    trace, steps = run["trace"], run["counters"].get("trace_steps")
    if not trace or not steps:
        return None
    return (trace["window_s"] - trace["busy_s"]) / steps * 1e3
