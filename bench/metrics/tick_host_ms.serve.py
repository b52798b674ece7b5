"""Mean host time of one scheduler tick (``step()``) in the window,
before the profiler starts (benchmark's own span, host clock)."""


def read(run):
    ticks = run.got.get("ticks")
    if not ticks:
        return None
    return 1e3 * sum(ticks) / len(ticks)
