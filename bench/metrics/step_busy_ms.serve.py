"""Device busy time in the traced window over the rolling steps executed
in it (whole ticks are traced)."""


def read(run):
    if run.summary is None or not run.steps_traced:
        return None
    return 1e3 * run.summary["busy_s"] / run.steps_traced
