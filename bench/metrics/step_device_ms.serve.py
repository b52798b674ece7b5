"""Device busy time in the traced window over the Euler steps the device
itself ran there: launches of the fused-step kernel under the program's
``fused_step`` scope, one per step."""

from bench import program_trace


def read(run):
    got = program_trace.of_run(run, __file__)
    if got is None or run.summary is None:
        return None
    pt, (lo, hi) = got
    steps = program_trace.device_steps(pt, lo, hi)
    if not steps:
        return None
    return 1e3 * run.summary["busy_s"] / steps
