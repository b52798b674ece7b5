"""Own device time of the operations under the program's
``expert_exchange`` scope (the collective that joins the chips' expert
predictions), averaged over chips, per sampler step traced; it includes
a chip's wait for the slowest one.  Read from each operation's name
stack in the trace (``bench/program_trace.name_stacks``); nothing where
no operation carries the scope."""

import glob
import os

from bench import program_trace
from bench import trace as trace_mod

SCOPE = "expert_exchange"


def read(run):
    if run.trace is None or not run.steps_traced:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    paths = glob.glob(os.path.join(root, program_trace.TRACE_DIR, "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        return None
    with open(paths[0], "rb") as f:
        stacks = program_trace.name_stacks(f.read())
    lo, hi = trace_mod.window(run.trace)
    total, seen = 0.0, False
    for ops in run.trace.devices:
        for (name, s, e), own in zip(ops, trace_mod.self_ns(ops)):
            if s >= lo and e <= hi \
                    and SCOPE in stacks.get(name, "").split("/"):
                total += own
                seen = True
    if not seen:
        return None
    return 1e-6 * total / len(run.trace.devices) / run.steps_traced
