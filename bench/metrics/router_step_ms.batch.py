"""Own device time of the operations under the program's ``router``
scope, over the sampler steps traced: the router forward (its attention
included), top-k and the dispatch plan; nested operations counted
once."""

from bench import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, __file__, "router")
