"""Own device time of the operations under the program's ``attention``
scope, over the sampler steps traced: the experts' attention products,
without the projections around them; nested operations counted once."""

from bench import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, __file__, "attention")
