"""Own device time of the operations under the program's
``layer_weights`` scope, over the sampler steps traced: each layer's
weights sliced out of the ``(K, L, ...)`` store, and padded to the
kernel's lanes where the width needs it; nested operations counted
once."""

from bench import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, __file__, "layer_weights")
