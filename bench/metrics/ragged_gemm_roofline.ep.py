"""One chip's share of the window's routed token-row GEMM work
(bench/work.py ``ragged_gemm`` over the routed pairs, divided by the
``expert_shards`` chips that hold the experts) at its least time, over
the ``ragged_gemm`` kernel's device time averaged over chips, in
percent.  Only the useful work counts: rows a chip computes for pairs
that another chip's experts own show as lost roofline."""

from bench import work
from bench.metrics_util import roofline


def read(run):
    images, k, g = run.step_shape()
    chips = run.config.get("expert_shards", 1)
    flops, nbytes = work.ragged_gemm(
        run.config, images * k, g, len(run.config["experts"]),
        work.weight_bytes(run.config["sampler"]["param_dtype"]))
    return roofline(run, "ragged_gemm", flops / chips, nbytes / chips)
