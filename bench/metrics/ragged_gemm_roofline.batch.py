"""Least time of the window's token-row GEMM work (bench/work.py
``ragged_gemm``: weights read once per touched expert per step, in the
store's type as the configuration's ``param_dtype`` states it) over the
summed device time of the ``ragged_gemm`` kernel, in percent."""

from bench import work
from bench.metrics_util import roofline


def read(run):
    images, k, g = run.step_shape()
    flops, nbytes = work.ragged_gemm(
        run.config, images * k, g, len(run.config["experts"]),
        work.weight_bytes(run.config["sampler"]["param_dtype"]))
    return roofline(run, "ragged_gemm", flops, nbytes)
