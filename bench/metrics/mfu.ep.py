"""Model FLOPs of the sampler steps in the traced window (router, routed
experts on both guidance branches, attention products; bench/work.py)
over the window's length times the bf16 peak of all the chips the
configuration's experts are placed on (``expert_shards``), in percent."""

from bench import work


def read(run):
    if run.summary is None or not run.steps_traced or run.peaks is None:
        return None
    images, k, g = run.step_shape()
    chips = run.config.get("expert_shards", 1)
    flops = work.model_flops(run.config, images, k, g) * run.steps_traced
    return 100.0 * flops / (run.summary["window_s"]
                            * run.peaks["bf16_flops"] * chips)
