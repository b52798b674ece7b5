"""What a per-layer metric's reader is handed, and shared arithmetic."""

from __future__ import annotations

import dataclasses

from bench import trace as trace_mod


def percentile(values, q: float):
    """Nearest-rank percentile: the smallest value with at least ``q``%
    of the samples at or below it; ``None`` for no samples."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, -(-int(q * len(s)) // 100))
    return s[min(rank, len(s)) - 1]


@dataclasses.dataclass
class Run:
    """One traced run: the configuration and mix, the chip's peaks, what
    the drive loop recorded (``got``), and the trace with its summary."""

    config: dict
    traffic: dict
    peaks: dict | None
    got: dict
    trace: trace_mod.Trace | None
    summary: dict | None

    @property
    def steps_traced(self) -> int:
        return self.got["steps_traced"]

    def kernel_s(self, kernel: str) -> float:
        """Summed device seconds of ``kernel``'s operations in the window."""
        if self.trace is None:
            return 0.0
        return trace_mod.kernel_ns(self.trace, kernel)[0] * 1e-9

    def step_shape(self) -> tuple[int, int, int]:
        """``(images, k, g)`` of one sampler step of this traffic: a
        closed mix's batch, or the rolling batch's capacity (every row is
        computed, occupied or not)."""
        images = self.traffic.get("batch") or self.traffic["max_resident"]
        return images, self.config["sampler"]["top_k"], 2


def roofline(run: Run, kernel: str, flops: float, nbytes: float):
    """Percent of the roofline: least time of ``flops`` and ``nbytes`` per
    step over the steps traced, at the chip's bf16 peak and HBM bandwidth,
    over the kernel's summed device time.  ``None`` when the trace holds
    no such kernel."""
    t = run.kernel_s(kernel)
    if t <= 0.0 or not run.steps_traced or run.peaks is None:
        return None
    least = max(flops / run.peaks["bf16_flops"],
                nbytes / run.peaks["hbm_bytes_s"]) * run.steps_traced
    return 100.0 * least / t
