"""HLO-level analysis: collective bytes + three-term roofline.

cost_analysis() gives FLOPs/bytes of the (per-device, SPMD-partitioned)
module but NOT collective traffic; that is recovered by parsing the
optimized HLO text and summing the result-shape bytes of every collective
op, weighted by its wire cost:

    all-reduce          2·(n−1)/n ≈ 2   (ring: reduce-scatter + all-gather)
    all-gather          (n−1)/n   ≈ 1
    reduce-scatter      (n−1)/n   ≈ 1
    all-to-all          (n−1)/n   ≈ 1
    collective-permute  1

Replica-group sizes are parsed when present; the asymptotic factor is used
otherwise.  This is the §Roofline 'collective_bytes' source.
"""

from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_GROUP_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * nb


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_type: dict
    count_by_type: dict

    @property
    def total_wire_bytes(self) -> float:
        return float(sum(self.bytes_by_type.values()))


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum wire bytes of every collective in optimized HLO text."""
    bytes_by: dict[str, float] = {}
    count_by: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        tuple_shapes, dtype, dims, op = m.groups()
        if tuple_shapes is not None:
            size = sum(
                _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(tuple_shapes)
            )
        else:
            size = _shape_bytes(dtype, dims)
        gm = _GROUP_RE.search(line)
        if gm:
            n = len([x for x in gm.group(1).split(",") if x.strip()])
        else:
            n = 0
        if op == "all-reduce":
            factor = 2.0 * (n - 1) / n if n > 1 else 2.0
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            factor = (n - 1) / n if n > 1 else 1.0
        else:  # collective-permute
            factor = 1.0
        bytes_by[op] = bytes_by.get(op, 0.0) + size * factor
        count_by[op] = count_by.get(op, 0) + 1
    return CollectiveStats(bytes_by, count_by)


def compiled_bytes_accessed(compiled) -> float:
    """Total HBM traffic (bytes accessed) of a compiled XLA executable.

    ``compiled`` is the result of ``jax.jit(fn).lower(*args).compile()``.
    XLA's ``cost_analysis`` reports the memory-traffic estimate the
    compiler itself used ("bytes accessed"); returns 0.0 when the backend
    provides no estimate.  Divide by the step count for a per-step
    HBM-bytes figure — the metric the step-fused sampler section of
    ``BENCH_sampler.json`` tracks.
    """
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0
    if not isinstance(ca, dict):
        return 0.0
    return float(ca.get("bytes accessed", 0.0))


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    """Three roofline terms, seconds per step per chip (§Roofline)."""

    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    peak_flops: float
    hbm_bw: float
    ici_bw: float

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
        }


def model_flops(cfg, shape, params_total: int, active_params: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D for a train step;
    2·N·D_tokens for inference (forward only)."""
    n = active_params
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
