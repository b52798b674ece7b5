"""Static HLO cost model: trip counts, dot flops, collective parsing."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_cost import HloCostModel, parse_hlo
from repro.launch.hlo_analysis import (collective_bytes,
                                       compiled_bytes_accessed)


def test_scan_trip_count_multiplies_flops():
    """A scanned matmul must count L× the body flops (cost_analysis
    famously counts it once — the whole reason this model exists)."""
    d, L = 64, 7

    def f(ws, x):
        def body(x, w):
            return x @ w, None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    ws = jnp.zeros((L, d, d))
    x = jnp.zeros((8, d))
    compiled = jax.jit(f).lower(ws, x).compile()
    totals = HloCostModel(compiled.as_text()).totals()
    expected = 2 * 8 * d * d * L
    assert abs(totals.flops - expected) / expected < 0.05, (
        totals.flops, expected
    )


def test_single_dot_flops():
    a = jnp.zeros((32, 64))
    b = jnp.zeros((64, 16))
    compiled = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    totals = HloCostModel(compiled.as_text()).totals()
    assert totals.flops == 2 * 32 * 64 * 16


def test_bytes_reasonable_for_elementwise():
    x = jnp.zeros((1024, 1024))
    compiled = jax.jit(lambda x: jnp.tanh(x) + 1.0).lower(x).compile()
    totals = HloCostModel(compiled.as_text()).totals()
    nbytes = 1024 * 1024 * 4
    # read + write, allow fusion-accounting slack
    assert nbytes <= totals.hbm_bytes <= 6 * nbytes


def test_collective_regex_on_synthetic_hlo():
    hlo = """
HloModule m

ENTRY %main (p: f32[16,128]) -> f32[16,128] {
  %p = f32[16,128]{1,0} parameter(0)
  %ag = f32[256,128]{1,0} all-gather(%p), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
  %ar = f32[256,128]{1,0} all-reduce(%ag), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, to_apply=%add
  ROOT %out = f32[16,128]{1,0} slice(%ar), slice={[0:16], [0:128]}
}
"""
    stats = collective_bytes(hlo)
    ag = 256 * 128 * 4 * (15 / 16)
    ar = 256 * 128 * 4 * 2 * (15 / 16)
    np.testing.assert_allclose(stats.bytes_by_type["all-gather"], ag)
    np.testing.assert_allclose(stats.bytes_by_type["all-reduce"], ar)
    assert stats.count_by_type == {"all-gather": 1, "all-reduce": 1}


def test_parse_hlo_computations():
    x = jnp.zeros((4, 4))
    compiled = jax.jit(lambda x: x @ x).lower(x).compile()
    comps = parse_hlo(compiled.as_text())
    assert comps, "no computations parsed"
    assert any("main" in n for n in comps)


# --- compiled_bytes_accessed degradation (interpret-mode/CPU backends) -------


class _FakeCompiled:
    """Stand-in for a jax compiled executable with a fixed cost_analysis."""

    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_bytes_accessed_real_compiled_is_nonnegative_float():
    x = jnp.zeros((8, 8))
    compiled = jax.jit(lambda x: x @ x + 1.0).lower(x).compile()
    out = compiled_bytes_accessed(compiled)
    assert isinstance(out, float) and out >= 0.0


def test_bytes_accessed_raising_backend_degrades_to_zero():
    """Backends without a cost model raise from cost_analysis()."""
    fake = _FakeCompiled(NotImplementedError("no cost model on this backend"))
    assert compiled_bytes_accessed(fake) == 0.0


def test_bytes_accessed_empty_cost_analysis_list():
    """A payload that is not a properties dict reports nothing."""
    assert compiled_bytes_accessed(_FakeCompiled([])) == 0.0


def test_bytes_accessed_missing_key_degrades_to_zero():
    """CPU/interpret builds report flops but no 'bytes accessed' key."""
    assert compiled_bytes_accessed(_FakeCompiled({"flops": 123.0})) == 0.0
    assert compiled_bytes_accessed(_FakeCompiled([{"flops": 1.0}])) == 0.0


def test_bytes_accessed_non_dict_payload_degrades_to_zero():
    assert compiled_bytes_accessed(_FakeCompiled("bogus")) == 0.0
    assert compiled_bytes_accessed(_FakeCompiled(None)) == 0.0


def test_bytes_accessed_reads_key_old_and_new_shapes():
    """The installed jax returns a dict; the list-of-dicts shape of
    older releases is no longer read."""
    assert compiled_bytes_accessed(
        _FakeCompiled({"bytes accessed": 42.0})) == 42.0
    assert compiled_bytes_accessed(
        _FakeCompiled([{"bytes accessed": 7.0}])) == 0.0
