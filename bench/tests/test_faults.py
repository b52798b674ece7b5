"""A run drives each cell's timed path at a tiny size on the CPU (the
look for a chip skipped) and comes out correct; with the timed path
broken underneath, or the control in the served program's place, the
same run comes out not correct."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, run
from bench.tests.tiny import make_root, tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("faults"), BENCH)


@pytest.fixture(autouse=True)
def _restore_precision():
    was = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", was)


def _unchanged(monkeypatch):
    """The Euler step returns its state unchanged."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "fused_step",
                        lambda preds, x_t, *a, **k: x_t)


def _half_batch(monkeypatch):
    """Half of the work is left out of the step: the first half of every
    row's channels keeps its value, so each served request is hit, in
    whichever row the rolling batch placed it."""
    from repro.kernels import ops

    real = ops.fused_step

    def half(preds, x_t, *a, **k):
        out = real(preds, x_t, *a, **k)
        h = max(x_t.shape[-1] // 2, 1)
        return jnp.concatenate([x_t[..., :h], out[..., h:]], axis=-1)

    monkeypatch.setattr(ops, "fused_step", half)


def _altered(monkeypatch):
    """One served value is altered where the answer is produced."""
    from repro.launch.serve import ServingEngine
    from repro.serving.batch import RollingBatch

    gen, res = ServingEngine.generate, RollingBatch.resolve
    monkeypatch.setattr(ServingEngine, "generate",
                        lambda *a, **k: gen(*a, **k).at[0, 0, 0, 0].add(1.0))
    monkeypatch.setattr(RollingBatch, "resolve",
                        lambda *a, **k: res(*a, **k).at[0, 0, 0, 0].add(1.0))


def _wrong_rows(monkeypatch):
    """The scheduler hands a request the rows of the slot next to its own."""
    from repro.serving import batch

    def resolve(self, req):
        rows = self._rows_of[req.seq]
        idx = jnp.asarray([(r + 1) % self.capacity for r in rows], jnp.int32)
        out = batch._take_rows(self.x, idx)
        self.release(req, finished=True)
        return out

    monkeypatch.setattr(batch.RollingBatch, "resolve", resolve)


def _control(monkeypatch, cfg=None):
    """The control: the plain reference of ``cfg`` (default the tiny
    configuration) one precision step lower (``high``, for float32 at
    ``highest``) put in the served program's place, answering each call
    and each request from its own key and prompt."""
    from repro.launch.serve import ServingEngine
    from repro.serving.batch import RollingBatch

    cfg = cfg or tiny()
    res = RollingBatch.resolve

    def control(key, text):
        return jnp.asarray(check.reference_latents(
            cfg, SEED, [np.asarray(key)], [np.asarray(text)], "high"))

    def resolve(self, req):
        res(self, req)
        return control(req.key, req.text_emb)

    monkeypatch.setattr(ServingEngine, "generate",
                        lambda self, key, text, n: control(key, text))
    monkeypatch.setattr(RollingBatch, "resolve", resolve)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered, "wrong_rows": _wrong_rows,
          "control": _control}


def _run(root, workload):
    return run.run(root, workload, SEED, 0.5, False, require_chip=False,
                   cache=False)


@pytest.mark.parametrize("workload", ["tiny-closed", "tiny-open"])
def test_sound_run_is_correct(root, workload):
    res = _run(root, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


#: a closed mix has no scheduler rows to mix up
CASES = [(w, f) for w in ("tiny-closed", "tiny-open") for f in sorted(FAULTS)
         if not (w == "tiny-closed" and f == "wrong_rows")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_broken_timed_path_is_not_correct(root, workload, fault,
                                          monkeypatch):
    FAULTS[fault](monkeypatch)
    res = _run(root, workload)
    assert not res["correct"], res["checks"]
    if fault == "control":          # it fails on the gap, not elsewhere
        gap = res["checks"]["latent_gap"]
        assert gap["value"] > gap["limit"], res["checks"]
        assert res["checks"]["nonfinite"]["value"] == 0
