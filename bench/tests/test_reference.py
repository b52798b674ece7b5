"""The plain reference against the served program at a tiny size, and
the control: the reference one precision step lower must read a gap the
served program does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference, system, weights
from bench.tests.tiny import tiny

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def served():
    cfg = tiny()
    engine = system.build_engine(cfg, SEED)
    key = np.array([3, 5], np.uint32)
    text = np.random.default_rng(1).standard_normal((4, 8, 16),
                                                    dtype=np.float32)
    out = np.asarray(engine.generate(key, text, 4))
    return cfg, key, text, out


def _ref(cfg, key, text, precision):
    return check.reference_latents(cfg, SEED, [key], [text], precision)


def test_weights_list_and_stack_agree():
    cfg = tiny()
    m = system.model_sizes(cfg)
    lst = weights.expert_list(SEED, m, 8)
    (stack,) = weights.expert_blocks(SEED, m, 8)
    for e in (0, 5):
        for a, b in zip(jax.tree.leaves(lst[e]), jax.tree.leaves(stack)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[e])


def test_one_block_is_the_unblocked_reference(served):
    """On one chip the check hands the reference one block, the whole
    stack: the latents are those of ``reference.sample`` over the stack
    of the engine's own draws, bit for bit."""
    cfg, key, text, _ = served
    m = system.model_sizes(cfg)
    stack = jax.tree.map(lambda *xs: np.stack(xs),
                         *weights.expert_list(SEED, m, 8))
    shape = (4, cfg["latent_size"], cfg["latent_size"],
             cfg["latent_channels"])
    noise = jax.random.normal(jnp.asarray(key), shape, jnp.float32)
    plain = reference.sample(
        noise, jnp.asarray(text), stack, weights.router(SEED, cfg["router"]),
        reference.time_grid(cfg["sampler"]["num_steps"]),
        spec=reference.freeze(cfg), precision="highest")
    np.testing.assert_array_equal(_ref(cfg, key, text, "highest"),
                                  np.asarray(plain))


def test_weights_match_the_program_layout():
    from repro.models import dit as D

    ecfg, rcfg = system.dit_configs(tiny())
    for cfgd, router in ((ecfg, False), (rcfg, True)):
        want = jax.eval_shape(lambda: D.init(cfgd, jax.random.PRNGKey(0)))
        got = jax.eval_shape(
            lambda: weights._draw(
                weights.layout(
                    {**system.model_sizes(tiny()), **tiny()["router"]}
                    if router else system.model_sizes(tiny()),
                    router=router),
                jax.random.PRNGKey(0)))
        assert jax.tree.structure(want) == jax.tree.structure(got)
        assert [x.shape for x in jax.tree.leaves(want)] == \
            [x.shape for x in jax.tree.leaves(got)]


def test_reference_matches_the_served_program(served):
    cfg, key, text, out = served
    ref = _ref(cfg, key, text, "highest")
    assert check.gap(out, ref) < cfg["check"]["latent_gap_limit"] / 4


def test_control_one_precision_lower_fails_the_limit(served):
    cfg, key, text, _ = served
    ref = _ref(cfg, key, text, "highest")
    control = _ref(cfg, key, text, "high")
    assert check.gap(control, ref) > cfg["check"]["latent_gap_limit"]


def test_precisions_are_ordered():
    a = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                    jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(a, np.float64)
    err = {p: float(np.max(np.abs(np.asarray(reference._mm(a, a, p))
                                  - exact)))
           for p in reference.PRECISIONS}
    assert err["highest"] < err["high"] < err["default"]
