"""Unit + property tests for SE(2) group operations, plus the end-to-end
model property the group structure buys: globally re-posing a scene leaves
SE(2)-relative rollout action distributions unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")   # optional dev dep; see requirements-dev.txt
from hypothesis import assume, given, settings, strategies as st

from repro.core import se2

jax.config.update("jax_enable_x64", False)


def rand_pose(rng, shape=()):
    xy = rng.uniform(-5, 5, size=shape + (2,))
    th = rng.uniform(-np.pi, np.pi, size=shape + (1,))
    return jnp.asarray(np.concatenate([xy, th], axis=-1), dtype=jnp.float32)


def test_identity_compose():
    rng = np.random.default_rng(0)
    p = rand_pose(rng, (7,))
    e = se2.identity((7,))
    np.testing.assert_allclose(se2.compose(e, p), p, atol=1e-6)
    np.testing.assert_allclose(se2.compose(p, e), p, atol=1e-6)


def test_inverse():
    rng = np.random.default_rng(1)
    p = rand_pose(rng, (7,))
    e = se2.compose(se2.inverse(p), p)
    np.testing.assert_allclose(np.asarray(e), 0.0, atol=1e-5)
    e2 = se2.compose(p, se2.inverse(p))
    np.testing.assert_allclose(np.asarray(e2), 0.0, atol=1e-5)


def test_matrix_homomorphism():
    rng = np.random.default_rng(2)
    p1, p2 = rand_pose(rng, (5,)), rand_pose(rng, (5,))
    m12 = se2.matrix(se2.compose(p1, p2))
    np.testing.assert_allclose(
        np.asarray(m12), np.asarray(se2.matrix(p1) @ se2.matrix(p2)), atol=1e-5)


def test_from_matrix_roundtrip():
    rng = np.random.default_rng(3)
    p = rand_pose(rng, (9,))
    np.testing.assert_allclose(
        np.asarray(se2.from_matrix(se2.matrix(p))), np.asarray(p), atol=1e-5)


def test_relative_matches_matrix():
    rng = np.random.default_rng(4)
    pn, pm = rand_pose(rng, (4,)), rand_pose(rng, (4,))
    rel = se2.relative(pn, pm)
    expect = se2.from_matrix(
        jnp.linalg.inv(se2.matrix(pn)) @ se2.matrix(pm))
    np.testing.assert_allclose(np.asarray(rel), np.asarray(expect), atol=1e-4)


def test_relative_left_invariance():
    rng = np.random.default_rng(5)
    pn, pm, z = rand_pose(rng, (6,)), rand_pose(rng, (6,)), rand_pose(rng)
    rel = se2.relative(pn, pm)
    rel_z = se2.relative(se2.compose(z, pn), se2.compose(z, pm))
    np.testing.assert_allclose(np.asarray(rel), np.asarray(rel_z), atol=1e-4)


def test_transform_points():
    p = jnp.asarray([1.0, 2.0, np.pi / 2], dtype=jnp.float32)
    pts = jnp.asarray([[1.0, 0.0]], dtype=jnp.float32)
    out = se2.transform_points(p, pts)
    np.testing.assert_allclose(np.asarray(out), [[1.0, 3.0]], atol=1e-5)


finite_floats = st.floats(min_value=-4.0, max_value=4.0, allow_nan=False,
                          width=32)


@settings(max_examples=50, deadline=None)
@given(x1=finite_floats, y1=finite_floats, t1=finite_floats,
       x2=finite_floats, y2=finite_floats, t2=finite_floats,
       x3=finite_floats, y3=finite_floats, t3=finite_floats)
def test_associativity(x1, y1, t1, x2, y2, t2, x3, y3, t3):
    a = jnp.asarray([x1, y1, t1], dtype=jnp.float32)
    b = jnp.asarray([x2, y2, t2], dtype=jnp.float32)
    c = jnp.asarray([x3, y3, t3], dtype=jnp.float32)
    lhs = se2.compose(se2.compose(a, b), c)
    rhs = se2.compose(a, se2.compose(b, c))
    # angles compare on the circle
    np.testing.assert_allclose(np.asarray(lhs[:2]), np.asarray(rhs[:2]),
                               atol=1e-4)
    dth = float(se2.wrap_angle(lhs[2] - rhs[2]))
    assert abs(dth) < 1e-4


# ---------------------------------------------------------------------------
# Global SE(2) invariance of rollout action distributions.
#
# Applying one rigid transform z to EVERY pose in a scene leaves all
# relative poses p_n^{-1} p_m unchanged, so an SE(2)-relative model's
# action logits — and hence what a closed-loop rollout samples — must not
# move (up to the Fourier truncation / f32 error). The "absolute" baseline
# reads raw poses through a learned embedding and must move measurably.
# ---------------------------------------------------------------------------

def _sim_setup(encoding):
    from repro.data import scenarios
    from repro.nn import module as nnm
    from repro.nn.agent_sim import AgentSimConfig, AgentSimModel

    scen = scenarios.ScenarioConfig(num_map=4, num_agents=2, num_steps=3)
    cfg = AgentSimConfig(d_model=32, num_layers=2, num_heads=2, head_dim=12,
                         d_ff=64, num_actions=scen.num_actions,
                         encoding=encoding, fourier_terms=18,
                         attn_impl="ref")
    model = AgentSimModel(cfg)
    params = nnm.init_params(model.specs(), jax.random.key(1))
    batch = {k: jnp.asarray(v)
             for k, v in scenarios.generate_batch(3, 0, 1, scen).items()}
    return model, params, batch


_SIM_CACHE = {}


def _action_dists(encoding, z):
    """Softmax action distributions of the last sim step — what a rollout
    samples from — after re-posing the whole scene by z."""
    if encoding not in _SIM_CACHE:
        _SIM_CACHE[encoding] = _sim_setup(encoding)
    model, params, batch = _SIM_CACHE[encoding]
    b = dict(batch)
    b["map_pose"] = se2.compose(z, batch["map_pose"])
    b["agent_pose"] = se2.compose(z, batch["agent_pose"])
    logits, _ = model(params, b)
    return np.asarray(jax.nn.softmax(logits[:, -1].astype(jnp.float32), -1))


transl = st.floats(min_value=-4.0, max_value=4.0, allow_nan=False, width=32)
angle = st.floats(min_value=-float(np.float32(np.pi)),
                  max_value=float(np.float32(np.pi)), allow_nan=False,
                  width=32)


@settings(max_examples=8, deadline=None)
@given(zx=transl, zy=transl, zth=angle)
def test_rollout_action_dists_invariant_relative_encodings(zx, zy, zth):
    z = jnp.asarray([zx, zy, zth], jnp.float32)
    e = jnp.zeros(3, jnp.float32)
    # se2_repr is exact (f32 roundoff only); se2_fourier carries the
    # truncation error of the F=18 basis on top.
    for encoding, tol in (("se2_repr", 5e-4), ("se2_fourier", 5e-3)):
        base = _action_dists(encoding, e)
        moved = _action_dists(encoding, z)
        np.testing.assert_allclose(moved, base, atol=tol,
                                   err_msg=encoding)


@settings(max_examples=8, deadline=None)
@given(zx=transl, zy=transl, zth=angle)
def test_rollout_action_dists_absolute_not_invariant(zx, zy, zth):
    assume(abs(zx) + abs(zy) > 1.0 or abs(zth) > 0.5)
    z = jnp.asarray([zx, zy, zth], jnp.float32)
    base = _action_dists("absolute", jnp.zeros(3, jnp.float32))
    moved = _action_dists("absolute", z)
    assert np.max(np.abs(moved - base)) > 1e-4
