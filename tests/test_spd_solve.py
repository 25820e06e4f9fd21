"""ops/spd_solve: the batched Cholesky solve with the system on the lanes.

The kernel runs here in interpret mode; the batching rule is steered to
it by patching what it asks (the platform, the kernel's ``interpret``),
since on the CPU these tests run on it keeps XLA's Cholesky. The last
tests compile the kernel for a described TPU with the real Mosaic
compiler (nothing executes)."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops import spd_solve as mod
from photon_ml_tpu.ops.spd_solve import MAX_LANE_DIM, lane_solve, spd_solve

EPS = float(np.finfo(np.float32).eps)


@pytest.fixture
def on_lanes(monkeypatch):
    """The rule as on the TPU, the kernel interpreted."""
    monkeypatch.setattr(mod, "effective_platform", lambda: "tpu")
    monkeypatch.setattr(mod, "lane_solve", partial(lane_solve, interpret=True))


def _systems(rng, n, dim, cond_max=1e6, l2=1e-3):
    """``H = X'X + l2 I``, one a system, the condition numbers spread
    from 10 to ``cond_max`` over the batch; float32, and the condition
    numbers of what float32 holds."""
    q, _ = np.linalg.qr(rng.normal(size=(n, dim, dim)))
    top = np.geomspace(10.0, cond_max, n) if dim > 1 else np.ones(n)
    spectrum = l2 * np.geomspace(np.ones(n), top, dim, axis=1)
    X = np.sqrt(spectrum - l2)[:, :, None] * np.swapaxes(q, 1, 2)
    H = (np.swapaxes(X, 1, 2) @ X + l2 * np.eye(dim)).astype(np.float32)
    g = rng.normal(size=(n, dim)).astype(np.float32)
    return H, g, np.linalg.cond(H.astype(np.float64))


def _errors(x, H, g):
    want = np.linalg.solve(H.astype(np.float64), g.astype(np.float64)[..., None])
    want = want[..., 0]
    return np.linalg.norm(x - want, axis=1) / np.linalg.norm(want, axis=1)


# one lane, a lane short of a block, a block, a ragged third block
@pytest.mark.parametrize("n", [1, 127, 128, 300])
@pytest.mark.parametrize("dim", [1, 2, 21, 64, 128])
def test_batched_solve_is_as_close_to_float64_as_cho_solve(
        rng, on_lanes, dim, n):
    H, g, cond = _systems(rng, n, dim)
    got = np.asarray(jax.vmap(spd_solve)(jnp.asarray(H), jnp.asarray(g)))
    cho = np.asarray(jax.vmap(mod._cho_solve)(jnp.asarray(H), jnp.asarray(g)))
    assert got.shape == (n, dim) and np.isfinite(got).all()
    # within 4x of cho_solve's own error, a system at a time; where
    # cho_solve was lucky, of what a float32 Cholesky solve typically
    # leaves at that condition number
    room = 4 * np.maximum(_errors(cho, H, g), 0.1 * EPS * cond)
    assert (_errors(got, H, g) <= room).all()


@pytest.mark.parametrize("case", ["unbatched", "over_the_bound", "on_the_cpu"])
def test_where_the_kernel_does_not_run_the_bits_are_cho_solves(
        rng, monkeypatch, case):
    def no_kernel(*a, **k):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(mod, "lane_solve", no_kernel)
    if case != "on_the_cpu":
        monkeypatch.setattr(mod, "effective_platform", lambda: "tpu")
    dim = MAX_LANE_DIM + 1 if case == "over_the_bound" else 12
    H, g, _ = _systems(rng, 3, dim, cond_max=100.0)
    H, g = jnp.asarray(H), jnp.asarray(g)
    if case == "unbatched":
        got, want = spd_solve(H[0], g[0]), mod._cho_solve(H[0], g[0])
    else:
        got, want = jax.vmap(spd_solve)(H, g), jax.vmap(mod._cho_solve)(H, g)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _newton_like(H, g):
    """A vmapped ``lax.while_loop`` whose body solves, as
    ``_damped_newton`` does: lanes stop at different iterations."""
    def one(H_e, g_e):
        def body(st):
            x, it = st
            return x - spd_solve(H_e, H_e @ x - g_e), it + 1

        def cond(st):
            x, it = st
            return (jnp.linalg.norm(H_e @ x - g_e) > 1e-3) & (it < 5)

        return jax.lax.while_loop(cond, body, (jnp.zeros_like(g_e), 0))

    return jax.vmap(one)(H, g)


def test_the_rule_engages_inside_a_vmapped_while_loop(rng, on_lanes):
    H, g, _ = _systems(rng, 5, 16, cond_max=100.0)
    g[0] = 0.0  # this lane never enters the loop
    H, g = jnp.asarray(H), jnp.asarray(g)
    text = str(jax.make_jaxpr(_newton_like)(H, g))
    assert "pallas_call" in text and mod.KERNEL_NAME in text
    assert "cholesky" not in text
    x, iters = _newton_like(H, g)
    np.testing.assert_allclose(
        np.asarray(x), np.asarray(jax.vmap(mod._cho_solve)(H, g)),
        rtol=1e-4, atol=1e-5)
    assert int(iters[0]) == 0 and int(iters.max()) <= 2


@pytest.mark.parametrize("dim,path", [
    (1, "division"), (8, "lanes"), (12, "xla")])
def test_a_system_that_is_not_positive_definite_gives_no_finite_answer(
        rng, request, dim, path):
    if path != "xla":
        request.getfixturevalue("on_lanes")
    assert mod.solve_path(dim, mod.effective_platform()) == path
    H, g, _ = _systems(rng, 4, dim, cond_max=100.0)
    H[2] -= 2 * np.linalg.eigvalsh(H[2].astype(np.float64)).max() * np.eye(dim)
    got = np.asarray(jax.vmap(spd_solve)(jnp.asarray(H), jnp.asarray(g)))
    # as cholesky: the bad system's answer is not finite, its
    # neighbours' are untouched
    assert not np.isfinite(got[2]).any()
    assert np.isfinite(np.delete(got, 2, axis=0)).all()


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dim", [21, 64, MAX_LANE_DIM])
def test_the_kernel_compiles_for_the_chip(one_chip, dim):
    """Mosaic takes the kernel at the half-step's width, at a width that
    pads and at the bound (its VMEM limit)."""
    H = jax.ShapeDtypeStruct((300, dim, dim), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((300, dim), jnp.float32, sharding=one_chip)
    compiled = lane_solve.lower(H, g).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_kernels_bytes_do_not_follow_the_callers_stack(one_chip):
    """The persistent compile cache's key covers the kernel's serialized
    body, locations and all. As ``enable_compilation_cache`` sets JAX
    (one frame of traceback a location), two callers lower the same
    bytes, and the kernel keeps its name on the device; at JAX's default
    (ten frames) the bytes follow the caller, and a bank program, traced
    from wherever it is first needed, could compile anew from run to
    run."""
    H = jax.ShapeDtypeStruct((300, 16, 16), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((300, 16), jnp.float32, sharding=one_chip)

    def lowered():
        # the undecorated function: each lowering traces the kernel anew
        return jax.jit(lambda H, g: lane_solve.__wrapped__(H, g)).lower(H, g)

    def kernel_bytes(text):
        return re.search(
            r'body[^:]*: [^A-Za-z0-9+/]*([A-Za-z0-9+/=]+)', text).group(1)

    def from_deeper():
        return kernel_bytes(lowered().as_text())

    limit = jax.config.jax_traceback_in_locations_limit
    try:
        jax.config.update("jax_traceback_in_locations_limit", 1)
        program = lowered()
        assert kernel_bytes(program.as_text()) == from_deeper()
        assert f"%{mod.KERNEL_NAME}" in program.compile().as_text()
        jax.config.update("jax_traceback_in_locations_limit", 10)
        assert kernel_bytes(lowered().as_text()) != from_deeper()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
