"""Pallas kernel validation: interpret-mode execution against the pure-jnp
oracles across shape/dtype sweeps + semiring properties + end-to-end CEFT.

The wrappers compile for the default backend unless told otherwise, so every
call here asks for the interpreter explicitly (``interpret=True``); that the
kernels also compile for a TPU is checked in tests/test_tpu_compile.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ceft_relax, edge_relax, minplus, pallas_edge_relax, pallas_relax
from repro.kernels.ref import ceft_relax_ref, edge_relax_ref, minplus_ref

# module-level so the jitted sweeps, keyed by relax_fn, trace them once
interp_relax = functools.partial(pallas_relax, interpret=True)
interp_edge_relax = functools.partial(pallas_edge_relax, interpret=True)

SHAPES_MINPLUS = [(4, 3, 5), (128, 16, 128), (300, 37, 260), (1, 1, 1),
                  (257, 129, 255), (16, 256, 16)]


@pytest.mark.parametrize("wrapper", ["minplus", "ceft_relax", "edge_relax",
                                     "edge_relax_superstep"])
def test_wrappers_never_fall_back_to_the_interpreter(wrapper):
    """Called without ``interpret=True`` on the CPU, every wrapper asks for a
    compiled kernel and fails loudly instead of quietly interpreting."""
    from repro.kernels import ops

    assert jax.default_backend() == "cpu"
    f32 = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
    args = {"minplus": (f32(4, 4), f32(4, 4)),
            "ceft_relax": (f32(8, 2, 4), f32(8, 2), f32(8, 2), f32(4), f32(4, 4)),
            "edge_relax": (f32(8, 4), f32(8), f32(4), f32(4, 4)),
            "edge_relax_superstep": (f32(2, 8, 4), f32(2, 8), f32(4), f32(4, 4)),
            }[wrapper]
    with pytest.raises(ValueError, match="interpret mode"):
        getattr(ops, wrapper)(*args)


@pytest.mark.parametrize("shape", SHAPES_MINPLUS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_minplus_matches_ref(shape, dtype):
    m, k, n = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    a = jnp.asarray(rng.uniform(-5, 5, (m, k)), dtype)
    b = jnp.asarray(rng.uniform(-5, 5, (k, n)), dtype)
    got = minplus(a, b, interpret=True)
    want = minplus_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-5)


@given(st.integers(0, 10_000))
@settings(max_examples=15)
def test_minplus_semiring_properties(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 20))
    a = jnp.asarray(rng.uniform(-5, 5, (n, n)), jnp.float32)
    # identity: I with 0 on diag, +inf off-diag
    eye = jnp.where(jnp.eye(n, dtype=bool), 0.0, 3.0e38)
    mp = functools.partial(minplus, interpret=True)
    np.testing.assert_allclose(mp(a, eye), a, rtol=1e-6)
    np.testing.assert_allclose(mp(eye, a), a, rtol=1e-6)
    # associativity (in fp32 exact: min/plus of same values)
    b = jnp.asarray(rng.uniform(-5, 5, (n, n)), jnp.float32)
    c = jnp.asarray(rng.uniform(-5, 5, (n, n)), jnp.float32)
    left = mp(mp(a, b), c)
    right = mp(a, mp(b, c))
    np.testing.assert_allclose(left, right, rtol=1e-5, atol=1e-4)


CELL_SHAPES = [(8, 3, 4), (5, 1, 2), (16, 7, 13), (33, 9, 64), (64, 2, 128), (1, 1, 1)]


@pytest.mark.parametrize("shape", CELL_SHAPES)
def test_ceft_relax_matches_ref(shape):
    W, D, P = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    pv = jnp.asarray(rng.uniform(0, 100, (W, D, P)), jnp.float32)
    pdata = jnp.asarray(rng.uniform(0, 10, (W, D)), jnp.float32)
    validp = jnp.asarray(rng.random((W, D)) < 0.8, jnp.float32)
    L = jnp.asarray(rng.uniform(0, 2, (P,)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.5, 2, (P, P)), jnp.float32)
    got = ceft_relax(pv, pdata, validp, L, bw, interpret=True)
    want = ceft_relax_ref(pv, pdata, validp, L, bw)
    for g, w, name in zip(got, want, ["maxk", "argk", "argl"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


@given(st.integers(0, 10_000))
@settings(max_examples=10)
def test_ceft_jax_with_pallas_relax_end_to_end(seed):
    """The full DP sweep with the Pallas kernel plugged in reproduces the
    numpy Algorithm-1 results (values and the backtracked path)."""
    from repro.core import ceft, random_machine
    from repro.core.ceft_jax import ceft_jax
    from conftest import make_random_dag

    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 10))
    P = int(rng.integers(1, 5))
    g = make_random_dag(n, 0.4, rng)
    comp = rng.uniform(1, 10, size=(n, P))
    m = random_machine(P, rng, L_range=(0.0, 1.0))
    a = ceft(g, comp, m)
    b = ceft_jax(g, comp, m, relax=interp_relax)
    np.testing.assert_allclose(b.ceft, a.ceft, rtol=2e-5)
    assert b.cpl == pytest.approx(a.cpl, rel=2e-5)


EDGE_SHAPES = [(5, 3), (128, 16), (300, 7), (1, 1), (257, 13), (64, 64)]


@pytest.mark.parametrize("shape", EDGE_SHAPES)
def test_edge_relax_matches_ref(shape):
    """Segment-tiled edge relaxation (the CSR sweep's Pallas inner loop)."""
    E, P = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    pv = jnp.asarray(rng.uniform(0, 100, (E, P)), jnp.float32)
    pdata = jnp.asarray(rng.uniform(0, 10, (E,)), jnp.float32)
    L = jnp.asarray(rng.uniform(0, 2, (P,)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.5, 2, (P, P)), jnp.float32)
    got = edge_relax(pv, pdata, L, bw, interpret=True)
    want = edge_relax_ref(pv, pdata, L, bw)
    for g, w, name in zip(got, want, ["minl", "argl"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


@given(st.integers(0, 10_000))
@settings(max_examples=10)
def test_ceft_jax_csr_with_pallas_edge_relax_end_to_end(seed):
    """The CSR DP sweep with the segment-tiled Pallas kernel plugged in
    reproduces the numpy Algorithm-1 results (values and backtracked path)."""
    from repro.core import ceft, random_machine
    from repro.core.ceft_jax import ceft_jax_csr
    from conftest import make_random_dag

    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 10))
    P = int(rng.integers(1, 5))
    g = make_random_dag(n, 0.4, rng)
    comp = rng.uniform(1, 10, size=(n, P))
    m = random_machine(P, rng, L_range=(0.0, 1.0))
    a = ceft(g, comp, m)
    b = ceft_jax_csr(g, comp, m, relax=interp_edge_relax)
    np.testing.assert_allclose(b.ceft, a.ceft, rtol=2e-5)
    assert b.cpl == pytest.approx(a.cpl, rel=2e-5)
    assert b.path == a.path


SUPERSTEP_SHAPES = [(1, 5, 3), (4, 128, 16), (3, 300, 7), (2, 64, 64), (1, 1, 1)]


@pytest.mark.parametrize("shape", SUPERSTEP_SHAPES)
def test_edge_relax_superstep_matches_ref(shape):
    """Stacked super-step tile variant (ISSUE 4): a fused run's (R, E, P)
    edge tables relaxed in one pallas_call, vs the stacked oracle."""
    from repro.kernels import edge_relax_superstep
    from repro.kernels.ref import edge_relax_superstep_ref

    R, E, P = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    pv = jnp.asarray(rng.uniform(0, 100, (R, E, P)), jnp.float32)
    pdata = jnp.asarray(rng.uniform(0, 10, (R, E)), jnp.float32)
    L = jnp.asarray(rng.uniform(0, 2, (P,)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.5, 2, (P, P)), jnp.float32)
    got = edge_relax_superstep(pv, pdata, L, bw, interpret=True)
    want = edge_relax_superstep_ref(pv, pdata, L, bw)
    for g, w, name in zip(got, want, ["minl", "argl"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


def test_edge_relax_superstep_consistent_with_per_level():
    """Each stacked slice equals the single-level edge_relax on that slice:
    the super-step variant is the same contraction, batched over the run."""
    from repro.kernels import edge_relax_superstep

    rng = np.random.default_rng(77)
    R, E, P = 4, 96, 5
    pv = jnp.asarray(rng.uniform(0, 100, (R, E, P)), jnp.float32)
    pdata = jnp.asarray(rng.uniform(0, 10, (R, E)), jnp.float32)
    L = jnp.asarray(rng.uniform(0, 2, (P,)), jnp.float32)
    bw = jnp.asarray(rng.uniform(0.5, 2, (P, P)), jnp.float32)
    minl, argl = edge_relax_superstep(pv, pdata, L, bw, interpret=True)
    for r in range(R):
        m1, a1 = edge_relax(pv[r], pdata[r], L, bw, interpret=True)
        np.testing.assert_array_equal(np.asarray(minl[r]), np.asarray(m1))
        np.testing.assert_array_equal(np.asarray(argl[r]), np.asarray(a1))


@pytest.mark.parametrize("shape", [(8, 3, 4), (16, 7, 13)])
def test_ceft_relax_bf16(shape):
    """bf16 kernel path agrees with the bf16 oracle (TPU's native dtype)."""
    W, D, P = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    pv = jnp.asarray(rng.uniform(0, 100, (W, D, P)), jnp.bfloat16)
    pdata = jnp.asarray(rng.uniform(0, 10, (W, D)), jnp.bfloat16)
    validp = jnp.asarray(rng.random((W, D)) < 0.8, jnp.bfloat16)
    L = jnp.asarray(rng.uniform(0, 2, (P,)), jnp.bfloat16)
    bw = jnp.asarray(rng.uniform(0.5, 2, (P, P)), jnp.bfloat16)
    got = ceft_relax(pv, pdata, validp, L, bw, interpret=True)
    want = ceft_relax_ref(pv, pdata, validp, L, bw)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32), rtol=1e-2)
