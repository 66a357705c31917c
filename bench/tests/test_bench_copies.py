"""Each piece the benchmark copies from the program still agrees with the
program's own on a small seeded case, so a divergence shows."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_support as S  # noqa: E402

from harness import ceft_ref, dense_lm, rgg  # noqa: E402

SPEC = {"n": 300, "P": 6, "o": 4, "alpha": 0.75, "beta": 50, "c": 1.0,
        "heterogeneity": "high", "gamma": 0.1, "proc_beta": 0.5,
        "bw_range": [0.5, 2.0]}


@pytest.mark.parametrize("seed", [0, 11])
def test_rgg_copy_draws_the_programs_workload(seed):
    from repro.graphs.rgg import rgg as program_rgg

    wl = program_rgg("high", SPEC["n"], SPEC["P"],
                         np.random.default_rng(seed), o=SPEC["o"],
                         alpha=SPEC["alpha"], beta=SPEC["beta"])
    w = rgg.workload(SPEC, np.random.default_rng(seed))
    g = wl.graph
    src = np.repeat(np.arange(g.n), np.diff(g.cindptr))
    np.testing.assert_array_equal(w["src"], src)
    np.testing.assert_array_equal(w["dst"], g.cindices)
    np.testing.assert_array_equal(w["data"], g.cdata)
    np.testing.assert_array_equal(w["comp"], wl.comp)
    np.testing.assert_array_equal(w["bw"], wl.machine.bw)
    np.testing.assert_array_equal(w["L"], wl.machine.L)
    np.testing.assert_array_equal(w["level"], g.level)


@pytest.mark.parametrize("seed", [1, 4])
def test_ceft_copy_equals_the_programs_numpy_ceft(seed):
    from repro.core import Machine, ceft, chain_cost
    from repro.core.taskgraph import from_edge_arrays

    w = rgg.workload(SPEC, np.random.default_rng(seed))
    g = from_edge_arrays(w["n"], w["src"], w["dst"], w["data"])
    m = Machine(w["L"], w["bw"], np.ones(SPEC["P"], np.int64))
    want = ceft(g, w["comp"], m)
    args = (w["n"], w["src"], w["dst"], w["data"], w["comp"], w["L"],
            w["bw"])
    got = ceft_ref.ceft(*args)
    np.testing.assert_array_equal(got["ceft"], want.ceft)
    np.testing.assert_array_equal(got["pred_task"], want.pred_task)
    np.testing.assert_array_equal(got["pred_proc"], want.pred_proc)
    assert got["cpl"] == want.cpl
    assert got["path"] == want.path
    assert ceft_ref.chain_cost(got["path"], *args) == pytest.approx(
        chain_cost(want.path, g, w["comp"], m), rel=1e-12)
    # a chain that skips a task is no path
    assert ceft_ref.chain_cost(got["path"][::2], *args) == float("inf")


def test_bf16_control_is_far_from_the_reference():
    w = rgg.workload(SPEC, np.random.default_rng(2))
    args = (w["n"], w["src"], w["dst"], w["data"], w["comp"], w["L"],
            w["bw"])
    ref = ceft_ref.ceft(*args)
    import ml_dtypes

    low = ceft_ref.ceft(*args, dtype=ml_dtypes.bfloat16)
    assert abs(low["cpl"] - ref["cpl"]) / ref["cpl"] > 1e-4


def test_reference_forward_equals_the_programs_float32_model():
    """The plain reference and the program's model, both in float32 under
    "highest", give the same last-token logits on the benchmark's weights."""
    from repro.models.model import build

    cfg = S.small_config("minicpm-2b")
    arch = dataclasses.replace(S.small_arch(cfg), compute_dtype="float32")
    params = dense_lm.make_params(cfg, 3)
    tokens = np.random.default_rng(0).integers(2, cfg["vocab"], 24)
    with jax.default_matmul_precision("highest"):
        _, want = build(arch).prefill(
            params, {"tokens": jnp.asarray(tokens[None], jnp.int32)})
    got = dense_lm.forward_logits(cfg, params, jnp.asarray(tokens),
                                  jnp.asarray([23]))
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0, 0],
                               rtol=1e-4, atol=1e-5)


def test_weights_match_the_programs_parameter_tree():
    from repro.models.model import build

    cfg = S.small_config("minicpm-2b")
    params = dense_lm.make_params(cfg, 2**31 + 5)
    want = build(S.small_arch(cfg)).abstract()
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    again = dense_lm.make_params(cfg, 2**31 + 5)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(again)))
