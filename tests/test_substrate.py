"""Substrate tests: the mesh/sharding layer on the installed JAX, the
chip-only smoke script's refusal on the CPU, optimizer, schedules,
checkpointing, data determinism, gradient compression."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec

from repro import checkpoint as ckpt
from repro import substrate
from repro.data import DataConfig, SyntheticLM
from repro.optim import AdamW, warmup_cosine, wsd
from repro.optim.grad_compress import ef_quantize, ef_quantize_tree, init_ef

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------- mesh/sharding substrate
def test_make_mesh_insufficient_devices():
    with pytest.raises(RuntimeError, match="devices"):
        substrate.make_mesh((1024, 64), ("data", "model"))


def test_constrain_no_mesh_is_identity():
    x = jnp.ones((4, 4))
    assert substrate.constrain(x, "data", "model") is x
    assert substrate.constrain_spec(x, PartitionSpec("data", None)) is x
    from repro.models.common import constrain as logical_constrain
    assert logical_constrain(x, "batch", "embed_d") is x


def test_constrain_under_active_mesh_jit():
    mesh = substrate.make_mesh((1, 1), ("data", "model"))
    x = jnp.ones((4, 4))
    with substrate.mesh_context(mesh):
        y = jax.jit(lambda a: substrate.constrain(a, "data", None))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_modern_generation_routing():
    """make_mesh builds Auto axes; mesh_context activates the mesh for the
    abstract-mesh queries and restores 'no mesh' on exit."""
    mesh = substrate.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto,) * 2
    assert substrate.mesh_axis_sizes(mesh) == {"data": 1, "model": 1}
    assert substrate.current_abstract_mesh() is None
    with substrate.mesh_context(mesh):
        assert substrate.current_abstract_mesh().axis_names == ("data", "model")
        assert substrate.current_axis_sizes() == {"data": 1, "model": 1}
    assert substrate.current_axis_sizes() is None


def test_modern_constrain_divisibility_degradation():
    """Under a (data=2, model=4) mesh a (4, 6) array keeps 'data' on dim 0
    and drops 'model' from dim 1 (6 % 4 != 0).  An abstract mesh suffices to
    trace the constraint, so no fake devices are needed."""
    am = AbstractMesh((2, 4), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
    with jax.sharding.use_abstract_mesh(am):
        jaxpr = jax.make_jaxpr(
            lambda a: substrate.constrain(a, "data", "model"))(jnp.ones((4, 6)))
    (eqn,) = jaxpr.eqns
    assert eqn.primitive.name == "sharding_constraint"
    assert eqn.params["sharding"].spec == PartitionSpec("data", None)


def test_shard_map_modern_kwarg_detection():
    """The substrate turns replication checking off: an out_spec that asks
    for replication the checker cannot infer is accepted, where the checked
    jax.shard_map refuses it."""
    mesh = substrate.make_mesh((1,), ("data",))
    kw = dict(mesh=mesh, in_specs=(PartitionSpec("data"),),
              out_specs=PartitionSpec())
    f = substrate.shard_map(lambda a: a + 1, **kw)
    np.testing.assert_array_equal(np.asarray(f(jnp.ones(4))), 2 * np.ones(4))
    with pytest.raises(ValueError, match="replication"):
        jax.shard_map(lambda a: a + 1, **kw)(jnp.ones(4))


def test_shard_map_legacy_executes():
    mesh = substrate.make_mesh((1,), ("data",))
    f = substrate.shard_map(lambda a: a * 2, mesh=mesh,
                            in_specs=(PartitionSpec(),),
                            out_specs=PartitionSpec())
    np.testing.assert_array_equal(np.asarray(f(jnp.ones(4))), 2 * np.ones(4))


def test_chip_smoke_refuses_without_tpu():
    """chip_smoke.py is a chip-only proof: on the CPU it exits non-zero
    before any phase and prints no ok line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr, r.stderr


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_from_env_or_fixed(from_env, monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set the helper leaves JAX's own
    setting alone; otherwise the cache goes to the fixed <repo>/.jax_cache."""
    prev = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = substrate.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert got == str(REPO / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_adamw_converges_on_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=100.0)
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = opt.init(params)

    def loss(p):
        return jnp.sum((p["w"] - jnp.asarray([1.0, 2.0, 3.0])) ** 2)

    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(g, state, params)
    assert float(loss(params)) < 1e-3


def test_adamw_grad_clip_and_bias_correction():
    opt = AdamW(lr=1e-2, clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    g = {"w": jnp.full(4, 100.0)}
    new_p, state, gn = opt.update(g, state, params)
    assert float(gn) == pytest.approx(200.0, rel=1e-5)  # ||g|| = sqrt(4*100^2)
    # first step of Adam moves by ~lr regardless of grad scale
    assert np.allclose(np.asarray(new_p["w"]), -1e-2, rtol=1e-3)


def test_schedules():
    cos = warmup_cosine(1.0, warmup=10, total=100)
    assert float(cos(jnp.asarray(0))) == 0.0
    assert float(cos(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(cos(jnp.asarray(100))) == pytest.approx(0.1, rel=1e-3)
    w = wsd(1.0, warmup=10, total=100, decay_frac=0.2)
    assert float(w(jnp.asarray(50))) == pytest.approx(1.0)   # stable phase
    assert float(w(jnp.asarray(80))) == pytest.approx(1.0)   # decay start
    assert float(w(jnp.asarray(100))) == pytest.approx(0.01, rel=1e-3)


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32)}}
    ckpt.save(tmp_path, 7, tree)
    assert ckpt.latest_valid(tmp_path) == 7
    like = jax.tree.map(lambda x: np.zeros_like(x), tree)
    out = ckpt.restore(tmp_path, 7, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_corruption_fallback(tmp_path):
    tree = {"w": jnp.ones(8)}
    ckpt.save(tmp_path, 1, tree)
    ckpt.save(tmp_path, 2, jax.tree.map(lambda x: x * 2, tree))
    # corrupt the newest shard
    shard = tmp_path / "step_2" / "000000.npy"
    shard.write_bytes(b"garbage")
    assert ckpt.latest_valid(tmp_path) == 1  # falls back to the intact one
    out = ckpt.restore(tmp_path, 1, {"w": np.zeros(8)})
    np.testing.assert_array_equal(out["w"], np.ones(8))


def test_checkpoint_async(tmp_path):
    tree = {"w": jnp.full(16, 3.0)}
    t = ckpt.save(tmp_path, 5, tree, async_=True)
    t.join()
    assert ckpt.latest_valid(tmp_path) == 5


def test_data_determinism_and_restartability():
    cfg = DataConfig(vocab=128, seq_len=32, global_batch=4, seed=9)
    a = SyntheticLM(cfg)
    b = SyntheticLM(cfg)  # a "restarted" pipeline
    for step in (0, 5, 17):
        x, y = a.batch(step), b.batch(step)
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
    # labels are tokens shifted by one
    x = a.batch(3)
    np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    # structure: not uniform (zipf-ish marginal)
    counts = np.bincount(x["tokens"].ravel(), minlength=128)
    assert counts.max() > 4 * max(counts.mean(), 1)


def test_error_feedback_invariant():
    """g + ef == g_hat + new_ef exactly (per step), so the accumulated
    quantization error never grows."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(512,)) * 10, jnp.float32)
    ef = jnp.zeros(512)
    for _ in range(50):
        gh, ef2 = ef_quantize(g, ef)
        np.testing.assert_allclose(np.asarray(g + ef), np.asarray(gh + ef2),
                                   rtol=1e-5, atol=1e-4)
        ef = ef2
    # the error stays bounded by one quantization bucket
    assert float(jnp.abs(ef).max()) < float(jnp.abs(g).max()) / 127 * 2


def test_ef_tree_and_sgd_convergence_with_compression():
    """SGD with EF-int8 compressed grads converges to the same optimum."""
    target = jnp.asarray([1.0, -2.0, 0.5, 3.0])
    params = {"w": jnp.zeros(4)}
    ef = init_ef(params)
    lr = 0.05
    for _ in range(400):
        g = {"w": 2 * (params["w"] - target)}
        gh, ef = ef_quantize_tree(g, ef)
        params = {"w": params["w"] - lr * gh["w"]}
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)
