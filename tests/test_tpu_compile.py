"""Compile the main path for a described TPU v5e chip (no chip attached).

Interpret-mode tests cannot show what the chip's compiler refuses: a block
that breaks the (8, 128) tiling rule, a reshape Mosaic cannot lower, a
program that does not fit the chip's memory.  These tests compile, for one
chip of a described ``v5e:2x2`` topology, the Pallas kernels at the shapes
their ``ops.py`` wrappers produce on a TPU, the CSR super-steps at the
buckets of the paper's largest RGG (n=16384 tasks, P=64 classes), and the
serving engine's minicpm-2b and granite-4.0-h-small prefill and decode
steps at published width.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a worker that cannot skips
here instead of failing collection for every worker.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as C
from repro.core.ceft_jax import _superstep_fns_for, xla_edge_relax
from repro.kernels import ops
from repro.models.common import abstract_params
from repro.models.model import build
from repro.models.transformer import SLOTS
from repro.serve.engine import Engine

V5E_HBM_BYTES = 15.75 * 2**30  # what the compiler reports usable on one v5e


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_of(topo):
    """ShapeDtypeStruct factory placed on the described topology's first
    chip, with the persistent compilation cache off: a compile for a
    described chip can be written to it but never read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


P = 64  # classes; the wrappers pad the lane axis to 128


@pytest.mark.parametrize("kernel", ["edge_relax", "edge_relax_superstep",
                                    "ceft_relax", "minplus"])
def test_pallas_kernel_compiles_for_v5e(kernel, shape_of):
    S = shape_of
    args = {
        "edge_relax": (S((1024, P)), S((1024,)), S((P,)), S((P, P))),
        "edge_relax_superstep": (S((4, 1024, P)), S((4, 1024)), S((P,)),
                                 S((P, P))),
        "ceft_relax": (S((64, 8, P)), S((64, 8)), S((64, 8)), S((P,)),
                       S((P, P))),
        "minplus": (S((512, 300)), S((300, 512))),
    }[kernel]
    compiled = _compile(getattr(ops, kernel), *args)
    assert "tpu_custom_call" in compiled.as_text()


# The buckets _fused_runs picks for rgg("high", 16384, 64, seed 5, o=4,
# alpha=0.75, beta=50): v_b = 16384 and a first segment-layout run of
# R=192 levels, W_b=192 tasks and E_b=1024 edges per level.
V_B, R, W_B, E_B, D_B = 16384, 192, 192, 1024, 8


def _csr_superstep(S, layout: str, masked: bool = True):
    """The CSR super-step compiled at these buckets for a described v5e."""
    i32 = jnp.int32
    fns = _superstep_fns_for(xla_edge_relax, "tpu")
    carry = (S((V_B + 1, P)), S((V_B + 1, P), i32), S((V_B + 1, P), i32))
    if layout == "seg":
        run = (S((R, W_B), i32), S((R, E_B), i32), S((R, E_B)),
               S((R, E_B), i32), S((R,), i32))
        fn = fns[(False, "seg", masked, False)]
    else:
        run = (S((R, W_B), i32), S((R, W_B, D_B), i32), S((R, W_B, D_B)))
        fn = fns[(False, "dense", False, False)]
    return fn.lower(*carry, S((V_B + 1, P)), *run, S((P,)),
                    S((P, P))).compile()


@pytest.mark.parametrize("layout", ["seg", "dense"])
def test_csr_superstep_compiles_for_v5e(layout, shape_of):
    compiled = _csr_superstep(shape_of, layout)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("masked", [True, False])
def test_seg_superstep_has_no_element_gathers(masked, shape_of):
    """The segment layout reads its back-pointers without gathering single
    elements: a v5e runs such a gather element by element, and the W_B x P
    of them per level once took most of the sweep's device time.  Only row
    gathers (a whole P-wide row per index) may remain."""
    hlo = _csr_superstep(shape_of, "seg", masked).as_text()
    gathers = [line for line in hlo.splitlines() if " gather(" in line]
    assert gathers, "no gather at all: the pattern below matches nothing"
    element = [g for g in gathers
               if re.search(r"slice_sizes=\{1(,1)*\}", g)]
    assert not element, element


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_minicpm_engine_step_fits_one_v5e(step, shape_of):
    """The serving engine's steps at published width (2.7B params kept in
    float32): prefill of 4 x 128-token prompts, and decode against a
    144-token cache (128 + 16 new), each compiled from the Engine's own
    jitted step (abstract weights: nothing is allocated).  Decode runs at
    batch 2, the largest micro-batch chip_smoke.py's router run may
    coalesce (``--batch 2``): at batch 4 the step needs 16.11 GiB, because
    it makes a bfloat16 copy of the whole float32 weight stack (4.88 GB)."""
    cfg = C.get("minicpm-2b")
    params = jax.tree.map(lambda a: shape_of(a.shape, a.dtype),
                          build(cfg).abstract())
    engine = Engine(cfg, params=params)
    if step == "prefill":
        compiled = engine._prefill.lower(
            params, {"tokens": shape_of((4, 128), jnp.int32)}).compile()
    else:
        B = 2
        cache = jax.tree.map(
            lambda a: shape_of(a.shape, a.dtype),
            abstract_params(engine.model.cache_specs(B, 128 + 16)))
        compiled = engine._decode.lower(
            params, cache, shape_of((B, 1), jnp.int32),
            shape_of((), jnp.int32)).compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_granite_hybrid_engine_step_fits_one_v5e(step, shape_of):
    """granite-4.0-h-small's chip share (4.42 B bfloat16 parameters, 18
    Mamba-2 and 2 attention layers, 9 held experts of 72 each) at the
    serving cell's largest shapes: prefill of 4 x 1024-token prompts with
    dropless experts and four SSD chunks, and decode at batch 4 against a
    1280-token cache with the expert-slot counter carried."""
    cfg = C.get("granite-4.0-h-small")
    params = jax.tree.map(lambda a: shape_of(a.shape, a.dtype),
                          build(cfg).abstract())
    engine = Engine(cfg, params=params)
    B = 4
    if step == "prefill":
        compiled = engine._prefill.lower(
            params, {"tokens": shape_of((B, 1024), jnp.int32)}).compile()
    else:
        cache = jax.tree.map(
            lambda a: shape_of(a.shape, a.dtype),
            abstract_params(engine.model.cache_specs(B, 1024 + 256)))
        cache[SLOTS] = shape_of((3,), jnp.int32)
        compiled = engine._decode.lower(
            params, cache, shape_of((B, 1), jnp.int32),
            shape_of((), jnp.int32)).compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES
