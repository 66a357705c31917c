"""Kernel-layer benchmark: the CEFT level-relaxation contraction.

Reports the XLA fused relaxation at each shape (relaxations/s, effective
GB/s) and cross-checks the Pallas kernel against its oracle: compiled on an
accelerator, in interpret mode on the CPU (correctness only -- interpret
timing is meaningless).  The row name says which mode ran.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ceft_jax import xla_relax
from repro.kernels import ceft_relax
from repro.kernels.ref import ceft_relax_ref

from .common import CSV, scale

SHAPES = [(256, 4, 16), (256, 8, 64), (1024, 4, 64), (1024, 8, 128)]


def run(seed: int = 3):
    csv = CSV(["bench", "W", "D", "P", "impl", "us_per_call", "GB_per_s",
               "max_abs_err_vs_ref"])
    rng = np.random.default_rng(seed)
    relax_jit = jax.jit(xla_relax)
    for (W, D, P) in SHAPES:
        pv = jnp.asarray(rng.uniform(0, 100, (W, D, P)), jnp.float32)
        pdata = jnp.asarray(rng.uniform(0, 10, (W, D)), jnp.float32)
        validb = jnp.asarray(rng.random((W, D)) < 0.9)
        L = jnp.asarray(rng.uniform(0, 2, (P,)), jnp.float32)
        bw = jnp.asarray(rng.uniform(0.5, 2, (P, P)), jnp.float32)

        out = relax_jit(pv, pdata, validb, L, bw)
        out[0].block_until_ready()
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            out = relax_jit(pv, pdata, validb, L, bw)
        out[0].block_until_ready()
        t = (time.perf_counter() - t0) / reps
        # bytes through the fused op: inputs + outputs (the kernel's HBM model)
        bts = 4 * (W * D * P + 2 * W * D + P + P * P + 3 * W * P)
        want = ceft_relax_ref(pv, pdata, validb.astype(jnp.float32), L, bw)
        err = float(jnp.max(jnp.abs(out[0] - want[0])))
        csv.row("relax_xla", W, D, P, "xla_fused", f"{t * 1e6:.1f}",
                f"{bts / t / 1e9:.2f}", f"{err:.1e}")

        # Pallas kernel: correctness cross-check at bench shapes
        interpret = jax.default_backend() == "cpu"
        got = ceft_relax(pv, pdata, validb.astype(jnp.float32), L, bw,
                         interpret=interpret)
        errp = float(jnp.max(jnp.abs(got[0] - want[0])))
        csv.row("relax_pallas_interpret" if interpret else "relax_pallas",
                W, D, P, "pallas", "-", "-", f"{errp:.1e}")


if __name__ == "__main__":
    run()
