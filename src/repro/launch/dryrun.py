"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell against the production topology,
record memory/cost/collective analysis for §Dry-run and §Roofline.

A host-CPU tool: the production mesh is a fake fleet of CPU devices, so
importing this module pins JAX to the CPU and appends the fleet's device
count (512, or REPRO_DRYRUN_DEVICES) to XLA_FLAGS.  Both must happen before
jax initializes a backend.  Tests and benches never import this module, so
they keep seeing the single real CPU device.

  python -m repro.launch.dryrun --arch glm4-9b --cell train_4k --mesh single
  python -m repro.launch.dryrun --all --out experiments/dryrun      # driver
"""
import os

os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))))

import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_platforms", "cpu")

from .. import configs as C
from ..models.common import (profile_names, resolve_spec, sharding_profile,
                             tree_map_pspec)
from ..models.model import build
from ..substrate import (
    compiled_cost_analysis,
    make_mesh as substrate_make_mesh,
    mesh_context,
)
from .hlo_stats import collective_stats
from .mesh import mesh_axis_sizes
from .steps import (
    DecodeStep,
    TrainStep,
    abstract_cache,
    abstract_state,
    build_train,
    input_shardings,
    make_optimizer,
)
from jax.sharding import NamedSharding, PartitionSpec


def make_mesh(kind: str, smoke: bool = False):
    devs = np.asarray(jax.devices())
    if kind == "moe":  # EP-aligned single-pod mesh (see PROFILES["moe_ep"])
        shape, axes = ((2, 2, 2), ("data", "expert", "tp")) if smoke else \
                      ((16, 8, 2), ("data", "expert", "tp"))
    elif smoke:
        shape = (2, 2, 2) if kind == "multi" else (4, 2)
        axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    else:
        shape = (2, 16, 16) if kind == "multi" else (16, 16)
        axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return substrate_make_mesh(shape, axes, devices=devs)


def analytic_bytes_per_device(spec_tree, mesh, dtype_override=None) -> int:
    ms = mesh_axis_sizes(mesh)
    total = 0

    def add(_, p):
        nonlocal total
        spec = resolve_spec(p.shape, p.logical, ms)
        shard = 1
        for entry in spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                shard *= ms[ax]
        size = int(np.prod(p.shape)) * jnp.dtype(dtype_override or p.dtype).itemsize
        total += size // shard
        return None

    tree_map_pspec(add, spec_tree)
    return total


def run_cell(arch: str, cell_name: str, mesh_kind: str, smoke: bool, out_dir: Path, profile: str = 'baseline'):
    # scoped for the whole lower+compile: the profile travels with this cell,
    # not with process-global state (concurrent cells stay independent)
    with sharding_profile(profile):
        return _run_cell(arch, cell_name, mesh_kind, smoke, out_dir, profile)


def _run_cell(arch: str, cell_name: str, mesh_kind: str, smoke: bool, out_dir: Path, profile: str):
    cfg = C.get(arch, smoke=smoke)
    # smoke: shrink the cells to smoke scale but keep their character
    cell = C.smoke_cell(cell_name) if smoke else C.SHAPES[cell_name]
    mesh = make_mesh(mesh_kind, smoke)
    model = build(cfg)
    rec = {
        "arch": arch, "cell": cell_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh_axis_sizes(mesh)),
        "seq_len": cell.seq_len, "global_batch": cell.global_batch,
        "kind": cell.kind, "ok": False,
        "n_params": cfg.n_params(), "n_active_params": cfg.n_active_params(),
    }
    t0 = time.monotonic()
    try:
        with mesh_context(mesh):
            inputs = model.input_specs(cell)
            in_sh = input_shardings(inputs, mesh)
            if cell.kind == "train":
                opt = make_optimizer(cfg)
                step = TrainStep(model, opt)
                params, opt_state = abstract_state(model, opt)
                specs = model.specs()
                from ..models.common import param_shardings
                p_sh = param_shardings(specs, mesh)
                m_sh = param_shardings(opt.moment_specs(specs), mesh)
                from ..optim import AdamWState
                o_sh = AdamWState(NamedSharding(mesh, PartitionSpec()), m_sh, m_sh)
                jitted = jax.jit(step, in_shardings=(p_sh, o_sh, in_sh),
                                 out_shardings=(p_sh, o_sh, None))
                lowered = jitted.lower(params, opt_state, inputs)
                rec["state_bytes_per_device"] = (
                    analytic_bytes_per_device(specs, mesh)
                    + 2 * analytic_bytes_per_device(opt.moment_specs(specs), mesh)
                )
            elif cell.kind == "prefill":
                from ..models.common import param_shardings
                params = model.abstract()
                p_sh = param_shardings(model.specs(), mesh)
                jitted = jax.jit(model.prefill, in_shardings=(p_sh, in_sh))
                lowered = jitted.lower(params, inputs)
                rec["state_bytes_per_device"] = analytic_bytes_per_device(
                    model.specs(), mesh)
            else:  # decode
                from ..models.common import param_shardings
                params = model.abstract()
                cache = abstract_cache(model, cell)
                p_sh = param_shardings(model.specs(), mesh)
                c_sh = param_shardings(model.cache_specs(cell.global_batch, cell.seq_len), mesh)
                step = DecodeStep(model)
                jitted = jax.jit(step, in_shardings=(p_sh, c_sh, in_sh),
                                 out_shardings=(None, None, c_sh))
                lowered = jitted.lower(params, cache, inputs)
                rec["state_bytes_per_device"] = analytic_bytes_per_device(
                    model.specs(), mesh) + analytic_bytes_per_device(
                    model.cache_specs(cell.global_batch, cell.seq_len), mesh)
            rec["lower_s"] = round(time.monotonic() - t0, 2)
            t1 = time.monotonic()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.monotonic() - t1, 2)

            try:
                ca = compiled_cost_analysis(compiled)
                rec["cost_analysis"] = {
                    k: ca[k] for k in ("flops", "bytes accessed", "transcendentals")
                    if k in ca
                }
            except Exception as e:  # pragma: no cover
                rec["cost_analysis"] = {"error": repr(e)}
            try:
                ma = compiled.memory_analysis()
                rec["memory_analysis"] = {
                    a: int(getattr(ma, a))
                    for a in ("argument_size_in_bytes", "output_size_in_bytes",
                              "temp_size_in_bytes", "alias_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(ma, a)
                } or {"repr": repr(ma)}
            except Exception as e:  # pragma: no cover
                rec["memory_analysis"] = {"error": repr(e)}
            try:
                txt = compiled.as_text()
                rec["collectives"] = collective_stats(txt, mesh.devices.size)
            except Exception as e:  # pragma: no cover
                rec["collectives"] = {"error": repr(e)}
            rec["ok"] = True
    except Exception:
        rec["error"] = traceback.format_exc(limit=20)
    rec["total_s"] = round(time.monotonic() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    rec["profile"] = profile
    tag = "" if profile == "baseline" else f"__{profile}"
    fn = out_dir / f"{arch}__{cell_name}__{mesh_kind}{tag}.json"
    fn.write_text(json.dumps(rec, indent=1, default=float))
    status = "OK " if rec["ok"] else "FAIL"
    print(f"[{status}] {arch:16s} {cell_name:12s} {mesh_kind:6s} "
          f"lower={rec.get('lower_s', '-'):>7}s compile={rec.get('compile_s', '-'):>7}s",
          flush=True)
    if not rec["ok"]:
        # the traceback must reach the parent process, not just the json
        print(rec["error"], file=sys.stderr, flush=True)
    return rec["ok"]


def driver(args):
    cells = []
    for arch in (args.archs or C.ARCHS):
        cfg = C.get(arch, smoke=args.smoke)
        names = C.cells_for(C.get(arch))  # applicability from the FULL config
        for cell in names:
            for mk in (["single", "multi"] if args.mesh == "both" else [args.mesh]):
                cells.append((arch, cell, mk))
    if args.only_missing:
        cells = [
            (a, c, m) for (a, c, m) in cells
            if not (Path(args.out) / f"{a}__{c}__{m}.json").exists()
            or not json.loads((Path(args.out) / f"{a}__{c}__{m}.json").read_text())["ok"]
        ]
    print(f"dry-run driver: {len(cells)} cells", flush=True)
    fails = []
    for arch, cell, mk in cells:
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--cell", cell, "--mesh", mk, "--out", args.out]
        if args.smoke:
            cmd.append("--smoke")
        cmd += ["--profile", args.profile]
        env = dict(os.environ)
        if args.devices:
            env["REPRO_DRYRUN_DEVICES"] = str(args.devices)
        r = subprocess.run(cmd, env=env)
        if r.returncode != 0:
            fails.append((arch, cell, mk))
    print(f"driver done, {len(fails)} subprocess failures: {fails}", flush=True)
    return 1 if fails else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=C.ARCHS)
    ap.add_argument("--archs", nargs="*", help="driver: subset of archs")
    ap.add_argument("--cell", choices=list(C.SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both", "moe"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--devices", type=int, default=0, help="driver: fake device count")
    ap.add_argument("--profile", default="baseline", choices=profile_names())
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    if args.all:
        sys.exit(driver(args))
    assert args.arch and args.cell and args.mesh in ("single", "multi", "moe")
    ok = run_cell(args.arch, args.cell, args.mesh, args.smoke, Path(args.out),
                  profile=args.profile)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
