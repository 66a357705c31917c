"""Roofline analysis via component probes (deliverable g).

XLA's cost_analysis does NOT scale ``scan`` bodies by trip count (verified
empirically -- a scan of 10 matmuls reports 1 matmul of flops), so whole-model
numbers from the dry-run compile are per-iteration only.  Instead we compile
every *scan-free component* of the step on the production mesh (same sharding
constraints as the model), read its per-device HLO flops / bytes / collective
bytes, and assemble the cell's totals with exact trip counts taken from the
code structure:

    layer scans        x n_layers (per kind)
    attention tiles    x nq * nk  (the online-softmax chunk grid; the masked
                                   upper triangle is counted -- that waste is
                                   real in our implementation and is visible in
                                   the useful-FLOPs ratio)
    SSD chunks         x S / Q
    loss chunks        x S / loss_chunk
    optimizer update   x param_bytes / probe_bytes

Training components are compiled as jax.value_and_grad (fwd+bwd in one
program); remat="full" adds one extra forward per layer, exactly like the
jax.checkpoint policy in the model.

Terms (per device == global/(chips x peak), cost_analysis is per-device under
SPMD -- verified):

    compute_s    = flops / peak_flops          (197 TF/s bf16, v5e)
    memory_s     = bytes / hbm_bw              (819 GB/s)
    collective_s = collective_bytes / link_bw  (50 GB/s/link ICI)
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..configs.base import ArchConfig, ShapeCell
from ..models import common as mc
from ..models.layers import (
    attn_decode,
    attn_specs,
    mlp,
    mlp_specs,
    qkv_proj,
    rmsnorm,
    rmsnorm_spec,
)
from ..models.moe import moe, moe_specs
from ..models.ssm import ssd_decode, ssd_prefill, ssm_specs
from ..models.common import (
    PSpec,
    ShardingProfile,
    abstract_params,
    active_profile,
    param_shardings,
    profile_names,
    resolve_profile,
    resolve_spec,
    sharding_profile,
)
from ..substrate import compiled_cost_analysis, mesh_context
from .hlo_stats import collective_stats
from .mesh import mesh_axis_sizes

HW = {"peak_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9}
Q_CHUNK, K_CHUNK = 512, 1024  # layers.chunked_attention defaults


def _sh(mesh, shape, logical):
    return NamedSharding(mesh, resolve_spec(shape, logical, mesh_axis_sizes(mesh)))


def _io_bytes_per_device(args, shardings, out_avals, mesh) -> float:
    """Fusion-ideal HBM traffic: every input read once, every output written
    once, at the per-device shard sizes (the TPU roofline convention; the
    XLA:CPU 'bytes accessed' has no fusion and overcounts intermediates)."""
    total = 0.0
    for a, sh in zip(jax.tree.leaves(args), jax.tree.leaves(shardings)):
        shp = sh.shard_shape(a.shape) if hasattr(sh, "shard_shape") else a.shape
        total += float(np.prod(shp)) * jnp.dtype(a.dtype).itemsize
    ms = mesh_axis_sizes(mesh)
    n = float(np.prod(list(ms.values())))
    for o in jax.tree.leaves(out_avals):
        # outputs: assume they shard as well as the batch-heaviest input (XLA
        # picks); divide by the full device count as the optimistic bound
        total += float(np.prod(o.shape)) * jnp.dtype(o.dtype).itemsize / n
    return total


def _compile_stats(fn, args, shardings, mesh) -> dict:
    with mesh_context(mesh):
        lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
        compiled = lowered.compile()
    ca = compiled_cost_analysis(compiled)
    coll = collective_stats(compiled.as_text(), mesh.devices.size)
    out_avals = jax.eval_shape(fn, *args)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_hlo": float(ca.get("bytes accessed", 0.0)),
        "bytes": _io_bytes_per_device(args, shardings, out_avals, mesh),
        "coll": float(coll["collective_bytes_per_device"]),
    }


@dataclasses.dataclass
class Probe:
    name: str
    fn: Callable
    args: tuple
    shardings: tuple
    trips: float
    grad: bool = False  # compile value_and_grad instead of fn


def _scalarize(fn):
    def wrapped(*args):
        out = fn(*args)
        leaves = jax.tree.leaves(out)
        return sum(jnp.sum(x.astype(jnp.float32)) for x in leaves)
    return wrapped


def _abs(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def build_probes(cfg: ArchConfig, cell: ShapeCell, mesh) -> list[Probe]:
    B, S = cell.global_batch, cell.seq_len
    D = cfg.d_model
    bf16 = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    train = cell.kind == "train"
    decode = cell.kind == "decode"
    probes: list[Probe] = []
    pattern = cfg.layer_pattern()
    reps = cfg.n_layers // cfg.period
    n_attn = sum(1 for mx, _ in pattern if mx == "attn") * reps
    n_ssm = sum(1 for mx, _ in pattern if mx == "ssm") * reps
    n_mlp = sum(1 for _, ch in pattern if ch == "mlp") * reps
    n_moe = sum(1 for _, ch in pattern if ch == "moe") * reps
    if cfg.family == "encdec":
        # self+cross projections at S tokens; encoder blocks at enc_seq tokens
        # are folded in as fractional trips of the S-token probes
        frac = cfg.enc_seq / max(S, 1)
        n_attn = cfg.n_layers * 2 + cfg.enc_layers * frac
        n_mlp = cfg.n_layers + cfg.enc_layers * frac

    x_sh = _sh(mesh, (B, S, D), ("batch", "seq", "none"))
    x_abs = _abs((B, S, D), bf16)

    def add(name, fn, params_specs, extra_args, extra_sh, trips, grad,
            argnums=(0, 1)):
        p_abs = abstract_params(params_specs, jnp.float32)
        p_sh = param_shardings(params_specs, mesh)
        f = _scalarize(fn) if grad else fn
        g = jax.value_and_grad(f, argnums=argnums) if grad else fn
        probes.append(Probe(name, g, (p_abs,) + extra_args, (p_sh,) + extra_sh,
                            trips, grad))

    # ---------------------------------------------------------- attention
    if n_attn and not decode:
        specs = {"norm": rmsnorm_spec(D), **attn_specs(cfg)}

        def attn_proj(p, x):
            h = rmsnorm(p["norm"], x, cfg.norm_eps)
            q, k, v = qkv_proj(p, h, cfg, None)
            Bx, Sx = x.shape[:2]
            ctx = jnp.repeat(v, cfg.n_heads // cfg.n_kv_heads, axis=2)
            out = ctx.reshape(Bx, Sx, -1) @ p["wo"].astype(x.dtype)
            return x + out

        add("attn_proj", attn_proj, specs, (x_abs,), (x_sh,), n_attn, train)

        hq, hd = cfg.n_heads, cfg.hd
        # flat-Hq layout: the model constrains q as (B,S,Hq,hd) with Hq on the
        # model axis (divisible for every assigned arch); k/v arrive expanded
        # across GQA groups, as XLA materializes them inside the scan
        qt = _abs((B, hq, Q_CHUNK, hd), bf16)
        kt = _abs((B, hq, hd, K_CHUNK), bf16)
        vt = _abs((B, hq, K_CHUNK, hd), bf16)
        st_m = _abs((B, hq, Q_CHUNK), jnp.float32)
        st_acc = _abs((B, hq, Q_CHUNK, hd), jnp.float32)
        # heads take the model axis when divisible; otherwise the q-chunk
        # dim does (matching XLA's behavior of keeping seq sharding and
        # all-gathering k/v when the head count does not divide)
        tile_sh = (
            _sh(mesh, qt.shape, ("batch", "heads", "tile_q", "none")),
            _sh(mesh, kt.shape, ("batch", "heads", "none", "none")),
            _sh(mesh, vt.shape, ("batch", "heads", "none", "none")),
            _sh(mesh, st_m.shape, ("batch", "heads", "tile_q")),
            _sh(mesh, st_m.shape, ("batch", "heads", "tile_q")),
            _sh(mesh, st_acc.shape, ("batch", "heads", "tile_q", "none")),
        )

        def attn_tile(q, kT, vT, m_run, l_run, acc):
            scale = 1.0 / math.sqrt(hd)
            s = (jnp.einsum("bhqd,bhdk->bhqk", q, kT) * scale).astype(jnp.float32)
            m2 = jnp.maximum(m_run, s.max(axis=-1))
            alpha = jnp.exp(m_run - m2)
            pexp = jnp.exp(s - m2[..., None])
            l2 = l_run * alpha + pexp.sum(axis=-1)
            acc2 = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", pexp.astype(vT.dtype), vT).astype(jnp.float32)
            return m2, l2, acc2

        nq = max(1, math.ceil(S / Q_CHUNK))
        nk = max(1, math.ceil(S / K_CHUNK))
        if cfg.family == "encdec":  # enc (TxT) + dec self (SxS) + cross (SxT)
            T = cfg.enc_seq
            tiles = (cfg.enc_layers * math.ceil(T / Q_CHUNK) * math.ceil(T / K_CHUNK)
                     + cfg.n_layers * nq * nk
                     + cfg.n_layers * nq * math.ceil(T / K_CHUNK))
        else:
            tiles = n_attn * nq * nk
        probes.append(Probe(
            "attn_tile",
            (jax.value_and_grad(_scalarize(attn_tile), argnums=(0, 1, 2))
             if train else attn_tile),
            (qt, kt, vt, st_m, st_m, st_acc), tile_sh, tiles, train))

    if n_attn and decode:
        specs = {"norm": rmsnorm_spec(D), **attn_specs(cfg)}
        Sc = min(S, cfg.window) if cfg.window else S
        cache_abs = {"k": _abs((B, Sc, cfg.n_kv_heads, cfg.hd), bf16),
                     "v": _abs((B, Sc, cfg.n_kv_heads, cfg.hd), bf16)}
        cache_sh = {k: _sh(mesh, v.shape, ("cache_batch", "cache_seq", "heads", "cache_hd"))
                    for k, v in cache_abs.items()}
        x1 = _abs((B, 1, D), bf16)
        x1_sh = _sh(mesh, x1.shape, ("batch", "none", "none"))

        def dec_attn(p, x, cache, pos):
            h = rmsnorm(p["norm"], x, cfg.norm_eps)
            out, nc = attn_decode(p, h, cfg, cache, pos, None, window=cfg.window)
            return x + out, nc

        add("dec_attn", dec_attn, specs,
            (x1, cache_abs, _abs((), jnp.int32)),
            (x1_sh, cache_sh, NamedSharding(mesh, PartitionSpec())),
            n_attn, False)

    # ---------------------------------------------------------------- ssd
    if n_ssm:
        specs = {"block_norm": rmsnorm_spec(D), "ssm": ssm_specs(cfg)}
        if decode:
            di, H, P, N = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            x1 = _abs((B, 1, D), bf16)
            st = {"ssm": _abs((B, H, P, N), jnp.float32),
                  "conv": _abs((B, cfg.ssm_conv - 1, di + 2 * N), bf16)}
            st_sh = {"ssm": _sh(mesh, st["ssm"].shape, ("cache_batch", "ssm_inner", "none", "none")),
                     "conv": _sh(mesh, st["conv"].shape, ("cache_batch", "none", "ssm_inner"))}

            def dec_ssd(p, x, state):
                h = rmsnorm(p["block_norm"], x, cfg.norm_eps)
                out, ns = ssd_decode(p["ssm"], h, cfg, state)
                return x + out, ns

            add("dec_ssd", dec_ssd, specs, (x1, st),
                (_sh(mesh, x1.shape, ("batch", "none", "none")), st_sh),
                n_ssm, False)
        else:
            # (a) per-layer projections: weights stream from HBM once per layer
            di, H, P, N = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state

            def ssm_proj(p, x):
                from ..models.ssm import _causal_conv
                h = rmsnorm(p["block_norm"], x, cfg.norm_eps)
                zxbcdt = h @ p["ssm"]["in_proj"].astype(h.dtype)
                z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
                xbc = _causal_conv(xbc, p["ssm"]["conv_w"].astype(h.dtype),
                                   p["ssm"]["conv_b"].astype(h.dtype))
                xs = xbc[..., :di]
                y = rmsnorm(p["ssm"]["norm"], xs * jax.nn.silu(z), cfg.norm_eps)
                return x + y @ p["ssm"]["out_proj"].astype(h.dtype)

            add("ssm_proj", ssm_proj, specs, (x_abs,), (x_sh,), n_ssm, train)

            # (b) per-chunk inner SSD (dual form + state construction), no
            # weights -- mirrors ssm.ssd_prefill's chunk math exactly
            Q = cfg.ssm_chunk
            xh = _abs((B, Q, H, P), bf16)
            Bh = _abs((B, Q, N), jnp.float32)
            dth = _abs((B, Q, H), jnp.float32)
            inner_sh = (
                _sh(mesh, xh.shape, ("batch", "none", "ssm_inner", "none")),
                _sh(mesh, Bh.shape, ("batch", "none", "none")),
                _sh(mesh, Bh.shape, ("batch", "none", "none")),
                _sh(mesh, dth.shape, ("batch", "none", "ssm_inner")),
            )

            def ssd_inner(xh, Bc, Cc, dt):
                from ..models.ssm import _segsum
                A = -jnp.ones((H,), jnp.float32) * 0.5
                dA = dt * A
                dAc = jnp.cumsum(dA, axis=1)
                L = jnp.exp(_segsum(jnp.moveaxis(dA, -1, 1)))
                scores = jnp.einsum("bin,bjn->bij", Cc, Bc)
                M = scores[:, None] * L
                xdt = xh * dt[..., None].astype(xh.dtype)
                y_diag = jnp.einsum("bhij,bjhp->bihp", M.astype(xh.dtype), xdt)
                decay = jnp.exp(dAc[:, -1:, :] - dAc)
                states = jnp.einsum("bqn,bqh,bqhp->bhpn", Bc,
                                    (dt * decay), xh.astype(jnp.float32))
                y_off = jnp.einsum("bqn,bhpn,bqh->bqhp", Cc, states,
                                   jnp.exp(dAc)).astype(xh.dtype)
                return y_diag + y_off

            probes.append(Probe(
                "ssd_inner",
                (jax.value_and_grad(_scalarize(ssd_inner), argnums=(0, 1, 2, 3))
                 if train else ssd_inner),
                (xh, Bh, Bh, dth), inner_sh,
                n_ssm * math.ceil(S / Q), train))

    # ------------------------------------------------------------- mlp/moe
    tok_shape = (B, 1, D) if decode else (B, S, D)
    tok_abs = _abs(tok_shape, bf16)
    tok_sh = _sh(mesh, tok_shape, ("batch", "seq" if not decode else "none", "none"))
    if n_mlp:
        specs = {"norm": rmsnorm_spec(D), **mlp_specs(cfg)}

        def mlp_block(p, x):
            return x + mlp(p, rmsnorm(p["norm"], x, cfg.norm_eps), cfg)

        add("mlp_block", mlp_block, specs, (tok_abs,), (tok_sh,), n_mlp, train)
    if n_moe:
        specs = {"norm": rmsnorm_spec(D), **moe_specs(cfg)}

        def moe_block(p, x):
            y, aux, _ = moe(p, rmsnorm(p["norm"], x, cfg.norm_eps), cfg)
            return x + y + aux

        add("moe_block", moe_block, specs, (tok_abs,), (tok_sh,), n_moe, train)

    # ------------------------------------------------------- embed + loss
    emb_spec = {"embed": PSpec((cfg.vocab, D), ("vocab", "embed_d"), init="embed")}
    if decode:
        tok = _abs((B, 1), jnp.int32)

        def emb_unemb(p, t):
            x = p["embed"][t].astype(bf16)
            return (x @ p["embed"].T.astype(bf16)).astype(jnp.float32)

        add("embed+unembed", emb_unemb, emb_spec,
            (tok,), (_sh(mesh, tok.shape, ("batch", "none")),), 1, False)
    else:
        c = min(cfg.loss_chunk, S)
        spec = {"unembed": PSpec((D, cfg.vocab), ("embed_d", "vocab"))}
        hc = _abs((B, c, D), bf16)
        lc = _abs((B, c), jnp.int32)

        def loss_chunk(p, h, l):
            logits = (h @ p["unembed"].astype(h.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
            return jnp.sum(lse - gold)

        add("loss_chunk", loss_chunk, spec,
            (hc, lc), (_sh(mesh, hc.shape, ("batch", "none", "none")),
                       _sh(mesh, lc.shape, ("batch", "none"))),
            math.ceil(S / c), train)

        tok = _abs((B, S), jnp.int32)

        def emb(p, t):
            return p["embed"][t].astype(bf16)

        add("embed", emb, emb_spec, (tok,),
            (_sh(mesh, tok.shape, ("batch", "seq")),), 1, train, argnums=(0,))

    # ------------------------------------------------------------ optimizer
    if train:
        probe_shape = (4096, 4096)
        pb = _abs(probe_shape, jnp.float32)
        mb = _abs(probe_shape, jnp.dtype(cfg.optstate_dtype))
        psh = _sh(mesh, probe_shape, ("embed", "ffn"))

        def adam_probe(p, g, m1, v1):
            m2 = 0.9 * m1.astype(jnp.float32) + 0.1 * g
            v2 = 0.95 * v1.astype(jnp.float32) + 0.05 * g * g
            step = m2 / (jnp.sqrt(v2) + 1e-8) + 0.1 * p
            return (p - 1e-3 * step,
                    m2.astype(m1.dtype), v2.astype(v1.dtype))

        trips = cfg.n_params() / float(np.prod(probe_shape))
        probes.append(Probe("adamw", adam_probe, (pb, pb, mb, mb),
                            (psh, psh, psh, psh), trips, False))
    return probes


def analyze_cell(cfg: ArchConfig, cell: ShapeCell, mesh,
                 profile: str | ShardingProfile | None = None) -> dict:
    # all probe construction + lowering happens under one scoped profile, so
    # concurrent analyses with different profiles cannot race
    prof = resolve_profile(profile) if profile is not None else active_profile()
    with sharding_profile(prof):
        return _analyze_cell(cfg, cell, mesh, prof)


def _analyze_cell(cfg: ArchConfig, cell: ShapeCell, mesh,
                  prof: ShardingProfile) -> dict:
    chips = int(mesh.devices.size)
    comps = {}
    totals = {"flops": 0.0, "bytes": 0.0, "bytes_hlo": 0.0, "coll": 0.0}
    for pr in build_probes(cfg, cell, mesh):
        st = _compile_stats(pr.fn, pr.args, pr.shardings, mesh)
        comps[pr.name] = {**st, "trips": pr.trips, "grad": pr.grad}
        for k in totals:
            totals[k] += st[k] * pr.trips
        # remat="full": backward recomputes the forward once more
        if pr.grad and cfg.remat == "full" and pr.name != "loss_chunk":
            # approximation: fwd ~ (vag - fwd) ~ vag/3 for matmul-bound blocks
            totals["flops"] += st["flops"] / 3.0 * pr.trips
            totals["bytes"] += st["bytes"] / 3.0 * pr.trips

    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    n = cfg.n_active_params()
    model_flops = (6.0 if cell.kind == "train" else 2.0) * n * tokens
    hlo_global = totals["flops"] * chips
    terms = {
        "compute_s": totals["flops"] / HW["peak_flops"],
        "memory_s": totals["bytes"] / HW["hbm_bw"],
        "collective_s": totals["coll"] / HW["link_bw"],
    }
    terms_upper = {"memory_hlo_s": totals["bytes_hlo"] / HW["hbm_bw"]}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    return {
        "arch": cfg.name, "cell": cell.name, "chips": chips,
        "profile": prof.name,
        "mesh_shape": dict(mesh_axis_sizes(mesh)),
        "terms": terms, "terms_upper": terms_upper, "dominant": dominant,
        "step_time_lower_bound_s": bound,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_global,
        "useful_flops_ratio": model_flops / max(hlo_global, 1.0),
        "roofline_fraction": (model_flops / HW["peak_flops"] / chips) / max(bound, 1e-30),
        "components": comps,
    }


def main():
    import argparse
    from .. import configs as C
    from .dryrun import make_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=C.ARCHS, required=False)
    ap.add_argument("--cell", choices=list(C.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "moe"])
    ap.add_argument("--smoke", action="store_true",
                    help="small fake fleet, smoke configs + shrunk cells")
    ap.add_argument("--profile", default="baseline", choices=profile_names())
    ap.add_argument("--out", default="experiments/roofline")
    args = ap.parse_args()
    mesh = make_mesh(args.mesh, smoke=args.smoke)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cells = ([(args.arch, args.cell)] if not args.all else
             [(a, c) for a in C.ARCHS for c in C.cells_for(C.get(a))])
    for arch, cell_name in cells:
        cfg = C.get(arch, smoke=args.smoke)
        cell = C.smoke_cell(cell_name) if args.smoke else C.SHAPES[cell_name]
        try:
            rec = analyze_cell(cfg, cell, mesh, profile=args.profile)
        except Exception as e:  # pragma: no cover
            import traceback
            rec = {"arch": arch, "cell": cell_name, "error": traceback.format_exc(limit=15)}
        rec["profile"] = args.profile
        tag = "" if args.profile == "baseline" else f"__{args.profile}"
        (out / f"{arch}__{cell_name}__{args.mesh}{tag}.json").write_text(
            json.dumps(rec, indent=1, default=float))
        if "terms" in rec:
            t = rec["terms"]
            print(f"{arch:16s} {cell_name:12s} comp={t['compute_s']*1e3:9.3f}ms "
                  f"mem={t['memory_s']*1e3:9.3f}ms coll={t['collective_s']*1e3:9.3f}ms "
                  f"dom={rec['dominant'][:-2]:10s} useful={rec['useful_flops_ratio']:.2f} "
                  f"roofline={rec['roofline_fraction']:.2f}", flush=True)
        else:
            print(f"{arch:16s} {cell_name:12s} ERROR", flush=True)


if __name__ == "__main__":
    main()
