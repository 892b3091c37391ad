import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (assignment deliverable e).

For every (architecture × shape × mesh) cell this lowers + compiles the real
step function (train_step / prefill / decode) against ShapeDtypeStruct inputs
with production shardings, and records:

  prod mode:  memory_analysis (fits-HBM proof, with auto microbatch
              escalation for train cells), compile wall time, and the
              collective-op inventory of the optimized per-device HLO.
  cost mode:  exact FLOPs / bytes / collective-bytes via fully-unrolled scans
              at 2–3 small layer counts, extrapolated linearly in L (exact:
              per-layer HLO is identical; measured that XLA cost_analysis
              counts a while body once regardless of trip count).

Also dry-runs the paper's workload itself: the distributed-PKT support pass
and one peel sub-level on the production mesh (mode=truss).

Results land in artifacts/dryrun/*.json (idempotent; --force re-runs).
"""

import argparse
import dataclasses
import functools
import json
import re
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_production_mesh
from repro.configs import (ARCHS, SHAPES, get_config, input_specs,
                           cell_is_valid)
from repro.models.model import ModelConfig, init_params
from repro.models import sharding as shard_rules
from repro.train.step import TrainState, train_step
from repro.optim.adamw import adamw_init, AdamWConfig
from repro.serve import engine

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")
HBM_BYTES = 16 * 2**30          # v5e
FIT_TARGET = 15.5 * 2**30

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "pred": 1, "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(m) -> float:
    dt, dims = m
    if dt not in _DTYPE_BYTES:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collectives(hlo: str) -> dict:
    """Per-device collective inventory from optimized HLO text.

    bytes convention (ring model, per device):
      all-reduce: 2×result, all-gather/all-to-all/permute: result,
      reduce-scatter: operand (≈ result × group size).
    """
    out: dict[str, dict] = {k: {"count": 0, "bytes": 0.0}
                            for k in _COLL_KINDS}
    for line in hlo.splitlines():
        if "=" not in line:
            continue
        for kind in _COLL_KINDS:
            token = f" {kind}("
            start_tok = f" {kind}-start("
            if token not in line and start_tok not in line:
                continue
            if f"{kind}-done" in line:
                continue
            lhs, _, rhs = line.partition("=")
            lhs_shapes = _SHAPE_RE.findall(lhs.split("=")[0])
            # result shapes appear on the RHS before the op name too; prefer
            # the RHS type annotation (post-'=' up to the op token)
            pre_op = rhs.split(kind)[0]
            res_shapes = _SHAPE_RE.findall(pre_op)
            shapes = res_shapes or lhs_shapes
            res_bytes = sum(_shape_bytes(m) for m in shapes)
            if kind == "reduce-scatter":
                inner = rhs.partition("(")[2]
                op_shapes = _SHAPE_RE.findall(inner.split(")")[0])
                b = sum(_shape_bytes(m) for m in op_shapes) or res_bytes
            elif kind == "all-reduce":
                b = 2 * res_bytes
            else:
                b = res_bytes
            out[kind]["count"] += 1
            out[kind]["bytes"] += b
            break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# ----------------------------------------------------------- cell builder ----

def _sp_spec(mesh_axes):
    dp = tuple(a for a in ("pod", "data") if a in mesh_axes)
    return (dp, "model", None)


def _cast_tree(tree, dtype):
    def cast(x):
        if np.issubdtype(x.dtype, np.floating):
            return jax.ShapeDtypeStruct(x.shape, dtype)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return jax.tree.map(cast, tree)


def build_cell(cfg: ModelConfig, shape: str, mesh, *, microbatches: int = 1,
               donate: bool = True):
    """Returns (jitted fn, example args (SDS), meta) for one cell."""
    axes = mesh.axis_names
    kind = SHAPES[shape][2]
    seq, gbs, _ = SHAPES[shape]
    spec = input_specs(cfg, shape)
    batch_sds = spec["batch"]
    bspec = shard_rules.batch_specs(cfg, batch_sds, axes,
                                    mesh_shape=dict(mesh.shape))
    bsh = {k: NamedSharding(mesh, s) for k, s in bspec.items()}

    if kind == "train":
        cfg = dataclasses.replace(cfg, act_pspec=_sp_spec(axes))
        pshape = jax.eval_shape(functools.partial(init_params, cfg),
                                jax.random.PRNGKey(0))
        pspec = shard_rules.param_specs(cfg, pshape, axes)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                           is_leaf=lambda x: isinstance(x, P))
        oshape = jax.eval_shape(lambda p: adamw_init(p), pshape)
        osh = {"m": psh, "v": psh}
        state_sds = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32),
                               params=pshape, opt=oshape)
        state_sh = TrainState(step=NamedSharding(mesh, P()), params=psh,
                              opt=osh)
        fn = functools.partial(train_step, cfg=cfg, opt_cfg=AdamWConfig(),
                               microbatches=microbatches)
        jfn = jax.jit(fn, in_shardings=(state_sh, bsh),
                      out_shardings=(state_sh, None),
                      donate_argnums=(0,) if donate else ())
        return jfn, (state_sds, batch_sds), {"kind": kind}

    # serving cells: bf16 params, KV/SSM cache
    seq_shard = (shape == "long_500k") and cfg.serve_seq_shard
    if kind == "prefill":
        cfg = dataclasses.replace(cfg, act_pspec=_sp_spec(axes))
    pshape = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.random.PRNGKey(0))
    pshape = _cast_tree(pshape, jnp.bfloat16)
    pspec = shard_rules.param_specs(cfg, pshape, axes,
                                    fsdp_enabled=cfg.serve_fsdp)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                       is_leaf=lambda x: isinstance(x, P))
    cache_sds = spec["cache"]
    csh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        shard_rules.cache_specs(cfg, cache_sds, axes, seq_shard=seq_shard,
                                mesh_shape=dict(mesh.shape)),
        is_leaf=lambda x: isinstance(x, P))

    if kind == "prefill":
        cfg_p = cfg

        def fn(params, batch, cache):
            return engine.prefill(params, cfg_p, batch, cache)

        jfn = jax.jit(fn, in_shardings=(psh, bsh, csh),
                      out_shardings=(None, csh),
                      donate_argnums=(2,) if donate else ())
        return jfn, (pshape, batch_sds, cache_sds), {"kind": kind}

    # decode: single new token
    tok_key = "embeds" if cfg.input_is_embeds else "tokens"
    tok_sds = batch_sds[tok_key]
    tok_sh = bsh[tok_key]
    pos_sds = batch_sds.get("positions")

    def fn(params, tokens, cache, positions=None):
        return engine.decode(params, cfg, tokens, cache, positions=positions)

    if pos_sds is not None:
        jfn = jax.jit(fn, in_shardings=(psh, tok_sh, csh, bsh["positions"]),
                      out_shardings=(None, None, csh),
                      donate_argnums=(2,) if donate else ())
        return jfn, (pshape, tok_sds, cache_sds, pos_sds), {"kind": kind}
    jfn = jax.jit(fn, in_shardings=(psh, tok_sh, csh),
                  out_shardings=(None, None, csh),
                  donate_argnums=(2,) if donate else ())
    return jfn, (pshape, tok_sds, cache_sds), {"kind": kind}


def lower_cell(cfg, shape, mesh, *, microbatches=1, want_hlo=False,
               donate=True):
    jfn, args, meta = build_cell(cfg, shape, mesh, microbatches=microbatches,
                                 donate=donate)
    t0 = time.time()
    with mesh:
        lowered = jfn.lower(*args)
        compiled = lowered.compile()
    dt = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    rec = {
        "compile_s": round(dt, 2),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": coll,
        "microbatches": microbatches,
        "kind": meta["kind"],
    }
    if want_hlo:
        rec["_hlo"] = hlo
    return rec


# ------------------------------------------------------------- cost mode ----

def _cost_layer_counts(cfg: ModelConfig) -> tuple[int, int, int]:
    """(L1, L2, tail_L) with L2-L1 = one period; 0 tail if none."""
    p = cfg.attn_every if (cfg.family == "hybrid" and cfg.attn_every) else 1
    r = cfg.n_layers % p
    return p, 2 * p, (p + r) if r else 0


def cost_cell(cfg: ModelConfig, shape: str, mesh, *,
              microbatches: int = 1) -> dict:
    """Exact extrapolated cost terms for the full-depth model."""
    L = cfg.n_layers
    L1, L2, Lt = _cost_layer_counts(cfg)
    kv_chunk = max(cfg.kv_chunk, 8192)     # fewer unrolled chunks, same math
    base_cfg = dataclasses.replace(cfg, unroll_scans=True, kv_chunk=kv_chunk,
                                   ssm_q_chunk=max(cfg.ssm_q_chunk, 512))

    def run(nl):
        c = dataclasses.replace(base_cfg, n_layers=nl)
        return lower_cell(c, shape, mesh, donate=False,
                          microbatches=microbatches)

    r1 = run(L1)
    r2 = run(L2)
    period = cfg.attn_every if (cfg.family == "hybrid" and cfg.attn_every) else 1
    k = L // period
    rt = run(Lt) if Lt else None

    def extrap(field, sub=None):
        def g(r):
            return r[field] if sub is None else r[field][sub]["bytes"]
        delta = g(r2) - g(r1)
        total = g(r1) + (k - 1) * delta
        if rt is not None:
            total += g(rt) - g(r1)
        return total

    coll = {}
    for kind in _COLL_KINDS:
        coll[kind] = {
            "bytes": extrap("collectives", kind),
            "count_L1": r1["collectives"][kind]["count"],
        }
    coll["total_bytes"] = sum(v["bytes"] for v in coll.values()
                              if isinstance(v, dict))
    return {
        "flops": extrap("flops"),
        "bytes_accessed": extrap("bytes_accessed"),
        "collectives": coll,
        "layer_counts": [L1, L2, Lt],
        "compile_s": r1["compile_s"] + r2["compile_s"]
        + (rt["compile_s"] if rt else 0.0),
        "kind": r1["kind"],
    }


# ------------------------------------------------------------ truss cells ----

def truss_cell(mesh, *, log_m: int = 27, chunk: int = 1 << 14) -> dict:
    """Dry-run the distributed PKT on the production mesh.

    Synthetic sizes: m = 2**log_m edges, wedge tables ~16 entries/edge.
    Lowers (a) the sharded support pass (no loops — exact cost) and (b) the
    full peel loop (compile/memory proof).
    """
    from repro.core.pkt_dist import make_support_dist, make_pkt_dist
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names) + ("model",)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    m = 1 << log_m
    two_m = 2 * m
    tab = 16 * m
    tab = -(-tab // (n_dev * chunk)) * (n_dev * chunk)
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    N = sds((two_m,), i32)
    Eid = sds((two_m,), i32)
    e1 = sds((tab,), i32)
    cs = sds((tab,), i32)
    lo = sds((tab,), i32)
    hi = sds((tab,), i32)

    S0 = sds((m,), i32)
    # per-device (m,) chunk ranges of each table slice, sharded like the rows
    meta = (sds((n_dev * m,), i32), sds((n_dev * m,), i32),
            sds((n_dev * m,), jnp.bool_))

    rec = {}
    sup = make_support_dist(mesh, axes, m=m, iters=20)
    with mesh:
        t0 = time.time()
        c = sup.lower(N, Eid, e1, cs, lo, hi).compile()
        ma = c.memory_analysis()
        ca = c.cost_analysis() or {}
        rec["support"] = {
            "compile_s": round(time.time() - t0, 2),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "arg_bytes": int(ma.argument_size_in_bytes),
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "collectives": parse_collectives(c.as_text()),
        }
        peel = make_pkt_dist(mesh, axes, m=m, chunk=chunk)
        t0 = time.time()
        # the peel table's resolved rows (e1, e2, e3) have the wedge rows'
        # shape and sharding
        c2 = peel.lower(S0, e1, cs, lo, *meta).compile()
        ma2 = c2.memory_analysis()
        rec["peel_loop"] = {
            "compile_s": round(time.time() - t0, 2),
            "temp_bytes": int(ma2.temp_size_in_bytes),
            "arg_bytes": int(ma2.argument_size_in_bytes),
            "collectives_static": parse_collectives(c2.as_text()),
        }
    rec["m"] = m
    rec["table_entries"] = tab
    rec["devices"] = n_dev
    return rec


# ------------------------------------------------------------------ main ----

def run_one(arch: str, shape: str, mesh_kind: str, mode: str,
            force: bool = False) -> dict | None:
    os.makedirs(ART_DIR, exist_ok=True)
    tag = f"{arch}__{shape}__{mesh_kind}__{mode}"
    path = os.path.join(ART_DIR, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    ok, why = cell_is_valid(arch, shape)
    if not ok:
        rec = {"skipped": True, "reason": why}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    cfg = get_config(arch)
    kind = SHAPES[shape][2]
    try:
        if mode == "cost":
            # match the microbatch count the prod pass settled on, so the
            # cost terms describe the configuration that actually fits
            prod = run_one(arch, shape, mesh_kind, "prod", force=False)
            mb = prod.get("microbatches", 1) if prod else 1
            rec = cost_cell(cfg, shape, mesh, microbatches=mb or 1)
        else:
            rec = None
            mbs = [1, 2, 4, 8, 16] if kind == "train" else [1]
            for mb in mbs:
                rec = lower_cell(cfg, shape, mesh, microbatches=mb)
                rec["fits_hbm"] = (rec["temp_bytes"] + rec["arg_bytes"]
                                   <= FIT_TARGET)
                if rec["fits_hbm"]:
                    break
    except Exception as e:  # record failures — they are bugs to fix
        rec = {"error": f"{type(e).__name__}: {e}"}
    rec["arch"] = arch
    rec["shape"] = shape
    rec["mesh"] = mesh_kind
    rec["mode"] = mode
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "pod", "multipod"])
    ap.add_argument("--mode", default=None, choices=[None, "prod", "cost"])
    ap.add_argument("--workload", default="lm", choices=["lm", "truss"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.workload == "truss":
        os.makedirs(ART_DIR, exist_ok=True)
        for mesh_kind in ([args.mesh] if args.mesh else ["pod", "multipod"]):
            path = os.path.join(ART_DIR, f"truss__{mesh_kind}.json")
            if os.path.exists(path) and not args.force:
                print(f"truss {mesh_kind}: cached")
                continue
            mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
            rec = truss_cell(mesh)
            rec["mesh"] = mesh_kind
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"truss {mesh_kind}: support temp "
                  f"{rec['support']['temp_bytes']/2**30:.2f} GiB, peel temp "
                  f"{rec['peel_loop']['temp_bytes']/2**30:.2f} GiB")
        return

    archs = [args.arch] if args.arch else ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["pod", "multipod"]
    modes = [args.mode] if args.mode else ["prod", "cost"]
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                for mode in modes:
                    if mode == "cost" and mesh_kind == "multipod":
                        continue  # roofline table is single-pod
                    t0 = time.time()
                    rec = run_one(arch, shape, mesh_kind, mode,
                                  force=args.force)
                    status = ("SKIP" if rec.get("skipped") else
                              "ERR " if rec.get("error") else "ok  ")
                    extra = ""
                    if not rec.get("skipped") and not rec.get("error"):
                        if mode == "prod":
                            tot = (rec["temp_bytes"] + rec["arg_bytes"]) / 2**30
                            extra = (f"mem {tot:6.2f} GiB mb={rec['microbatches']}"
                                     f" fits={rec.get('fits_hbm')}")
                        else:
                            extra = (f"flops {rec['flops']:.3e} coll "
                                     f"{rec['collectives']['total_bytes']:.3e}B")
                    print(f"{arch:18s} {shape:12s} {mesh_kind:8s} {mode:4s} "
                          f"{status} {time.time()-t0:6.1f}s  {extra}",
                          flush=True)
                    if rec.get("error"):
                        print("    ", rec["error"][:300], flush=True)


if __name__ == "__main__":
    main()
