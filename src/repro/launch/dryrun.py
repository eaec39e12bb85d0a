import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices build the production meshes
((16,16) single pod, (2,16,16) = 2 pods), `jax.jit(step).lower(**specs)`
+ `.compile()` must succeed for every cell, and the compiled artifact
yields the roofline terms (cost_analysis + HLO collective parse).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun
"""

import argparse
import json
import re
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.distributed import sharding as shd
from repro.launch.mesh import dp_axes, make_production_mesh, mesh_chips, \
    tp_axis
from repro.models.config import SHAPES, runnable_shapes
from repro.models.model import Model
from repro.train import optim

# TPU v5e targets (per chip / per link)
HW = dict(peak_flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str, loop_trip: int = 1):
    """Per-device bytes moved by collectives, from the optimized HLO.

    Convention: the RESULT shape of each collective op (ring traffic for
    all-gather ~ result; all-reduce ~ 2x operand in a ring, we report 1x and
    note the factor in EXPERIMENTS.md).

    `loop_trip`: HLO cost/text counts a while-loop body ONCE; collectives
    found inside non-ENTRY computations (the scan-over-layers body) are
    multiplied by the layer count.  `total_raw` keeps the uncorrected sum.
    """
    out = {}
    raw_total = 0
    entry = True
    for line in hlo_text.splitlines():
        # computation definitions start at column 0: "ENTRY %main (...) {"
        # or "%region_3.88 (...) -> ... {"; body lines are indented
        if line.startswith("ENTRY"):
            entry = True
        elif line.startswith("%") and line.rstrip().endswith("{"):
            entry = False
        m = _COLL_RE.search(line)
        if m:
            ty, op = m.group(1), m.group(2)
            b = shape_bytes(ty)
            raw_total += b
            mult = 1 if entry else loop_trip
            out[op] = out.get(op, 0) + b * mult
    out["total"] = sum(out.values())
    out["total_raw"] = raw_total
    return out


def _train_step_fn(model: Model):
    ocfg = optim.AdamWConfig()

    def step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True)(params)
        params, opt_state, _ = optim.apply_updates(grads=grads,
                                                   params=params,
                                                   state=opt_state, cfg=ocfg)
        return params, opt_state, loss

    return step


def build_cell(model: Model, shape_name: str, mesh):
    """-> (fn, args_specs, in_shardings, out_shardings)."""
    cfg = model.cfg
    dp = dp_axes(mesh)
    tp = tp_axis(mesh)
    kind = SHAPES[shape_name]["kind"]
    S = SHAPES[shape_name]["seq_len"]

    params_s = model.shape_params()
    param_ns = shd.named_shardings(params_s, cfg, mesh, dp, tp)

    if kind == "train":
        batch_s = model.input_specs(shape_name)
        batch_ns = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                shd.batch_specs(batch_s, mesh, dp),
                                is_leaf=lambda x: isinstance(x, P))
        opt_s = jax.eval_shape(optim.init_state, params_s)
        opt_ns = optim.AdamState(
            step=NamedSharding(mesh, P()),
            m=jax.tree.map(lambda s: s, param_ns),
            v=jax.tree.map(lambda s: s, param_ns))
        fn = _train_step_fn(model)
        return (fn, (params_s, opt_s, batch_s),
                (param_ns, opt_ns, batch_ns),
                (param_ns, opt_ns, NamedSharding(mesh, P())))

    if kind == "prefill":
        batch_s = model.input_specs(shape_name)
        batch_ns = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                shd.batch_specs(batch_s, mesh, dp),
                                is_leaf=lambda x: isinstance(x, P))

        def fn(params, batch):
            return model.prefill(params, batch, s_max=S)

        cache_s = jax.eval_shape(fn, params_s, batch_s)[1]
        cache_ns = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            shd.cache_specs(cache_s, mesh, dp, tp),
            is_leaf=lambda x: isinstance(x, P))
        out_ns = (NamedSharding(mesh, P()), cache_ns,
                  NamedSharding(mesh, P()))
        return fn, (params_s, batch_s), (param_ns, batch_ns), out_ns

    # decode: one new token against a seq_len-deep cache
    specs = model.input_specs(shape_name)
    cache_s = specs["cache"]
    cache_ns = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            shd.cache_specs(cache_s, mesh, dp, tp),
                            is_leaf=lambda x: isinstance(x, P))
    tok_s = {k: v for k, v in specs.items() if k != "cache"}
    tok_ns = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          shd.batch_specs(tok_s, mesh, dp),
                          is_leaf=lambda x: isinstance(x, P))

    def fn(params, cache, toks):
        return model.decode(params, cache, token=toks.get("token"),
                            pos=toks["pos"], embed=toks.get("embed"))

    out_ns = (NamedSharding(mesh, P()), cache_ns)
    return (fn, (params_s, cache_s, tok_s),
            (param_ns, cache_ns, tok_ns), out_ns)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir=None,
             donate: bool = True):
    cfg = get_config(arch)
    model = Model(cfg)
    if shape_name not in runnable_shapes(cfg):
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                   status="SKIP", reason="full attention at 500k "
                   "(DESIGN.md Sec. 5)")
        _emit(rec, out_dir)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    dp = dp_axes(mesh)
    shd.activate(mesh, dp, tp_axis(mesh),
                 shard_seq=(cfg.name == "qwen1.5-110b"))
    t0 = time.time()
    try:
        fn, args, in_ns, out_ns = build_cell(model, shape_name, mesh)
        kind = SHAPES[shape_name]["kind"]
        if not donate:
            dn = ()
        elif kind == "train":
            dn = (0, 1)          # params + optimizer state update in place
        elif kind == "decode":
            dn = (1,)            # KV/SSM cache updates in place
        else:
            dn = ()
        # One-shot lower/compile for cost analysis -- never re-invoked.
        # repro-lint: disable=jit-cache-hygiene
        jitted = jax.jit(fn, in_shardings=in_ns, out_shardings=out_ns,
                         donate_argnums=dn)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        from repro.launch.cost_model import hlo_cost
        cost = hlo_cost(compiled)
        mem = compiled.memory_analysis()
        colls = collective_bytes(compiled.as_text(),
                                 loop_trip=cfg.n_layers)
        chips = mesh_chips(mesh)

        # raw HLO numbers (NB: XLA counts while-loop bodies ONCE, so raw
        # flops/bytes under-report scanned layers ~L-fold; see cost_model)
        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        coll_dev = float(colls.get("total", 0))
        terms = dict(
            compute_s=flops_dev / HW["peak_flops_bf16"],
            memory_s=bytes_dev / HW["hbm_bw"],
            collective_s=coll_dev / HW["ici_bw"],
        )

        # analytical totals (validated vs unrolled HLO in
        # tests/test_cost_model.py) -- the numbers SS Roofline reasons from
        from repro.launch import cost_model
        from repro.launch.mesh import dp_axes as _dpa
        dp_size = 1
        for a in _dpa(mesh):
            dp_size *= mesh.shape[a]
        ana = cost_model.cell_cost(cfg, shape_name, chips=chips,
                                   dp=dp_size, tp=mesh.shape["model"])
        ana_flops_dev = ana.flops_total / chips
        ana_bytes_dev = ana.bytes_total / chips
        ana_terms = dict(
            compute_s=ana_flops_dev / HW["peak_flops_bf16"],
            memory_s=ana_bytes_dev / HW["hbm_bw"],
            collective_s=coll_dev / HW["ici_bw"],
        )
        dominant = max(ana_terms, key=ana_terms.get)
        n_params = cfg.param_count()
        n_active = cfg.active_param_count()
        tokens = (SHAPES[shape_name]["global_batch"]
                  * (SHAPES[shape_name]["seq_len"]
                     if SHAPES[shape_name]["kind"] != "decode" else 1))
        mf = (6 * n_active * tokens
              * (1 if SHAPES[shape_name]["kind"] == "train" else 1 / 3))
        rec = dict(
            arch=arch, shape=shape_name, mesh=mesh_kind, status="OK",
            chips=chips,
            flops_per_device=flops_dev, bytes_per_device=bytes_dev,
            collective_bytes_per_device=coll_dev,
            collectives=colls, roofline_hlo_raw=terms,
            analytic_flops_per_device=ana_flops_dev,
            analytic_bytes_per_device=ana_bytes_dev,
            roofline=ana_terms, dominant=dominant,
            model_flops=mf,
            useful_ratio=(mf / ana.flops_total
                          if ana.flops_total else None),
            memory=dict(
                argument=mem.argument_size_in_bytes,
                output=mem.output_size_in_bytes,
                temp=mem.temp_size_in_bytes,
                peak=getattr(mem, "peak_memory_in_bytes", None),
            ) if mem else None,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            n_params=n_params, n_active_params=n_active,
        )
    except Exception as e:  # noqa: BLE001 -- dry-run failures are findings
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                   status="FAIL", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    finally:
        shd.deactivate()
    _emit(rec, out_dir)
    return rec


def _emit(rec, out_dir):
    tag = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
    if rec["status"] == "OK":
        t = rec["roofline"]
        print(f"[{rec['status']}] {tag}: dominant={rec['dominant']} "
              f"compute={t['compute_s']:.3e}s memory={t['memory_s']:.3e}s "
              f"collective={t['collective_s']:.3e}s "
              f"peak/dev={_fmt_b(rec['memory']['peak'] if rec['memory'] else None)} "
              f"(lower {rec.get('lower_s', '-')}s "
              f"compile {rec.get('compile_s', '-')}s)")
    else:
        print(f"[{rec['status']}] {tag}: "
              f"{rec.get('reason', rec.get('error', ''))[:300]}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        safe = tag.replace("/", "_").replace(".", "_")
        with open(os.path.join(out_dir, safe + ".json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)


def _fmt_b(n):
    if n is None:
        return "?"
    for u in ("B", "KB", "MB", "GB", "TB"):
        if n < 1024:
            return f"{n:.1f}{u}"
        n /= 1024
    return f"{n:.1f}PB"


def run_compression_dryrun(mesh_kind: str, out_dir=None,
                           n_elems: int = 2_000_000_000):
    """Paper-representative cell: NUMARCK encode stage over the full mesh.

    n defaults to 2e9 elements (8 GB f32 variable, the int32-offset
    envelope; Stir-2/3 scale linearly in per-shard work).
    """
    from repro.core.types import NumarckParams
    from repro.distributed import pipeline as pl
    from jax import shard_map

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    axis_names = mesh.axis_names
    P_ = mesh_chips(mesh)
    params = NumarckParams(error_bound=1e-3, max_bins=1 << 16)
    t0 = time.time()
    try:
        # analyze stage: one-shot lower/compile for cost analysis.
        # repro-lint: disable=jit-cache-hygiene
        analyze = shard_map(
            partial(pl._analyze_shard, max_bins=params.max_bins,
                    b_max=params.b_max, elem_bytes=4, n_total=n_elems,
                    axis=axis_names[0], use_pallas=False),
            mesh=mesh, in_specs=(P(axis_names[0]), P(axis_names[0]), P()),
            out_specs=(P(axis_names[0]),) * 6, check_vma=False)
        # NB: shard over the first axis only for the collective pattern the
        # paper has (one flat allreduce); remaining axes replicate.
        n_shards = mesh.shape[axis_names[0]]
        ln_a = n_elems // n_shards
        sds = jax.ShapeDtypeStruct((n_shards * ln_a,), jnp.float32)
        # repro-lint: disable=jit-cache-hygiene
        low = jax.jit(analyze).lower(sds, sds, jnp.float32(1e-3))
        comp = low.compile()
        from repro.launch.cost_model import hlo_cost
        cost = hlo_cost(comp)
        colls = collective_bytes(comp.as_text())
        mem = comp.memory_analysis()
        rec = dict(arch="numarck-pipeline", shape=f"n{n_elems:.0e}",
                   mesh=mesh_kind, status="OK", chips=P_,
                   flops_per_device=float(cost.get("flops", 0)),
                   bytes_per_device=float(cost.get("bytes accessed", 0)),
                   collective_bytes_per_device=colls.get("total", 0),
                   collectives=colls,
                   roofline=dict(
                       compute_s=float(cost.get("flops", 0))
                       / HW["peak_flops_bf16"],
                       memory_s=float(cost.get("bytes accessed", 0))
                       / HW["hbm_bw"],
                       collective_s=colls.get("total", 0) / HW["ici_bw"]),
                   memory=dict(
                       argument=mem.argument_size_in_bytes,
                       output=mem.output_size_in_bytes,
                       temp=mem.temp_size_in_bytes,
                       peak=getattr(mem, "peak_memory_in_bytes", None),
                   ) if mem else None,
                   compile_s=round(time.time() - t0, 2))
        rec["dominant"] = max(rec["roofline"], key=rec["roofline"].get)
    except Exception as e:  # noqa: BLE001
        rec = dict(arch="numarck-pipeline", shape=f"n{n_elems:.0e}",
                   mesh=mesh_kind, status="FAIL",
                   error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    _emit(rec, out_dir)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single,multi")
    ap.add_argument("--out", default=None)
    ap.add_argument("--compression", action="store_true",
                    help="also dry-run the NUMARCK pipeline cell")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")

    results = []
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                results.append(run_cell(arch, shape, mesh_kind, args.out))
        if args.compression:
            results.append(run_compression_dryrun(mesh_kind, args.out))

    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    n_fail = sum(r["status"] == "FAIL" for r in results)
    print(f"\n== dry-run: {n_ok} OK, {n_skip} skipped (documented), "
          f"{n_fail} FAILED ==")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
