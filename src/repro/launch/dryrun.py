import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
# The dry-run (and only the dry-run) fabricates 512 host devices so
# jax.make_mesh can build the production meshes; smoke tests and benches
# never import this module and see 1 device.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
#       --shape train_4k --mesh pod
#   PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.jsonl
#
# Each cell: jit(step).lower(**ShapeDtypeStructs) -> .compile() ->
# memory_analysis() + cost/collective roofline (launch/roofline.py).

if os.environ.get("REPRO_DRYRUN_DEVICES"):  # tests use a small device count
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

import argparse
import functools
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.matador_tm import TM_CONFIGS
from repro.launch import roofline, specs
from repro.launch.mesh import make_production_mesh
from repro.models import sharding as shd
from repro.models import steps, transformer
from repro.optim import adamw


def _mesh(name: str):
    if name == "multipod":
        return make_production_mesh(multi_pod=True)
    if name == "pod":
        return make_production_mesh(multi_pod=False)
    # "DxM" shorthand or "model=N" / "data=D,model=M" axis specs
    from repro.launch.mesh import parse_mesh_spec

    return parse_mesh_spec(name)


def _named(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(arch: str, shape_name: str, mesh, *, smoke: bool = False):
    """Returns (lowered, model_flops_global). Raises on inapplicable cells."""
    if arch.startswith("tm-"):
        return _lower_tm_cell(arch, shape_name, mesh)

    if smoke:  # reduced config + shapes (subprocess sharding tests)
        from repro.configs import get_smoke_config
        import dataclasses as _dc

        cfg = get_smoke_config(arch)
        sp = specs.SHAPES[shape_name]
        sp = _dc.replace(
            sp, seq_len=min(sp.seq_len, 128), global_batch=min(sp.global_batch, 16)
        )
        specs.SHAPES[shape_name + "|smoke"] = sp
        shape_name = shape_name + "|smoke"
    else:
        cfg = get_config(arch)
        sp = specs.SHAPES[shape_name]
    if not specs.cell_is_runnable(cfg, shape_name):
        raise SkipCell(
            f"{arch} is full-attention; long_500k requires sub-quadratic "
            "attention (skip noted in DESIGN.md §7)"
        )
    if getattr(sp, "layout", "tp") == "dp" and cfg.param_count() >= 1e10:
        raise SkipCell(
            "pure-DP layout is for <10B-param archs (weights are gathered "
            "per use; large models need TP/EP)"
        )
    batch = specs.input_specs(cfg, shape_name)
    p_struct = specs.params_struct(cfg)
    mf = roofline.model_flops(cfg, sp.kind, sp.global_batch, sp.seq_len)

    if sp.kind == "train":
        pure_dp = getattr(sp, "layout", "tp") == "dp"
        p_specs = shd.param_specs(cfg, p_struct, mesh, train=True, pure_dp=pure_dp)
        o_struct = jax.eval_shape(adamw.adamw_init, p_struct)
        o_specs = adamw.OptState(m=p_specs, v=p_specs, step=P())
        b_specs = shd.batch_specs(cfg, batch, mesh, pure_dp=pure_dp)
        # 200B+ models need gradient accumulation to fit activations in HBM
        n_micro = 4 if cfg.param_count() > 5e10 else 1
        step = steps.make_train_step(
            cfg, mesh, microbatches=n_micro, pure_dp=pure_dp
        )
        jitted = jax.jit(
            step,
            in_shardings=(
                _named(p_specs, mesh), _named(o_specs, mesh), _named(b_specs, mesh),
            ),
            out_shardings=(_named(p_specs, mesh), _named(o_specs, mesh), None),
            donate_argnums=(0, 1),
        )
        return jitted.lower(p_struct, o_struct, batch), mf

    p_specs = shd.param_specs(cfg, p_struct, mesh, train=False)
    c_struct = specs.cache_specs_struct(cfg, shape_name)
    c_specs = shd.cache_specs(cfg, c_struct, mesh)
    if sp.kind == "prefill":
        b_specs = shd.batch_specs(cfg, batch, mesh)
        step = steps.make_prefill_step(cfg, mesh)
        jitted = jax.jit(
            step,
            in_shardings=(
                _named(p_specs, mesh), _named(b_specs, mesh), _named(c_specs, mesh),
            ),
            out_shardings=(None, _named(c_specs, mesh)),
            donate_argnums=(2,),
        )
        return jitted.lower(p_struct, batch, c_struct), mf

    # decode
    b_specs = shd.batch_specs(cfg, batch, mesh)
    step = steps.make_decode_step(cfg, mesh)
    jitted = jax.jit(
        step,
        in_shardings=(
            _named(p_specs, mesh), _named(c_specs, mesh), _named(b_specs, mesh), None,
        ),
        out_shardings=(None, _named(c_specs, mesh)),
        donate_argnums=(1,),
    )
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return jitted.lower(p_struct, c_struct, batch, pos), mf


class SkipCell(Exception):
    pass


# ---------------------------------------------------------------------------
# TM (the paper's own model) cells
# ---------------------------------------------------------------------------

TM_SHAPES = {
    "tm_train": dict(batch=8192, kind="train"),
    "tm_train_fused": dict(batch=8192, kind="train", engine="kernel"),
    "tm_infer": dict(batch=65536, kind="infer"),
    "tm_infer_fused": dict(batch=65536, kind="infer", engine="kernel"),
}


def _lower_tm_cell(arch: str, shape_name: str, mesh):
    from repro.core import packetizer, sharding as tm_shd, tm

    config = TM_CONFIGS[arch]
    spec = TM_SHAPES[shape_name]
    B = spec["batch"]
    C, L = config.n_clauses_total, config.n_literals
    W = packetizer.n_words(L)
    engine = spec.get("engine", "gspmd")
    # the *_fused cells lower the clause-sharded shard_map schedule with the
    # fused Pallas kernels as the per-shard body (interpret mode off-TPU)
    kernel_kw = dict(use_kernel=True) if engine == "kernel" else {}

    if spec["kind"] == "train":
        fn = tm_shd.sharded_train_step_fn(
            config, mesh, engine=engine, **kernel_kw)
        args = (
            jax.ShapeDtypeStruct((C, L), jnp.int8),
            jax.ShapeDtypeStruct((B, config.n_features), jnp.uint8),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.uint32),
        )
        # TM "model flops" analog: one bit-op per (sample, clause, literal)
        # pass for eval + feedback; report as equivalent MACs/2.
        mf = 2.0 * B * C * L
        return fn.lower(*args), mf

    fn = tm_shd.sharded_predict_fn(config, mesh, **kernel_kw)
    args = (
        jax.ShapeDtypeStruct((C, W), jnp.uint32),
        jax.ShapeDtypeStruct((C, config.n_classes), jnp.int32),
        jax.ShapeDtypeStruct((C,), jnp.uint8),
        jax.ShapeDtypeStruct((B, W), jnp.uint32),
    )
    mf = 2.0 * B * C * W
    return fn.lower(*args), mf


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str, *, smoke: bool = False) -> dict:
    mesh = _mesh(mesh_name)
    t0 = time.time()
    lowered, mf = lower_cell(arch, shape_name, mesh, smoke=smoke)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    report = roofline.build_report(
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name,
        n_devices=mesh.devices.size,
        hlo_text=compiled.as_text(),
        model_flops_global=mf,
        mem_analysis=mem,
        compile_seconds=t_compile,
    )
    rec = report.as_dict()
    rec["lower_seconds"] = t_lower
    rec["xla_cost_flops"] = float(
        (compiled.cost_analysis() or {}).get("flops", 0.0))
    return rec


def all_cells():
    for arch in ARCH_IDS:
        for shape_name in specs.SHAPES:
            yield arch, shape_name
    for arch in ("tm-mnist", "tm-edge-xl"):
        for shape_name in TM_SHAPES:
            yield arch, shape_name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", help="pod | multipod | DxM")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--meshes", default="pod,multipod")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs/shapes (sharding tests)")
    args = ap.parse_args(argv)

    cells = (
        [(a, s, m) for (a, s) in all_cells() for m in args.meshes.split(",")]
        if args.all
        else [(args.arch, args.shape, args.mesh)]
    )

    failures = 0
    for arch, shape_name, mesh_name in cells:
        try:
            rec = run_cell(arch, shape_name, mesh_name, smoke=args.smoke)
            status = "ok"
        except SkipCell as e:
            rec = {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "skipped": str(e),
            }
            status = "skip"
        except Exception as e:  # noqa: BLE001 — report and continue the sweep
            rec = {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc(limit=20),
            }
            status = "FAIL"
            failures += 1
        rec["status"] = status
        line = json.dumps(rec)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        brief = {
            k: rec.get(k)
            for k in (
                "arch", "shape", "mesh", "status", "bottleneck", "t_comp",
                "t_mem", "t_coll", "useful_flops_ratio", "temp_bytes",
                "compile_seconds", "error", "skipped",
            )
            if k in rec
        }
        print(json.dumps(brief), flush=True)
        if status == "ok":
            # the two artifacts the assignment names explicitly:
            print(f"  memory_analysis: args={rec['arg_bytes']:.3e} "
                  f"temp={rec['temp_bytes']:.3e} out={rec['output_bytes']:.3e} "
                  f"bytes/device", flush=True)
            print(f"  cost_analysis:   xla_flops={rec['xla_cost_flops']:.3e} "
                  f"(per-device, body-once) hlo_flops={rec['flops']:.3e} "
                  f"(trip-resolved)", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
