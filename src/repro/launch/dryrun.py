import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the REAL step function (train_step for ``train_*``,
prefill/decode for serving shapes) against ShapeDtypeStruct inputs on the
production mesh, compiles it, and records:

- ``memory_analysis``  (bytes per device — proves it fits),
- ``cost_analysis``    (HLO FLOPs / bytes for §Roofline),
- collective bytes parsed from the partitioned HLO (all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute),

writing one JSON record per cell to ``--out`` (default
``results/dryrun.jsonl``).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import functools
import json
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.shapes import ShapeSpec, get_shape, shapes_for
from repro.kernels import ops as KOPS

# Lower with the flash-structured attention reference so the compiled
# FLOP/byte profile matches the TPU Pallas kernels (no S² score buffers).
KOPS.set_default_impl("flash_structured")
from repro.distributed import hlo_analysis, hlo_parser
from repro.distributed import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.train import optimizer as O
from repro.train import trainer as TR


def build_lowerable(cfg, shape: ShapeSpec, mesh, *,
                    microbatches: int = 1, remat: bool = True,
                    remat_policy: str = "nothing", ce_chunks: int = 8,
                    param_spec_fn=None, cache_spec_fn=None,
                    batch_spec_fn=None, sharding_overrides=None):
    """Returns (jitted_fn, arg_specs) ready for .lower(*arg_specs).

    The ``*_spec_fn`` hooks post-process the default PartitionSpec trees —
    the §Perf iteration harness uses them to trial alternative shardings
    without touching the rule module."""
    p_shape = SP.params_shape(cfg)
    p_specs = SH.param_specs(cfg, mesh, p_shape)
    if sharding_overrides:
        p_specs = sharding_overrides(p_specs)
    if param_spec_fn:
        p_specs = param_spec_fn(cfg, mesh, p_shape, p_specs)
    ins = SP.input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = O.OptConfig()
        train_cfg = TR.TrainConfig(microbatches=microbatches, remat=remat,
                                   remat_policy=remat_policy,
                                   ce_chunks=ce_chunks)
        step = TR.make_train_step(cfg, opt_cfg, train_cfg)
        opt_shape = jax.eval_shape(O.init_opt_state, p_shape)
        z_specs = SH.zero1_specs(cfg, mesh, p_shape, p_specs)
        o_specs = {"m": z_specs, "v": z_specs,
                   "step": jax.sharding.PartitionSpec()}
        b_specs = SH.batch_specs(cfg, mesh, shape, ins["batch"])
        if batch_spec_fn:
            b_specs = batch_spec_fn(cfg, mesh, shape, b_specs)
        fn = jax.jit(step,
                     in_shardings=(p_specs, o_specs, b_specs),
                     out_shardings=(p_specs, o_specs, None),
                     donate_argnums=(0, 1))
        return fn, (p_shape, opt_shape, ins["batch"])

    if shape.kind == "prefill":
        def prefill_fn(params, inputs):
            logits, cache, idx = T.prefill(params, cfg, inputs,
                                           max_len=shape.seq_len)
            return logits, cache

        c_shape = SP.cache_shape(cfg, shape.global_batch, shape.seq_len)
        c_specs = SH.cache_specs(cfg, mesh, shape, c_shape)
        if cache_spec_fn:
            c_specs = cache_spec_fn(cfg, mesh, shape, c_specs)
        b_specs = SH.batch_specs(cfg, mesh, shape, ins["inputs"])
        if batch_spec_fn:
            b_specs = batch_spec_fn(cfg, mesh, shape, b_specs)
        fn = jax.jit(prefill_fn,
                     in_shardings=(p_specs, b_specs),
                     out_shardings=(None, c_specs))
        return fn, (p_shape, ins["inputs"])

    if shape.kind == "decode":
        def decode_fn(params, cache, inputs, index):
            return T.decode_step(params, cfg, cache, inputs, index)

        c_specs = SH.cache_specs(cfg, mesh, shape, ins["cache"])
        if cache_spec_fn:
            c_specs = cache_spec_fn(cfg, mesh, shape, c_specs)
        b_specs = SH.batch_specs(cfg, mesh, shape, ins["inputs"])
        if batch_spec_fn:
            b_specs = batch_spec_fn(cfg, mesh, shape, b_specs)
        fn = jax.jit(decode_fn,
                     in_shardings=(p_specs, c_specs, b_specs,
                                   jax.sharding.PartitionSpec()),
                     out_shardings=(None, c_specs),
                     donate_argnums=(1,))
        return fn, (p_shape, ins["cache"], ins["inputs"], ins["index"])

    raise ValueError(shape.kind)


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             microbatches: int = 1, remat: bool = True,
             keep_text: bool = False, **variant) -> Dict[str, Any]:
    cfg = configs.get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.size),
    }
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, arg_specs = build_lowerable(cfg, shape, mesh,
                                        microbatches=microbatches,
                                        remat=remat, **variant)
        lowered = fn.lower(*arg_specs)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        rec["cost"] = {"error": str(e)}

    try:
        from repro.distributed.memory_model import analytic_memory
        rec["analytic_memory"] = {
            k: (round(v, 1) if isinstance(v, float) else v)
            for k, v in analytic_memory(cfg, shape, mesh).items()}
    except Exception as e:  # pragma: no cover
        rec["analytic_memory"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["analysis"] = hlo_parser.analyze(hlo)
    rec["hlo_stats"] = hlo_analysis.op_histogram(hlo)
    # persist the partitioned module so §Perf iterations can re-analyse
    # without recompiling
    import gzip
    os.makedirs("results/hlo", exist_ok=True)
    hlo_path = (f"results/hlo/{arch.replace('/', '_')}_{shape_name}_"
                f"{rec['mesh']}.hlo.gz")
    with gzip.open(hlo_path, "wt") as f:
        f.write(hlo)
    rec["hlo_path"] = hlo_path
    if keep_text:
        rec["hlo_text"] = hlo
    return rec


def iter_cells(mesh_mode: str):
    for arch in configs.ASSIGNED:
        cfg = configs.get_config(arch)
        for shape in shapes_for(cfg):
            if mesh_mode in ("single", "both"):
                yield arch, shape.name, False
            if mesh_mode in ("multi", "both"):
                yield arch, shape.name, True


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args(argv)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if "error" not in r:
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    if args.all:
        cells = list(iter_cells(args.mesh))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, m)
                 for m in ([False] if args.mesh == "single" else
                           [True] if args.mesh == "multi" else [False, True])]

    n_fail = 0
    with open(args.out, "a") as f:
        for arch, shape, multi in cells:
            mesh_name = "2x16x16" if multi else "16x16"
            if (arch, shape, mesh_name) in done:
                print(f"[skip] {arch} {shape} {mesh_name}", flush=True)
                continue
            print(f"[cell] {arch} {shape} {mesh_name} ...", flush=True)
            try:
                rec = run_cell(arch, shape, multi,
                               microbatches=args.microbatches)
                print(f"   ok: lower {rec['lower_s']}s compile "
                      f"{rec['compile_s']}s flops={rec['cost'].get('flops')}",
                      flush=True)
            except Exception as e:
                n_fail += 1
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                print(f"   FAIL: {type(e).__name__}: {e}", flush=True)
            f.write(json.dumps(rec) + "\n")
            f.flush()
    print(f"done; {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
