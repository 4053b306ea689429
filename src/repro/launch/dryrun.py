"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct stand-ins (no allocation), print
memory/cost analysis and the collective schedule, and emit the roofline
terms (EXPERIMENTS.md §Dry-run / §Roofline read from this output).

Usage (main() gives the CPU backend 512 placeholder devices for the
production meshes, so run it as its own process):
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --json-out out.json
"""

import argparse
import json
import math
import os
import re
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import (activation_constraint, batch_shardings,
                               cache_shardings, opt_state_shardings,
                               param_shardings)
from repro.launch.mesh import make_production_mesh
from repro.models.registry import SHAPES, ModelBundle, get_bundle
from repro.optim import adamw

# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in the compiled HLO
    (per-device program => per-device collective bytes)."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _COLL_RE.search(line.split("(")[0] if "(" in line else line)
        if not m or "=" not in line:
            continue
        # only count op definitions: "%name = <shape(s)> <op>(...)"
        lhs, rhs = line.split("=", 1)
        op_m = _COLL_RE.search(rhs.split("(")[0])
        if not op_m:
            continue
        op = op_m.group(1)
        # result shapes live between '=' and the op name
        result_part = rhs.split(op)[0]
        size = 0.0
        for dt, dims in _SHAPE_RE.findall(result_part):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        out[op] = out.get(op, 0.0) + size
    return out


def sharded_param_bytes(bundle: ModelBundle, mesh) -> float:
    """Analytic per-device parameter bytes under the sharding policy."""
    from repro.distributed.sharding import resolve_pspec
    from repro.models.common import Spec
    total = 0.0
    dtype_bytes = 2 if bundle.cfg.dtype == "bfloat16" else 4
    for s in jax.tree.leaves(bundle.specs(),
                             is_leaf=lambda x: isinstance(x, Spec)):
        spec = resolve_pspec(s.shape, s.axes, mesh)
        denom = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= mesh.shape[a]
        total += math.prod(s.shape) / denom * dtype_bytes
    return total


def active_param_count(bundle: ModelBundle) -> int:
    """Active (per-token) params — MoE counts k/E of expert weights."""
    from repro.models.common import Spec
    cfg = bundle.cfg
    total = 0
    for path, s in jax.tree_util.tree_flatten_with_path(
            bundle.specs(), is_leaf=lambda x: isinstance(x, Spec))[0]:
        n = math.prod(s.shape)
        name = jax.tree_util.keystr(path)
        if "experts" in s.axes and cfg.num_experts:
            n = int(n * cfg.experts_per_token / cfg.num_experts)
        total += n
    return total


# --------------------------------------------------------------- lowering ----
def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               mesh=None, seq_shard: bool = True,
               remat: bool = True) -> Tuple[Any, Dict[str, Any]]:
    bundle = get_bundle(arch)
    shape = SHAPES[shape_name]
    ok, why = bundle.supports(shape)
    if not ok:
        return None, {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    p_shard = param_shardings(bundle, mesh)
    params_abs = bundle.abstract()
    info: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                            "mesh": dict(mesh.shape),
                            "params": bundle.param_count(),
                            "active_params": active_param_count(bundle)}

    with mesh:
        if shape.kind == "train":
            ins = bundle.input_specs(shape)
            b_shard = batch_shardings(bundle, mesh, ins)
            opt_abs = jax.eval_shape(adamw.init, params_abs)
            opt_shard = opt_state_shardings(p_shard, params_abs)
            ocfg = adamw.AdamWConfig()
            constrain = activation_constraint(mesh, seq_shard)

            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(
                    lambda p: bundle.loss(p, batch, constrain))(params)
                new_params, new_opt = adamw.apply(ocfg, grads, opt_state,
                                                  params)
                return new_params, new_opt, loss

            lowered = jax.jit(
                train_step,
                in_shardings=(p_shard, opt_shard, b_shard),
                out_shardings=(p_shard, opt_shard, None),
                donate_argnums=(0, 1),
            ).lower(params_abs, opt_abs, ins)
            tokens = shape.global_batch * shape.seq_len
            info["model_flops"] = 6 * info["active_params"] * tokens

        elif shape.kind == "prefill":
            ins = bundle.input_specs(shape)
            b_shard = batch_shardings(bundle, mesh, ins)
            cache_abs = jax.eval_shape(
                lambda: bundle.init_cache(shape.global_batch, shape.seq_len))
            c_shard = cache_shardings(bundle.cfg, cache_abs, mesh,
                                      long_context=shape.global_batch == 1)

            def prefill_step(params, cache, batch):
                return bundle.prefill(params, batch["tokens"], cache,
                                      batch.get("patch_embeds",
                                                batch.get("frame_embeds")))

            lowered = jax.jit(
                prefill_step,
                in_shardings=(p_shard, c_shard, b_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            ).lower(params_abs, cache_abs, ins)
            tokens = shape.global_batch * shape.seq_len
            info["model_flops"] = 2 * info["active_params"] * tokens

        else:   # decode
            ins = bundle.input_specs(shape)
            cache_abs = jax.eval_shape(
                lambda: bundle.init_cache(shape.global_batch, shape.seq_len))
            c_shard = cache_shardings(bundle.cfg, cache_abs, mesh,
                                      long_context=shape.global_batch == 1)
            tok_shard = batch_shardings(bundle, mesh, ins)["token"]

            def serve_step(params, cache, token):
                return bundle.decode(params, cache, token)

            lowered = jax.jit(
                serve_step,
                in_shardings=(p_shard, c_shard, tok_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            ).lower(params_abs, cache_abs, ins["token"])
            info["model_flops"] = 2 * info["active_params"] * \
                shape.global_batch
    return lowered, info


def analyze(lowered, info: Dict[str, Any]) -> Dict[str, Any]:
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # pre-0.5 jax: one dict per program
        cost = cost[0] if cost else None
    chips = 1
    for v in info["mesh"].values():
        chips *= v
    coll = collective_bytes(compiled.as_text())
    coll_total = sum(coll.values())
    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    bytes_accessed = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    # cost_analysis is per-device for SPMD programs
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_coll = coll_total / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    info.update({
        "chips": chips,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collective_bytes_per_chip": coll_total,
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "roofline_seconds": terms,
        "bottleneck": max(terms, key=terms.get),
        "model_flops_per_chip": info["model_flops"] / chips,
        "useful_flop_fraction": (info["model_flops"] / chips / flops
                                 if flops else 0.0),
    })
    # Analytic model (XLA:CPU cost_analysis counts loop bodies once — see
    # repro/launch/analytic.py; these are the §Roofline primary numbers).
    try:
        from repro.launch import analytic
        from repro.models.registry import SHAPES, get_config
        costs = analytic.cell_costs(get_config(info["arch"]),
                                    SHAPES[info["shape"]], chips)
        a_terms = {
            "compute": costs.flops_per_chip / PEAK_FLOPS,
            "memory": costs.hbm_bytes_per_chip / HBM_BW,
            "collective": costs.coll_bytes_per_chip / ICI_BW,
        }
        info["analytic"] = {
            "flops_per_chip": costs.flops_per_chip,
            "hbm_bytes_per_chip": costs.hbm_bytes_per_chip,
            "coll_bytes_per_chip": costs.coll_bytes_per_chip,
            "roofline_seconds": a_terms,
            "bottleneck": max(a_terms, key=a_terms.get),
            "mfu_bound": (info["model_flops"] / chips / PEAK_FLOPS) /
                         max(a_terms.values()),
        }
    except Exception as e:   # pragma: no cover
        info["analytic_error"] = str(e)
    return info


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             seq_shard: bool = True, verbose: bool = True) -> Dict[str, Any]:
    lowered, info = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               seq_shard=seq_shard)
    if lowered is None:
        if verbose:
            print(f"[skip] {arch} x {shape_name}: {info['skipped']}")
        return info
    info = analyze(lowered, info)
    if verbose:
        t = info["roofline_seconds"]
        print(f"[ok] {arch} x {shape_name} mesh={info['mesh']} "
              f"flops/chip={info['hlo_flops_per_chip']:.3e} "
              f"bytes/chip={info['hlo_bytes_per_chip']:.3e} "
              f"coll/chip={info['collective_bytes_per_chip']:.3e} "
              f"terms(ms)=[c {1e3*t['compute']:.2f} | m {1e3*t['memory']:.2f}"
              f" | x {1e3*t['collective']:.2f}] bound={info['bottleneck']} "
              f"useful={info['useful_flop_fraction']:.3f}")
        print(f"     memory/chip: args={info['memory']['argument_bytes']/1e9:.2f}GB "
              f"temps={info['memory']['temp_bytes']/1e9:.2f}GB "
              f"outputs={info['memory']['output_bytes']/1e9:.2f}GB "
              f"aliased={info['memory']['alias_bytes']/1e9:.2f}GB")
        if "analytic" in info:
            a = info["analytic"]
            t = a["roofline_seconds"]
            print(f"     analytic: flops/chip={a['flops_per_chip']:.3e} "
                  f"terms(ms)=[c {1e3*t['compute']:.2f} | m "
                  f"{1e3*t['memory']:.2f} | x {1e3*t['collective']:.2f}] "
                  f"bound={a['bottleneck']} mfu_bound={a['mfu_bound']:.3f}")
    return info


def main(argv=None):
    # Before the first device query: the production meshes need 512
    # placeholder devices.  Set here, not at import, so a process that
    # only imports this module keeps its own XLA_FLAGS.
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512")))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--json-out", type=str, default=None)
    args = ap.parse_args(argv)

    from repro.configs import ASSIGNED_ARCHS
    cells = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    results = []
    for arch, shape in cells:
        try:
            results.append(run_cell(arch, shape, multi_pod=args.multi_pod,
                                    seq_shard=not args.no_seq_shard))
        except Exception as e:   # a failing cell is a bug — surface it
            print(f"[FAIL] {arch} x {shape}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            results.append({"arch": arch, "shape": shape,
                            "error": f"{type(e).__name__}: {e}"})
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(results, f, indent=1, default=str)
    failed = [r for r in results if "error" in r]
    print(f"\n{len(results) - len(failed)}/{len(results)} cells passed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
