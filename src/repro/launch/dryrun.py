import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

# Multi-pod dry-run: lower + compile every (architecture x input shape)
# on the production meshes, print memory/cost analyses, and dump roofline
# inputs (deliverables e and g).
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
#       --mesh single --out experiments/dryrun
#
# Failures (sharding mismatch, OOM at compile, unsupported collective) are
# bugs in the system — the run exits nonzero if any pair fails.
import argparse
import functools
import json
import time
import traceback
from typing import Dict

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, InputShape, MeshConfig, ModelConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.federated.mesh_rounds import build_round_step, replicate_clients
from repro.launch.mesh import make_production_mesh
from repro.launch.specs_inputs import (
    adapt_config,
    decode_input_specs,
    prefill_input_specs,
    train_input_specs,
)
from repro.models import transformer as tfm
from repro.optim import sgd
from repro.sharding.specs import cache_specs, param_specs
from repro.utils import flops as fl
from repro.utils.hlo import collective_summary, parse_collectives

DEFAULT_V = 4  # baseline local rounds per sync (DEFL hillclimbs this)


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        functools.partial(tfm.init_params, cfg), jax.random.PRNGKey(0))


def _batch_spec(tree, leading_axes):
    ax = leading_axes if len(leading_axes) > 1 else leading_axes[0]
    return jax.tree.map(
        lambda x: P(ax, *([None] * (x.ndim - 1))), tree)


def lower_train(cfg: ModelConfig, shape: InputShape, mesh, mesh_cfg: MeshConfig,
                V: int = DEFAULT_V, aggregation: str = "allreduce",
                donate: bool = True, impl: str = "xla"):
    loss = functools.partial(tfm.loss_fn, cfg, impl=impl)
    opt = sgd(0.01)
    C = mesh_cfg.n_clients
    params_abs = jax.eval_shape(
        lambda p: replicate_clients(p, C), _abstract_params(cfg))
    pspecs = param_specs(params_abs, mesh, client_axes=mesh_cfg.client_axes)
    step = build_round_step(lambda p, b: loss(p, b), opt, V, aggregation,
                            mesh=mesh, param_specs_tree=pspecs,
                            client_axes=mesh_cfg.client_axes)
    inputs = train_input_specs(cfg, shape, mesh_cfg, V)
    bspecs = _batch_spec(inputs["batches"], mesh_cfg.client_axes)
    in_sh = (_ns(mesh, pspecs), (), _ns(mesh, bspecs),
             NamedSharding(mesh, P()))
    out_sh = (_ns(mesh, pspecs), (), NamedSharding(mesh, P()))
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0,) if donate else ())
    with mesh:
        return fn.lower(params_abs, (), inputs["batches"], inputs["weights"])


def lower_prefill(cfg: ModelConfig, shape: InputShape, mesh,
                  mesh_cfg: MeshConfig, impl: str = "xla"):
    batch_axes = mesh_cfg.client_axes  # batch shards over pod+data
    inputs = prefill_input_specs(cfg, shape)
    B = shape.global_batch
    bsize = int(np.prod([mesh.shape[a] for a in batch_axes]))
    b_ax = batch_axes if B % bsize == 0 else ()

    def serve(params, batch):
        return tfm.prefill(cfg, params, batch["tokens"],
                           batch.get("prefix_embeds"),
                           max_len=shape.seq_len, impl=impl)

    params_abs = _abstract_params(cfg)
    pspecs = param_specs(params_abs, mesh, client_axes=None)
    bspecs = jax.tree.map(
        lambda x: P(*((b_ax if len(b_ax) > 1 else b_ax[0] if b_ax else None,)
                      + (None,) * (x.ndim - 1))), inputs)
    fn = jax.jit(serve, in_shardings=(_ns(mesh, pspecs), _ns(mesh, bspecs)))
    with mesh:
        return fn.lower(params_abs, inputs)


def lower_decode(cfg: ModelConfig, shape: InputShape, mesh,
                 mesh_cfg: MeshConfig):
    batch_axes = mesh_cfg.client_axes
    B = shape.global_batch
    bsize = int(np.prod([mesh.shape[a] for a in batch_axes]))
    b_ax = tuple(batch_axes) if B % bsize == 0 else None
    cache_abs = jax.eval_shape(
        functools.partial(tfm.init_cache, cfg, B, shape.seq_len))
    cspecs = cache_specs(cache_abs, mesh, batch_axes=b_ax)
    inputs = decode_input_specs(cfg, shape)

    def serve(params, cache, batch):
        return tfm.decode_step(cfg, params, cache, batch["tokens"])

    params_abs = _abstract_params(cfg)
    pspecs = param_specs(params_abs, mesh, client_axes=None)
    tok_spec = jax.tree.map(
        lambda x: P(*(((b_ax if len(b_ax) > 1 else b_ax[0]) if b_ax else None,)
                      + (None,) * (x.ndim - 1))), inputs)
    fn = jax.jit(
        serve,
        in_shardings=(_ns(mesh, pspecs), _ns(mesh, cspecs), _ns(mesh, tok_spec)),
        out_shardings=(NamedSharding(mesh, P()), _ns(mesh, cspecs)),
        donate_argnums=(1,))
    with mesh:
        return fn.lower(params_abs, cache_abs, inputs)


def lower_pair(arch: str, shape_name: str, mesh, mesh_cfg: MeshConfig,
               V: int = DEFAULT_V, aggregation: str = "allreduce",
               impl: str = "xla", remat: bool = True,
               capacity: float = 0.0, dispatch: str = ""):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    cfg = adapt_config(cfg, shape)
    if not remat:
        cfg = cfg.replace(remat=False)
    if capacity and cfg.moe:
        import dataclasses as _dc
        cfg = cfg.replace(moe=_dc.replace(cfg.moe, capacity_factor=capacity))
    if dispatch and cfg.moe:
        import dataclasses as _dc
        cfg = cfg.replace(moe=_dc.replace(cfg.moe, dispatch=dispatch))
    if shape.kind == "train":
        return lower_train(cfg, shape, mesh, mesh_cfg, V, aggregation,
                           impl=impl), cfg
    if shape.kind == "prefill":
        return lower_prefill(cfg, shape, mesh, mesh_cfg, impl=impl), cfg
    return lower_decode(cfg, shape, mesh, mesh_cfg), cfg


def analyse(lowered, compiled, cfg: ModelConfig, shape: InputShape,
            mesh, V: int) -> Dict:
    n_dev = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        memory = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        memory = {"error": str(e)}
    colls = parse_collectives(compiled.as_text(), default_group=n_dev)
    csum = collective_summary(colls)
    # Roofline terms (seconds). cost_analysis is the per-device program.
    t_compute = flops_dev / fl.PEAK_FLOPS
    t_memory = bytes_dev / fl.HBM_BW
    t_coll = csum["total_wire_bytes"] / fl.ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mflops = fl.model_flops(cfg, shape, V if shape.kind == "train" else 1)
    return {
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "memory": memory,
        "collectives": csum,
        "terms_seconds": terms,
        "dominant": dominant,
        "model_flops": mflops,
        "hlo_flops_global": flops_dev * n_dev,
        "useful_flops_ratio": mflops / (flops_dev * n_dev) if flops_dev else None,
    }


def run_pair(arch: str, shape_name: str, mesh_name: str, out_dir: str,
             V: int = DEFAULT_V, aggregation: str = "allreduce",
             tag: str = "", impl: str = "xla", remat: bool = True,
             capacity: float = 0.0, dispatch: str = "") -> Dict:
    mesh_cfg = MeshConfig(multi_pod=(mesh_name == "multi"))
    mesh = make_production_mesh(multi_pod=mesh_cfg.multi_pod)
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "V": V, "aggregation": aggregation, "impl": impl,
                 "remat": remat, "capacity": capacity, "dispatch": dispatch,
                 "ok": False}
    t0 = time.time()
    try:
        shape = INPUT_SHAPES[shape_name]
        lowered, cfg = lower_pair(arch, shape_name, mesh, mesh_cfg, V,
                                  aggregation, impl=impl, remat=remat,
                                  capacity=capacity, dispatch=dispatch)
        rec["lower_seconds"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_seconds"] = time.time() - t1
        rec.update(analyse(lowered, compiled, cfg, shape, mesh, V))
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_seconds"] = time.time() - t0
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"-{tag}" if tag else ""
        fn = os.path.join(
            out_dir, f"{arch}--{shape_name}--{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--V", type=int, default=DEFAULT_V)
    ap.add_argument("--aggregation", default="allreduce")
    ap.add_argument("--tag", default="")
    ap.add_argument("--impl", default="xla")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--capacity", type=float, default=0.0)
    ap.add_argument("--dispatch", default="")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_pair(arch, shape_name, mesh_name, args.out,
                               V=args.V, aggregation=args.aggregation,
                               tag=args.tag, impl=args.impl,
                               remat=not args.no_remat,
                               capacity=args.capacity,
                               dispatch=args.dispatch)
                if rec["ok"]:
                    t = rec["terms_seconds"]
                    print(f"OK   {arch:26s} {shape_name:12s} {mesh_name:6s} "
                          f"lower={rec['lower_seconds']:6.1f}s "
                          f"compile={rec['compile_seconds']:6.1f}s "
                          f"comp={t['compute']:.3e} mem={t['memory']:.3e} "
                          f"coll={t['collective']:.3e} dom={rec['dominant']}",
                          flush=True)
                else:
                    failures += 1
                    print(f"FAIL {arch:26s} {shape_name:12s} {mesh_name:6s} "
                          f"{rec['error']}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
