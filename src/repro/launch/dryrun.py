import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax -----------------------------------------
"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes, record memory/cost analysis + collective schedule.

  PYTHONPATH=src python -m repro.launch.dryrun                  # full sweep
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x22b \
      --shape train_4k --multi-pod both --artifacts artifacts/dryrun

Artifacts land in <artifacts>/<mesh>/<arch>__<shape>.json and feed
``repro.roofline.analysis`` / EXPERIMENTS.md.  Already-present cells are
skipped (resumable sweep); --force recomputes.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import configs
from ..models import build, input_specs
from ..models.config import SHAPES
from ..models.registry import cache_specs
from ..models.shardings import (auto_mesh, batch_specs, cache_specs_tree,
                                param_specs, to_shardings)
from ..models import shardings as shardings_mod
from ..optim.adamw import AdamW
from ..roofline import hlo_walk
from ..roofline.analysis import Roofline, model_flops_for
from .mesh import data_axes, make_production_mesh

# grad-accumulation chunks per (shape kind); keeps live activations bounded
N_MICRO = {"train": 8, "prefill": 1, "decode": 1}


def _opt_state_specs(pspecs, optimizer):
    """AdamW m/v mirror the param sharding; count is replicated."""
    from ..train.train_step import TrainState  # noqa: F401
    return pspecs


def build_cell(arch: str, shape_name: str, mesh, *, smoke: bool = False,
               shape_override=None, cfg_override=None):
    """Returns (jitted fn, abstract_args, cfg, shape) for one cell.

    ``smoke=True`` swaps in the reduced config (dryrun-lite CI path);
    ``shape_override``/``cfg_override`` let tests shrink the cell further.
    """
    cfg = cfg_override or (configs.get_smoke(arch) if smoke else configs.get(arch))
    shape = shape_override or SHAPES[shape_name]
    mesh = auto_mesh(mesh)
    bundle = build(cfg)
    optimizer = AdamW(lr=1e-4)

    daxes = data_axes(mesh)
    fsdp = daxes if cfg.fsdp else None
    params_abs = bundle.abstract_params()
    repl = (("w_gate", "w_up", "w_down") if cfg.moe_capacity_sharding else ())
    pspecs = param_specs(params_abs, mesh, fsdp_axes=fsdp, replicate_names=repl)
    pshard = to_shardings(pspecs, mesh)

    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        from ..train.train_step import TrainState, _accumulate_grads

        n_micro = shape.microbatch or N_MICRO["train"]
        if shape.global_batch % n_micro:
            n_micro = 1

        def step(params, opt_state, batch):
            grads, metrics = _accumulate_grads(
                lambda p, b: bundle.loss(p, b), params, batch, n_micro)
            new_params, new_opt, om = optimizer.update(grads, opt_state, params)
            return new_params, new_opt, {**metrics, **om}

        opt_abs = jax.eval_shape(optimizer.init, params_abs)
        oshard = jax.tree.map(
            lambda _: None, opt_abs)  # placeholder; real spec below
        from ..optim.adamw import AdamWState
        oshard = AdamWState(
            count=NamedSharding(mesh, P()),
            m=to_shardings(pspecs, mesh),
            v=to_shardings(pspecs, mesh))
        bshard = to_shardings(batch_specs(specs["batch"], mesh), mesh)
        fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     donate_argnums=(0, 1))
        return fn, (params_abs, opt_abs, specs["batch"]), cfg, shape

    if shape.kind == "prefill":
        cshard = to_shardings(cache_specs_tree(specs["cache"], mesh), mesh)
        bshard = to_shardings(batch_specs(specs["batch"], mesh), mesh)

        def pre(params, batch, cache):
            return bundle.prefill(params, batch, cache)

        fn = jax.jit(pre, in_shardings=(pshard, bshard, cshard),
                     donate_argnums=(2,))
        return fn, (params_abs, specs["batch"], specs["cache"]), cfg, shape

    # decode
    total = shape.seq_len
    cshard = to_shardings(cache_specs_tree(specs["cache"], mesh), mesh)
    tshard = to_shardings(batch_specs(specs["token"], mesh), mesh)

    def dec(params, token, cache, pos):
        return bundle.decode(params, token, cache, pos, total)

    fn = jax.jit(dec,
                 in_shardings=(pshard, tshard, cshard, NamedSharding(mesh, P())),
                 donate_argnums=(2,))
    return fn, (params_abs, specs["token"], specs["cache"], specs["pos"]), cfg, shape


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             art_dir: Path, *, force: bool = False, verbose: bool = True):
    out_path = art_dir / mesh_name / f"{configs.canonical(arch)}__{shape_name}.json"
    if out_path.exists() and not force:
        if verbose:
            print(f"[dryrun] skip (cached): {arch} × {shape_name} × {mesh_name}")
        return json.loads(out_path.read_text())
    out_path.parent.mkdir(parents=True, exist_ok=True)

    t0 = time.time()
    shardings_mod.set_activation_mesh(mesh)
    fn, abs_args, cfg, shape = build_cell(arch, shape_name, mesh)
    with mesh:
        lowered = fn.lower(*abs_args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    cost = compiled.cost_analysis() or {}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            if hasattr(ma, f):
                mem[f] = int(getattr(ma, f))
    except Exception as e:                                    # pragma: no cover
        mem["error"] = str(e)

    hlo = compiled.as_text()
    try:
        import zstandard
        (out_path.parent / (out_path.stem + ".hlo.zst")).write_bytes(
            zstandard.ZstdCompressor(level=6).compress(hlo.encode()))
    except Exception:
        pass
    # trip-count-aware walker: XLA cost_analysis visits while bodies once,
    # so scanned programs under-count by the trip factor (see hlo_walk.py)
    walked = hlo_walk.analyze(hlo)
    coll = {k: walked[k] for k in hlo_walk.COLLECTIVES}
    coll["count"] = walked["coll_count"]
    coll["total"] = walked["coll_total"]
    chips = mesh.size
    mf = model_flops_for(cfg, shape)
    # memory term uses ESSENTIAL traffic (what must cross HBM under TPU-level
    # fusion); the upper bound (all top-level op I/O) is recorded alongside
    roof = Roofline.from_cell(
        arch=configs.canonical(arch), shape=shape_name, mesh_name=mesh_name,
        chips=chips,
        cost={"flops": walked["flops"], "bytes accessed": walked["traffic_ess"]},
        collectives=coll, model_flops=mf,
        peak_bytes=float(mem.get("temp_size_in_bytes", 0)
                         + mem.get("argument_size_in_bytes", 0)))

    rec = {
        "arch": configs.canonical(arch), "shape": shape_name,
        "mesh": mesh_name, "chips": chips,
        "lower_s": t_lower, "compile_s": t_compile,
        "walked": {k: float(v) for k, v in walked.items()},
        "cost_analysis_xla": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "memory_analysis": mem,
        "collectives": coll,
        "model_flops": mf,
        "roofline": {
            "compute_s": roof.compute_s, "memory_s": roof.memory_s,
            "collective_s": roof.collective_s, "bottleneck": roof.bottleneck,
            "useful_ratio": roof.useful_ratio,
        },
        "hlo_lines": hlo.count("\n"),
    }
    out_path.write_text(json.dumps(rec, indent=1))
    if verbose:
        m = rec["roofline"]
        print(f"[dryrun] OK {arch} × {shape_name} × {mesh_name}: "
              f"compile {t_compile:.1f}s, bottleneck={m['bottleneck']}, "
              f"compute={m['compute_s']:.3e}s mem={m['memory_s']:.3e}s "
              f"coll={m['collective_s']:.3e}s useful={m['useful_ratio']:.2f} "
              f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB/dev")
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--artifacts", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(configs.ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("pod16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("pods2x16x16", make_production_mesh(multi_pod=True)))

    art_dir = Path(args.artifacts)
    failures = []
    for mesh_name, mesh in meshes:
        for arch in archs:
            for shape_name in shapes:
                if not configs.supports_shape(arch, shape_name):
                    print(f"[dryrun] SKIP {arch} × {shape_name} "
                          f"(full-attention arch; see DESIGN.md §5)")
                    continue
                try:
                    run_cell(arch, shape_name, mesh, mesh_name, art_dir,
                             force=args.force)
                except Exception:
                    failures.append((arch, shape_name, mesh_name))
                    print(f"[dryrun] FAIL {arch} × {shape_name} × {mesh_name}")
                    traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all cells compiled")


if __name__ == "__main__":
    main()
