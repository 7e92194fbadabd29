import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# compile-only on fake host devices: never take an accelerator, which a
# parent process may hold
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro.compat import set_mesh                                 # noqa: E402
from repro.configs import ARCHS, get_config                       # noqa: E402
from repro.launch import hlo_stats                                # noqa: E402
from repro.launch.mesh import make_production_mesh                # noqa: E402
from repro.launch.specs import cache_len, input_specs             # noqa: E402
from repro.models import model as M                               # noqa: E402
from repro.models.config import SHAPES, shape_applicable          # noqa: E402
from repro.sharding import rules as R                             # noqa: E402
from repro.training import steps as S                             # noqa: E402
from repro.training.optimizer import AdamWState                   # noqa: E402

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell:
  jit(step).lower(ShapeDtypeStructs w/ NamedShardings).compile()
then record memory_analysis / cost_analysis / collective bytes to JSON for
EXPERIMENTS.md §Dry-run and the roofline (§Roofline). No arrays are ever
allocated — params, optimizer state, caches and batches are all
ShapeDtypeStruct stand-ins.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun
"""

LM_ARCHS = [a for a in ARCHS if a != "drone_graph"]


def _sds_with(shardings, shapes):
    return jax.tree.map(
        lambda sh, sd: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        shardings, shapes)


def _eval_shape_params(cfg):
    return jax.eval_shape(lambda k: M.init_model(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def lower_cell(arch: str, shape_name: str, mesh, *, lower_only=False,
               variant: str = "base"):
    cfg = get_config(arch)
    if variant == "opt":
        from repro.configs.variants import optimized
        cfg = optimized(cfg)
    kind, batch_sds, cache_sds = input_specs(cfg, shape_name)
    rules = R.rules_for(mesh)

    p_shapes = _eval_shape_params(cfg)
    p_shard = R.param_shardings(mesh, M.model_specs(cfg), p_shapes)
    params_in = _sds_with(p_shard, p_shapes)

    def _bshard(sd):
        spec = R.logical_to_spec(("batch",) + (None,) * (len(sd.shape) - 1),
                                 rules)
        # drop the batch mapping if the global batch doesn't divide the axes
        m = spec[0]
        axes = (m,) if isinstance(m, str) else tuple(m or ())
        n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if n > 1 and sd.shape[0] % n != 0:
            spec = jax.sharding.PartitionSpec(*((None,) + tuple(spec)[1:]))
        return jax.ShapeDtypeStruct(
            sd.shape, sd.dtype, sharding=jax.NamedSharding(mesh, spec))

    batch_in = jax.tree.map(_bshard, batch_sds)

    if kind == "train":
        opt_shapes = jax.eval_shape(
            lambda p: AdamWState(step=jnp.zeros((), jnp.int32),
                                 m=jax.tree.map(
                                     lambda x: jnp.zeros(x.shape, jnp.float32), p),
                                 v=jax.tree.map(
                                     lambda x: jnp.zeros(x.shape, jnp.float32), p)),
            p_shapes)
        opt_shard = AdamWState(
            step=jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            m=R.param_shardings(mesh, M.model_specs(cfg), p_shapes),
            v=R.param_shardings(mesh, M.model_specs(cfg), p_shapes))
        state_in = S.TrainState(params=params_in,
                                opt=_sds_with(opt_shard, opt_shapes))
        step = S.make_train_step(cfg)
        fn = jax.jit(step, donate_argnums=(0,))
        args = (state_in, batch_in)
    elif kind == "prefill":
        step = S.make_prefill_step(cfg, cache_len(cfg,
                                                  SHAPES[shape_name]["seq_len"]))
        fn = jax.jit(step)
        args = (params_in, batch_in)
    else:  # decode
        c_shard = R.cache_shardings(mesh, M.cache_specs(cfg), cache_sds)
        cache_in = _sds_with(c_shard, cache_sds)
        step = S.make_serve_step(cfg)
        fn = jax.jit(step, donate_argnums=(1,))
        args = (params_in, cache_in, batch_in)

    with set_mesh(mesh):
        lowered = fn.lower(*args)
        if lower_only:
            return lowered, None
        compiled = lowered.compile()
    return lowered, compiled


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             *, force=False, variant: str = "base") -> dict:
    suffix = "" if variant == "base" else f"__{variant}"
    path = os.path.join(out_dir,
                        f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            cached = json.load(f)
        if cached.get("status") != "error":   # errored cells always re-run
            return cached
    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "family": cfg.family, "variant": variant}
    ok, why = shape_applicable(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        t0 = time.time()
        try:
            lowered, compiled = lower_cell(arch, shape_name, mesh,
                                           variant=variant)
            rec["compile_s"] = round(time.time() - t0, 1)
            rec["cost"] = hlo_stats.cost_stats(compiled)
            rec["memory"] = hlo_stats.memory_stats(compiled)
            txt = compiled.as_text()
            rec["collectives"] = hlo_stats.collective_stats(txt)
            from repro.launch import hlo_walk
            rec["walk"] = hlo_walk.analyze(txt)
            rec["hlo_lines"] = txt.count("\n")
            rec["n_devices"] = int(np.prod(list(mesh.shape.values())))
            rec["status"] = "ok"
        except Exception as e:
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-3000:]
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multipod", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    args = ap.parse_args()

    archs = LM_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = (["single", "multipod"] if args.mesh == "both" else [args.mesh])

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_cell(arch, shape, mk, args.out, force=args.force,
                               variant=args.variant)
                tag = rec["status"]
                n_ok += tag == "ok"
                n_skip += tag == "skipped"
                n_err += tag == "error"
                extra = ""
                if tag == "ok":
                    f = rec["cost"].get("flops", 0)
                    mem = rec["memory"].get("temp_size_in_bytes", 0)
                    extra = (f" flops={f:.3e} temp={mem/2**30:.2f}GiB"
                             f" coll={rec['collectives']['bytes_per_device']/2**30:.3f}GiB"
                             f" t={rec.get('compile_s')}s")
                elif tag == "error":
                    extra = " " + rec["error"][:200]
                print(f"[{tag:7s}] {arch:24s} {shape:12s} {mk:8s}{extra}",
                      flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} err={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
