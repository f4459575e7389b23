import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh (16x16 single-pod, 2x16x16 multi-pod) and records the
artifacts the roofline reads:
  - compiled.memory_analysis()   (fits per device?)
  - compiled.cost_analysis()     (XLA's aggregate flops/bytes — NOTE: while
                                  bodies counted once; see dist/hlo_analysis)
  - trip-count-aware dot FLOPs / traffic / collective bytes from the HLO text

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]       # subprocess per cell
  python -m repro.launch.dryrun --list
Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json.
"""  # noqa: E402

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
OUT_DIR = ROOT / "experiments" / "dryrun"

ARCHS = [
    "qwen2-moe-a2.7b",
    "granite-moe-3b-a800m",
    "mistral-nemo-12b",
    "h2o-danube-1.8b",
    "qwen2.5-3b",
    "tinyllama-1.1b",
    "recurrentgemma-2b",
    "internvl2-1b",
    "hubert-xlarge",
    "mamba2-370m",
]
SHAPE_NAMES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             step_overrides: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config
    from repro.dist import meshctx, sharding
    from repro.dist.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model, input_specs
    from repro.train import step as step_mod

    t0 = time.time()
    cfg = get_config(arch)
    reason = cfg.skip_reason(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "skip" if reason else "pending", "skip_reason": reason,
    }
    if reason:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    meshctx.set_mesh(mesh)
    tp = mesh.shape["model"]
    model = build_model(cfg)
    shp = SHAPES[shape_name]
    scfg = step_mod.StepConfig(**({"remat": "full"} | (step_overrides or {})))

    key = jax.random.PRNGKey(0)
    batch_sds = input_specs(cfg, shape_name)

    if shp.kind in ("train", "prefill"):
        state_sds = jax.eval_shape(
            partial(step_mod.init_state, model, tp=tp), key)
        pspecs = sharding.partition_params(state_sds.params, cfg.family)
        state_specs = step_mod.TrainState(
            pspecs, sharding.partition_opt_state(state_sds.opt, pspecs),
            jax.sharding.PartitionSpec())
        batch_specs = sharding.partition_batch(batch_sds)
        if shp.kind == "train":
            fn = partial(step_mod.train_step, model, scfg, tp=tp)
            jitted = jax.jit(
                fn,
                in_shardings=(sharding.named(state_specs, mesh),
                              sharding.named(batch_specs, mesh)),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_sds, batch_sds)
        else:  # prefill == forward-only at scale (inference-prefill cell)
            def fwd(params, batch):
                logits, aux = model.forward(params, batch, tp=tp, remat="dots")
                return logits

            jitted = jax.jit(
                fwd,
                in_shardings=(sharding.named(pspecs, mesh),
                              sharding.named(batch_specs, mesh)),
            )
            lowered = jitted.lower(state_sds.params, batch_sds)
    else:  # decode
        params_sds = jax.eval_shape(partial(model.init, tp=tp), key)
        if os.environ.get("REPRO_SERVE_BF16", "0") == "1":
            # §Perf hillclimb B1: serve from bf16 weights (dense_apply casts
            # to activation dtype anyway — numerically identical path)
            params_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
                if x.dtype == jnp.float32 else x, params_sds)
        pspecs = sharding.partition_params(params_sds, cfg.family)
        cache_sds = jax.eval_shape(
            partial(model.init_cache, tp, shp.global_batch, shp.seq_len))
        cache_specs = sharding.partition_cache(cache_sds, cfg.family)
        tok_specs = sharding.partition_batch(batch_sds)
        fn = partial(step_mod.serve_step, model, tp=tp)
        jitted = jax.jit(
            fn,
            in_shardings=(sharding.named(pspecs, mesh),
                          sharding.named(cache_specs, mesh),
                          sharding.named(tok_specs["tokens"], mesh)),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_sds, cache_sds, batch_sds["tokens"])

    t_lower = time.time()
    compiled = lowered.compile()
    t_compile = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    rep = analyze_hlo(hlo)
    n_total, n_active = cfg.param_count()

    rec.update(
        status="ok",
        chips=mesh.size,
        tp=tp,
        seq=shp.seq_len,
        global_batch=shp.global_batch,
        kind=shp.kind,
        lower_s=round(t_lower - t0, 2),
        compile_s=round(t_compile - t_lower, 2),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            code_bytes=mem.generated_code_size_in_bytes,
        ),
        xla_cost=dict(
            flops=float(cost.get("flops", -1)),
            bytes_accessed=float(cost.get("bytes accessed", -1)),
        ),
        hlo_analysis=rep.as_dict(),
        params_total=n_total,
        params_active=n_active,
        hlo_lines=len(hlo.splitlines()),
    )
    return rec


def cell_out_path(arch: str, shape_name: str, multi_pod: bool,
                  tag: str = "") -> Path:
    mesh_name = ("pod2x16x16" if multi_pod else "pod16x16") +         (f"__{tag}" if tag else "")
    d = OUT_DIR / mesh_name
    d.mkdir(parents=True, exist_ok=True)
    return d / f"{arch}__{shape_name}.json"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=SHAPE_NAMES)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every cell as a subprocess (both meshes unless "
                         "--multi-pod/--single-pod given)")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--step-overrides", default="",
                    help='JSON StepConfig overrides, e.g. {"remat":"full"}')
    ap.add_argument("--tag", default="",
                    help="experiment tag (results in <mesh>__<tag>/)")
    args = ap.parse_args()

    if args.list:
        from repro.configs import get_config

        for a in ARCHS:
            cfg = get_config(a)
            cells = [s for s, v in cfg.valid_shapes().items() if v]
            skips = [f"{s}({cfg.skip_reason(s)})"
                     for s, v in cfg.valid_shapes().items() if v is None]
            print(f"{a:<24} run: {', '.join(cells)}"
                  + (f"  SKIP: {'; '.join(skips)}" if skips else ""))
        return

    if args.all:
        if args.multi_pod:
            meshes = [True]
        elif args.single_pod:
            meshes = [False]
        else:
            meshes = [False, True]
        failures = []
        for mp in meshes:
            for a in ARCHS:
                for s in SHAPE_NAMES:
                    out = cell_out_path(a, s, mp)
                    if out.exists() and not args.force:
                        print(f"[skip-cached] {out.name}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", a, "--shape", s]
                    if mp:
                        cmd.append("--multi-pod")
                    if args.step_overrides:
                        cmd += ["--step-overrides", args.step_overrides]
                    print(f"[run] {a} x {s} mesh={'2x16x16' if mp else '16x16'}",
                          flush=True)
                    r = subprocess.run(cmd, cwd=str(ROOT))
                    if r.returncode != 0:
                        failures.append((a, s, mp))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("all cells done")
        return

    assert args.arch and args.shape, "--arch and --shape (or --all/--list)"
    overrides = json.loads(args.step_overrides) if args.step_overrides else None
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, overrides)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
               "status": "error", "error": traceback.format_exc()}
    out = cell_out_path(args.arch, args.shape, args.multi_pod, args.tag)
    out.write_text(json.dumps(rec, indent=2))
    if rec["status"] == "ok":
        print(f"OK {args.arch} x {args.shape}: "
              f"compile {rec['compile_s']}s, "
              f"temp/device {rec['memory']['temp_bytes']/2**30:.2f} GiB, "
              f"dot_flops/device {rec['hlo_analysis']['dot_flops']:.3e}, "
              f"coll {rec['hlo_analysis']['collectives']['total_bytes']/2**30:.3f} GiB")
        print("memory_analysis:", rec["memory"])
        print("cost_analysis:", rec["xla_cost"])
    elif rec["status"] == "skip":
        print(f"SKIP {args.arch} x {args.shape}: {rec['skip_reason']}")
    else:
        print(rec.get("error", "error"), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
