"""Production training entrypoint: builds the mesh, shards state via the
partition rules, and runs the fault-tolerant trainer.

  python -m repro.launch.train --arch tinyllama-1.1b --steps 1000 \
      [--mesh 16x16|2x16x16|dxm] [--approx axq8|exact] [--qos]

On this CPU container use smoke archs (--arch tinyllama-1.1b-smoke); on a TPU
pod the same entrypoint drives the full configs.
"""
import argparse

import jax

from repro.configs import get_config
from repro.core.approx import policy_from_flag
from repro.core.dynamic import QoSController
from repro.data.pipeline import make_pipeline
from repro.dist import meshctx
from repro.kernels import dispatch as kdispatch
from repro.launch import compile_cache
from repro.models import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.train import step as step_mod
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b-smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--approx", default="exact")
    ap.add_argument("--plan", default=None,
                    help="ApproxPlan JSON (repro.tune): train under the "
                         "plan's policy with its per-layer degree ladder as "
                         "the QoS ladder (implies the plan's mode/block)")
    ap.add_argument("--qos", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="checkpoints/launch_train")
    ap.add_argument("--kernels", default=None,
                    choices=("auto", "pallas", "xla"),
                    help="attention kernel backend (default: REPRO_KERNELS "
                         "env or auto = pallas on TPU, xla elsewhere)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace_event JSON of the run "
                         "(data/step/checkpoint spans, straggler and "
                         "QoS-rung events)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write Prometheus text-format metrics (step/loss/"
                         "checkpoint counters, step-time histogram, degree "
                         "gauges) at exit")
    args = ap.parse_args()

    kdispatch.set_backend(args.kernels)
    if args.trace_out:
        obs_trace.enable()

    d, m = (int(x) for x in args.mesh.split("x")[:2])
    mesh = meshctx.make_mesh((d, m), ("data", "model"))
    meshctx.set_mesh(mesh)

    cfg = get_config(args.arch)
    plan = None
    if args.plan is not None:
        from repro.tune import ApproxPlan

        plan = ApproxPlan.load(args.plan)
        plan.validate_for(cfg)
        policy = plan.policy(dynamic=True)
    else:
        try:
            policy = policy_from_flag(args.approx, dynamic=args.qos)
        except ValueError as e:
            raise SystemExit(str(e))
    model = build_model(cfg, policy)
    pipe = make_pipeline(cfg, seq_len=args.seq, global_batch=args.batch)
    # same contract as serve: --qos steps the ladder (the plan's rungs when
    # --plan is given); a plan WITHOUT --qos trains the most-accurate rung
    # as a fixed configuration
    ladder = (plan.qos_ladder() if plan is not None
              else [{"ebits": 8}, {"ebits": 7}, {"ebits": 6}, {"ebits": 5}])
    qos = QoSController(
        ladder=ladder,
        low_water=-0.005, high_water=0.05) if args.qos else None
    static_degrees = (list(plan.degrees(0))
                      if (plan is not None and qos is None) else None)
    trainer = Trainer(
        model,
        step_mod.StepConfig(remat="none", total_steps=args.steps,
                            warmup=max(args.steps // 20, 5),
                            compress_grads=args.compress_grads),
        TrainerConfig(total_steps=args.steps, ckpt_every=max(args.steps // 4, 10),
                      ckpt_dir=args.ckpt_dir, qos=qos,
                      static_degrees=static_degrees),
        pipe, tp=m,
        registry=obs_metrics.get_registry() if args.metrics_out else None)
    out = trainer.run()
    print(f"[launch.train] done at step {out['final_step']}; "
          f"preempted={out['preempted']}; stragglers={len(out['stragglers'])}")
    if args.trace_out:
        obs_trace.get_tracer().write(args.trace_out)
        print(f"[launch.train] wrote Chrome trace -> {args.trace_out}")
    if args.metrics_out:
        obs_metrics.get_registry().write(args.metrics_out)
        print(f"[launch.train] wrote Prometheus metrics -> {args.metrics_out}")


if __name__ == "__main__":
    main()
