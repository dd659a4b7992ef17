"""Production training driver.

Drives any of the paper's four LSTM tasks (synthetic data, CPU-runnable) or a
reduced assigned-arch config, with the full distributed runtime: sharded data
pipeline, FP16-master FloatSD8 train step, atomic async checkpointing,
resume-from-latest, preemption handling, straggler monitoring.

  PYTHONPATH=src python -m repro.launch.train --task wikitext2 --steps 300 \
      --policy floatsd8_table6 --ckpt-dir /tmp/ckpt  [--full]
  PYTHONPATH=src python -m repro.launch.train --arch stablelm_3b --steps 20
      # reduced config of an assigned arch, causal-LM objective

Relaunching the same command after a crash resumes from the newest
checkpoint (RestartableLoop); --fail-at N demonstrates it.
"""
from __future__ import annotations

import argparse
import collections
import os
import time

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from ..configs.base import get_config
from ..core.policy import get_policy
from ..data import synthetic
from ..data.pipeline import ShardedPipeline
from ..distributed import sharding as shd
from ..distributed.checkpointing import CheckpointManager
from ..distributed.fault_tolerance import (
    PreemptionSignal,
    RestartableLoop,
    StragglerMonitor,
)
from ..models import build
from . import compile_cache
from ..obs.telemetry import TelemetryLogger
from ..optim import adam, sgd
from ..optim.train_state import init_state, make_train_step

TASK_OPT = {"udpos": ("adam", 1e-3), "snli": ("adam", 1e-3),
            "multi30k": ("adam", 1e-3), "wikitext2": ("sgd", 0.5)}


def make_mesh_for_host():
    """All addressable devices as a ("data","model") mesh with model=1: the
    train step runs data parallel over every device of the host."""
    n = len(jax.devices())
    # Auto axes: jax.make_mesh defaults to Explicit sharding-in-types, which
    # the model's gathers do not annotate
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def build_task(args):
    """Returns (model, batches_iter, opt, lr)."""
    if args.arch:
        cfg = get_config(args.arch).reduced()
        model = build(cfg)
        data = synthetic.wikitext2(batch=args.batch, seq=args.seq, vocab=cfg.vocab)
        return model, data.batches, adam(), args.lr or 1e-3
    from ..models.task_zoo import make_task

    model, data, opt, lr, _ = make_task(args.task, full=args.full)
    return model, data.batches, opt, args.lr or lr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="wikitext2",
                    choices=["udpos", "snli", "multi30k", "wikitext2"])
    ap.add_argument("--arch", default=None, help="assigned arch id (reduced cfg)")
    ap.add_argument("--policy", default="floatsd8_table6")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--full", action="store_true", help="paper-scale model")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", default="auto", choices=["auto", "never"],
                    help="auto: restore the newest valid checkpoint under "
                    "--ckpt-dir at startup (a SIGKILL'd run relaunched with "
                    "the same command continues bit-compatibly); never: "
                    "always start fresh")
    ap.add_argument("--dynamic-scale", action="store_true",
                    help="dynamic loss scaling: nonfinite grads skip the "
                    "update and halve the scale (backoff), sustained finite "
                    "windows double it — the recovery loop for fp8 "
                    "overflow, vs the default static scale")
    ap.add_argument("--no-fused", action="store_true",
                    help="disable the fused quantized-BPTT backward "
                    "(restores the autodiff + grad_quant tree-pass path)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="drop quantization-health telemetry from the step")
    ap.add_argument("--telemetry-out", default=None,
                    help="JSONL file for TrainTelemetry records "
                    "(default: <ckpt-dir>/telemetry.jsonl)")
    args = ap.parse_args()
    compile_cache.enable()

    policy = get_policy(args.policy)
    mesh = make_mesh_for_host()
    model, batches, opt, lr = build_task(args)

    with shd.use_mesh(mesh):
        # donated jitted step: params/opt buffers update in place; the
        # finite-check/skip logic is already fused inside the step
        step_fn = make_train_step(
            model.loss, opt, policy, lr=lr,
            fused=False if args.no_fused else None, donate=True,
            telemetry=not args.no_telemetry, mesh=mesh,
        )

        # the state is replicated on every device of the mesh (data
        # parallel), placed so the first step sees the layout every later
        # step does and compiles once
        replicated = NamedSharding(mesh, P())

        def init_fn():
            params = model.init(jax.random.PRNGKey(args.seed))
            return jax.device_put(init_state(
                params, opt, policy, dynamic_scale=args.dynamic_scale
            ), replicated)

        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        loop = RestartableLoop(
            ckpt, init_fn, save_every=args.save_every,
            preemption=PreemptionSignal(install_sigterm=True),
            straggler=StragglerMonitor(),
            shardings=replicated,
            resume=args.resume,
        )
        if loop.resumed:
            print(f"resumed from step {loop.start_step}", flush=True)

        pipeline = ShardedPipeline(batches, mesh)
        # bounded loss window: enough for the widest log average, never
        # unbounded growth over long runs
        hist = collections.deque(maxlen=max(args.log_every, 100))
        t_first_done = [None]  # wall time when the first (compile) step ends
        telemetry = None
        if not args.no_telemetry:
            tel_path = args.telemetry_out or os.path.join(
                args.ckpt_dir, "telemetry.jsonl"
            )
            os.makedirs(os.path.dirname(tel_path) or ".", exist_ok=True)
            telemetry = TelemetryLogger(path=tel_path)
            print(f"telemetry -> {tel_path}", flush=True)

        skipped = [0]  # nonfinite-grad steps (update skipped, scale backed off)

        def on_metrics(step, m):
            hist.append(float(m["loss"]))
            if not bool(m["grads_finite"]):
                skipped[0] += 1
            if t_first_done[0] is None:
                t_first_done[0] = time.time()
            if telemetry is not None:
                telemetry.update(step, m)
            if step % args.log_every == 0:
                window = list(hist)[-args.log_every:]
                print(
                    f"step {step:5d}  loss {np.mean(window):.4f}  "
                    f"scale {float(m['loss_scale']):.0f}  "
                    f"finite {bool(m['grads_finite'])}",
                    flush=True,
                )
                if telemetry is not None:
                    print(telemetry.format(telemetry.emit(step)), flush=True)

        t0 = time.time()
        state, last = loop.run(
            step_fn, pipeline, args.steps, fail_at=args.fail_at,
            on_metrics=on_metrics,
        )
        dt = time.time() - t0
        done = last - loop.start_step
        # warm rate excludes the first step of the run (jit compile)
        if t_first_done[0] is not None and done > 1:
            compile_s = t_first_done[0] - t0
            warm_dt = time.time() - t_first_done[0]
            rate = (
                f"compile {compile_s:.1f}s + {warm_dt/(done-1):.3f}s/step warm "
                f"({(done-1)/max(warm_dt,1e-9):.2f} steps/s)"
            )
        else:
            rate = f"{dt/max(done,1):.2f}s/step"
        print(
            f"trained {done} steps in {dt:.1f}s ({rate}); stragglers flagged: "
            f"{len(loop.straggler.flagged)}; nonfinite steps skipped: "
            f"{skipped[0]}",
            flush=True,
        )
        pipeline.close()
        ckpt.wait()


if __name__ == "__main__":
    main()
