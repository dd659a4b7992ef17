"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the placeholder device count before ANY other import (jax locks the
device count on first init) — see the first two lines.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm_3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs.base import ARCH_IDS, SHAPES, get_config
from ..distributed import sharding as shd
from .hlo_analysis import analyze_hlo
from .mesh import HW, make_production_mesh
from .specs import build_cell

__all__ = ["run_cell", "main", "count_active_params"]


def count_active_params(cfg, params_shape) -> tuple[int, int]:
    """(total, active) parameter counts; expert leaves scale by top_k/E.

    Expert weights are [E, d, h] — or [L, E, d, h] when the layer scan
    stacks them — so the expert dim may sit at axis 0 or 1.
    """
    total = active = 0
    for leaf in jax.tree_util.tree_leaves(params_shape):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        is_expert = (
            cfg.n_experts
            and leaf.ndim >= 3
            and cfg.n_experts in leaf.shape[:2]
        )
        if is_expert:
            active += n * cfg.top_k / cfg.n_experts
        else:
            active += n
    return int(total), int(active)


def _mem_stats(compiled):
    m = compiled.memory_analysis()
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_cell(
    arch: str,
    shape: str,
    multi_pod: bool = False,
    policy_name: str = "floatsd8_tpu",
    verbose: bool = True,
    **cell_kw,
) -> dict:
    save_hlo = cell_kw.pop("save_hlo", False)
    cfg = get_config(arch)
    skip = cfg.skips(shape)
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "policy": policy_name,
    }
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    # perf experiment (EXPERIMENTS.md §Perf HC2 it.4): REPRO_LSTM_TP0=1
    # replicates the small LSTM gate weights over the model axis instead of
    # TP-sharding hidden4 (the 85M model doesn't need TP; the per-step h
    # gathers it forces do not amortize).
    rules = None
    if os.environ.get("REPRO_LSTM_TP0") == "1" and cfg.family == "lstm":
        rules = {"hidden4": None, "act_mlp": None}
    try:
        with shd.use_mesh(mesh, rules=rules):
            t0 = time.time()
            cell = build_cell(arch, shape, mesh, policy_name=policy_name, **cell_kw)
            jf = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
            )
            lowered = jf.lower(*cell.args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()

        mem = _mem_stats(compiled)
        ca = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        if save_hlo:
            import gzip

            os.makedirs("results/hlo", exist_ok=True)
            with gzip.open(
                f"results/hlo/{arch}__{shape}__{rec['mesh']}.hlo.gz", "wt"
            ) as f:
                f.write(hlo_text)
        hlo = analyze_hlo(hlo_text, n_partitions=n_dev)
        # kernel-substitution variant: flash-attention tiles VMEM-resident
        hlo_fl = analyze_hlo(
            hlo_text, n_partitions=n_dev, vmem_scopes=("flashable",)
        )

        seq, gbatch, kind = SHAPES[shape]
        params_shape = jax.eval_shape(
            lambda k: cell.model.init(k), jax.random.PRNGKey(0)
        )
        total_p, active_p = count_active_params(cfg, params_shape)
        tokens = gbatch * (seq if kind != "decode" else 1)
        mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[kind]
        model_flops = mult * active_p * tokens / n_dev  # per device

        compute_s = hlo.flops / HW.peak_flops_bf16
        memory_s = hlo.bytes_accessed / HW.hbm_bw
        coll_s = hlo.collective_bytes / HW.ici_bw_per_link
        dom = max(
            ("compute", compute_s), ("memory", memory_s), ("collective", coll_s),
            key=lambda kv: kv[1],
        )[0]
        memory_s_fl = hlo_fl.bytes_accessed / HW.hbm_bw
        top_bytes = sorted(hlo.detail.items(), key=lambda kv: -kv[1])[:20]
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            kind=kind,
            n_devices=n_dev,
            memory=mem,
            xla_cost_analysis={
                k: float(ca[k]) for k in ("flops", "bytes accessed") if k in ca
            },
            hlo_flops=hlo.flops,
            hlo_dot_flops=hlo.dot_flops,
            hlo_bytes=hlo.bytes_accessed,
            collective_wire_bytes=hlo.collective_bytes,
            collective_raw_bytes=hlo.collective_raw,
            collective_breakdown={k: float(v) for k, v in hlo.collective_breakdown.items()},
            collective_count=hlo.collective_count,
            unknown_while=hlo.unknown_while,
            params_total=total_p,
            params_active=active_p,
            model_flops_per_device=model_flops,
            useful_flops_ratio=round(model_flops / hlo.flops, 4) if hlo.flops else None,
            roofline={
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": coll_s,
                "dominant": dom,
            },
            # kernel-substitution variant (flash tiles VMEM-resident on TPU)
            roofline_flash={
                "memory_s": memory_s_fl,
                "bytes": hlo_fl.bytes_accessed,
                "discounted_bytes": hlo_fl.bytes_by_op.get(
                    "vmem-resident(discounted)", 0.0
                ),
            },
            bytes_by_op={k: float(v) for k, v in sorted(
                hlo.bytes_by_op.items(), key=lambda kv: -kv[1])},
            top_bytes_instrs=[[k, float(v)] for k, v in top_bytes],
        )
        if verbose:
            print(
                f"[{rec['mesh']}] {arch:20s} {shape:12s} OK  "
                f"compile={rec['compile_s']:7.1f}s  "
                f"C={compute_s*1e3:8.2f}ms M={memory_s*1e3:8.2f}ms "
                f"X={coll_s*1e3:8.2f}ms dom={dom:10s} "
                f"useful={rec['useful_flops_ratio']}",
                flush=True,
            )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[{rec['mesh']}] {arch:20s} {shape:12s} FAIL {rec['error'][:160]}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--policy", default="floatsd8_tpu")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--attn-chunk", type=int, default=1024)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"skip existing {tag}", flush=True)
                    continue
                rec = run_cell(
                    arch, shape, multi_pod=mp, policy_name=args.policy,
                    remat=args.remat, attn_chunk=args.attn_chunk,
                    save_hlo=args.save_hlo,
                )
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                jax.clear_caches()  # keep host RAM bounded across the sweep


if __name__ == "__main__":
    main()
