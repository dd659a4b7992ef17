"""Recompute roofline terms in dry-run JSONs from saved HLO text
(results/hlo/*.hlo.gz) — no recompilation. Run after analyzer changes so
the whole table shares one accounting policy.

  PYTHONPATH=src python -m repro.launch.reanalyze --dir results/dryrun
"""
import argparse
import glob
import gzip
import json
import os

from .hlo_analysis import analyze_hlo
from .mesh import HW


def reanalyze_record(rec: dict, hlo_dir: str) -> bool:
    tag = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
    path = os.path.join(hlo_dir, tag + ".hlo.gz")
    if rec.get("status") != "ok" or not os.path.exists(path):
        return False
    with gzip.open(path, "rt") as f:
        text = f.read()
    n = rec["n_devices"]
    h = analyze_hlo(text, n_partitions=n)
    hf = analyze_hlo(text, n_partitions=n, vmem_scopes=("flashable",))
    compute_s = h.flops / HW.peak_flops_bf16
    memory_s = h.bytes_accessed / HW.hbm_bw
    coll_s = h.collective_bytes / HW.ici_bw_per_link
    dom = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", coll_s),
        key=lambda kv: kv[1],
    )[0]
    rec.update(
        hlo_flops=h.flops,
        hlo_dot_flops=h.dot_flops,
        hlo_bytes=h.bytes_accessed,
        collective_wire_bytes=h.collective_bytes,
        collective_raw_bytes=h.collective_raw,
        collective_breakdown={k: float(v) for k, v in h.collective_breakdown.items()},
        collective_count=h.collective_count,
        unknown_while=h.unknown_while,
        useful_flops_ratio=(
            round(rec["model_flops_per_device"] / h.flops, 4) if h.flops else None
        ),
        roofline={
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": coll_s,
            "dominant": dom,
        },
        roofline_flash={
            "memory_s": hf.bytes_accessed / HW.hbm_bw,
            "bytes": hf.bytes_accessed,
            "discounted_bytes": hf.bytes_by_op.get("vmem-resident(discounted)", 0.0),
        },
        bytes_by_op={k: float(v) for k, v in sorted(
            h.bytes_by_op.items(), key=lambda kv: -kv[1])},
        top_bytes_instrs=[
            [k, float(v)]
            for k, v in sorted(h.detail.items(), key=lambda kv: -kv[1])[:20]
        ],
    )
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--hlo-dir", default="results/hlo")
    a = ap.parse_args()
    for path in sorted(glob.glob(f"{a.dir}/*.json")):
        with open(path) as f:
            rec = json.load(f)
        if reanalyze_record(rec, a.hlo_dir):
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            rf = rec["roofline"]
            print(
                f"{os.path.basename(path):50s} C={rf['compute_s']*1e3:9.1f}ms "
                f"M={rf['memory_s']*1e3:9.1f}ms X={rf['collective_s']*1e3:9.1f}ms "
                f"dom={rf['dominant']}", flush=True,
            )


if __name__ == "__main__":
    main()
