"""Roofline table from the dry-run campaign artifacts (results/dryrun/*.json).

Per (arch x shape x mesh): the three roofline terms (compute / memory /
collective seconds), the dominant term, MODEL_FLOPS/HLO_FLOPs usefulness
ratio, and the roofline fraction = compute_term / max(all terms) — i.e. how
close the cell is to being compute-bound at peak.

Kernel-level mode (``--kernels LEDGER.json``): plots the cost-model
observatory's per-(op, backend) ledger rows — arithmetic intensity from
the analytical CostSpecs against a peak-FLOPs/peak-bandwidth roofline —
as a table plus an ASCII scatter. Accepts a ``bench_kernels --ledger-out``
artifact ({"meta", "rows"}), a BENCH_train.json (its "ledger" key), or a
bare row list.
"""
from __future__ import annotations

import argparse
import glob
import json
import os


def load(dirpath: str):
    rows = []
    for p in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(p) as f:
            rows.append(json.load(f))
    return rows


def fmt_row(r) -> str:
    if r.get("status") == "skipped":
        return (f"  {r['arch']:20s} {r['shape']:12s} {r['mesh']:10s} "
                f"SKIPPED ({r['reason']})")
    if r.get("status") != "ok":
        return (f"  {r['arch']:20s} {r['shape']:12s} {r['mesh']:10s} "
                f"ERROR {r.get('error','')[:80]}")
    rf = r["roofline"]
    c, m, x = rf["compute_s"], rf["memory_s"], rf["collective_s"]
    frac = c / max(c, m, x) if max(c, m, x) else 0.0
    return (f"  {r['arch']:20s} {r['shape']:12s} {r['mesh']:10s} "
            f"C={c*1e3:9.2f}ms M={m*1e3:9.2f}ms X={x*1e3:9.2f}ms "
            f"dom={rf['dominant']:10s} roofline={frac:5.1%} "
            f"useful={r['useful_flops_ratio']}")


def run(dirpath: str = "results/dryrun", mesh: str | None = None, verbose=True):
    rows = load(dirpath)
    if mesh:
        rows = [r for r in rows if r.get("mesh") == mesh]
    ok = [r for r in rows if r.get("status") == "ok"]
    if verbose:
        print(f"Roofline table ({len(rows)} cells, {len(ok)} compiled OK):")
        for r in rows:
            print(fmt_row(r))
        if ok:
            doms = {}
            for r in ok:
                doms[r["roofline"]["dominant"]] = doms.get(r["roofline"]["dominant"], 0) + 1
            print(f"  dominant-term histogram: {doms}")
    return rows


def markdown(dirpath: str = "results/dryrun", mesh: str = "16x16") -> str:
    """§Roofline markdown table for EXPERIMENTS.md."""
    rows = [r for r in load(dirpath) if r.get("mesh") == mesh]
    out = [
        "| arch | shape | C (ms) | M (ms) | X (ms) | dominant | roofline-frac "
        "| useful | M-flash (ms) |",
        "|---|---|---:|---:|---:|---|---:|---:|---:|",
    ]
    for r in rows:
        if r.get("status") == "skipped":
            out.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | *skipped* | — | — | — |"
            )
            continue
        if r.get("status") != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | ERROR | | | | | | |")
            continue
        rf = r["roofline"]
        c, m, x = rf["compute_s"], rf["memory_s"], rf["collective_s"]
        frac = c / max(c, m, x) if max(c, m, x) else 0.0
        mf = r.get("roofline_flash", {}).get("memory_s")
        out.append(
            f"| {r['arch']} | {r['shape']} | {c*1e3:.1f} | {m*1e3:.1f} | "
            f"{x*1e3:.1f} | {rf['dominant']} | {frac:.1%} | "
            f"{r['useful_flops_ratio']} | "
            f"{'' if mf is None else f'{mf*1e3:.1f}'} |"
        )
    return "\n".join(out)


# ---------------------------------------------------------------------------
# kernel-level roofline from the cost-model ledger
# ---------------------------------------------------------------------------


def load_ledger(path: str) -> list[dict]:
    """Ledger rows from any of the artifact shapes that carry them."""
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, list):
        return data
    rows = data.get("rows", data.get("ledger"))
    if rows is None:
        raise ValueError(f"{path}: no 'rows' or 'ledger' key (not a ledger artifact)")
    return rows


def kernel_table(rows: list[dict], peak_gflops: float, peak_gbs: float,
                 verbose: bool = True) -> list[dict]:
    """Per-(op, backend) roofline placement: arithmetic intensity (model),
    bound regime vs the machine ridge, attainable GFLOP/s, and — when the
    ledger carries measured wall-time — achieved GFLOP/s and roof fraction."""
    ridge = peak_gflops / peak_gbs  # FLOP/byte where compute == memory bound
    out = []
    for r in rows:
        ai = r.get("arithmetic_intensity", 0.0)
        attainable = min(peak_gflops, ai * peak_gbs)
        meas = r.get("measured_flops_per_s")
        out.append({
            "op": r["op"],
            "backend": r["backend"],
            "ai": ai,
            "bound": "compute" if ai >= ridge else "memory",
            "attainable_gflops": attainable,
            "measured_gflops": meas / 1e9 if meas else None,
            "roof_frac": (meas / 1e9) / attainable if meas and attainable else None,
        })
    if verbose:
        print(f"Kernel roofline (peak {peak_gflops:.0f} GFLOP/s, "
              f"{peak_gbs:.0f} GB/s, ridge AI {ridge:.1f} FLOP/B):")
        hdr = (f"  {'op':24s} {'backend':7s} {'AI':>8s} {'bound':>8s} "
               f"{'attain':>8s} {'meas':>8s} {'%roof':>6s}")
        print(hdr)
        for k in out:
            meas = f"{k['measured_gflops']:.2f}" if k["measured_gflops"] else "-"
            frac = f"{k['roof_frac']:.0%}" if k["roof_frac"] else "-"
            print(f"  {k['op']:24s} {k['backend']:7s} {k['ai']:8.2f} "
                  f"{k['bound']:>8s} {k['attainable_gflops']:8.2f} "
                  f"{meas:>8s} {frac:>6s}")
    return out


def kernel_scatter(rows: list[dict], peak_gflops: float, peak_gbs: float,
                   width: int = 60, height: int = 16) -> str:
    """ASCII roofline scatter: x = log10(arithmetic intensity), y = log10
    attainable GFLOP/s; '.' traces the roof, letters mark ledger points
    (legend below)."""
    import math

    pts = [(r["op"], r["backend"], r.get("arithmetic_intensity", 0.0))
           for r in rows if r.get("arithmetic_intensity", 0.0) > 0]
    if not pts:
        return "(no ledger points with nonzero arithmetic intensity)"
    ais = [p[2] for p in pts]
    x_lo = math.floor(math.log10(min(ais + [0.1])))
    x_hi = math.ceil(math.log10(max(ais + [peak_gflops / peak_gbs]))) + 1
    y_hi = math.log10(peak_gflops)
    y_lo = y_hi - 4  # four decades of GFLOP/s
    grid = [[" "] * width for _ in range(height)]

    def cell(ai):
        gx = (math.log10(ai) - x_lo) / (x_hi - x_lo)
        y = min(math.log10(max(min(peak_gflops, ai * peak_gbs), 1e-9)), y_hi)
        gy = (y - y_lo) / (y_hi - y_lo)
        col = min(max(int(gx * (width - 1)), 0), width - 1)
        row = min(max(int((1 - gy) * (height - 1)), 0), height - 1)
        return row, col

    for i in range(width):  # the roof itself
        ai = 10 ** (x_lo + i / (width - 1) * (x_hi - x_lo))
        r, c = cell(ai)
        grid[r][c] = "."
    legend = []
    for i, (op, backend, ai) in enumerate(sorted(pts, key=lambda p: p[2])):
        mark = chr(ord("a") + i % 26)
        r, c = cell(ai)
        grid[r][c] = mark
        legend.append(f"  {mark} = {op}/{backend} (AI {ai:.2f})")
    lines = ["attainable GFLOP/s (log) vs arithmetic intensity (log FLOP/B)"]
    lines += ["  |" + "".join(row) for row in grid]
    lines.append("  +" + "-" * width)
    lines += legend
    return "\n".join(lines)


def kernel_report(path: str, peak_gflops: float = 100.0,
                  peak_gbs: float = 50.0, verbose: bool = True) -> list[dict]:
    rows = load_ledger(path)
    out = kernel_table(rows, peak_gflops, peak_gbs, verbose=verbose)
    if verbose:
        print(kernel_scatter(rows, peak_gflops, peak_gbs))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--kernels", metavar="LEDGER_JSON",
                    help="kernel-level mode: roofline placement of cost-"
                         "ledger rows (bench_kernels --ledger-out artifact, "
                         "BENCH_train.json, or a bare row list)")
    ap.add_argument("--peak-gflops", type=float, default=100.0,
                    help="machine peak compute for the kernel roofline")
    ap.add_argument("--peak-gbs", type=float, default=50.0,
                    help="machine peak HBM bandwidth for the kernel roofline")
    a = ap.parse_args()
    if a.kernels:
        kernel_report(a.kernels, a.peak_gflops, a.peak_gbs)
    elif a.md:
        print(markdown(a.dir, a.mesh or "16x16"))
    else:
        run(dirpath=a.dir, mesh=a.mesh)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
