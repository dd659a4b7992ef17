"""Kernel-level microbenchmarks.

No TPU in this container, so wall-clock numbers are CPU-only sanity checks;
the TPU-relevant outputs are the *analytic* per-kernel roofline terms:

  floatsd_matmul : HBM bytes for FloatSD8-coded weights vs bf16 weights
                   (the 2x weight-traffic claim) + VMEM working set of the
                   chosen BlockSpec tiling.
  lstm_cell      : HBM round-trips fused vs unfused (the fusion claim).

Wall-clock compares the pure-jnp oracle paths under jit on CPU, verifying
the quantized path's overhead structure (decode+matmul vs plain matmul).

The ``--backend`` axis measures the DISPATCHED path (what nn/serving hot
paths actually run) per backend, so the ref-vs-pallas delta is measured,
not assumed:

    PYTHONPATH=src python benchmarks/bench_kernels.py --backend both
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import floatsd, floatsd4
from repro.kernels import dispatch as kd
from repro.kernels.floatsd_matmul.ref import floatsd_matmul_ref
from repro.kernels.lstm_cell.ref import lstm_cell_ref


def _time(f, *args, iters=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(verbose: bool = True) -> dict:
    M, K, N = 512, 2048, 2048
    bm, bn, bk = 256, 256, 512
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32) * 0.05)
    codes, bias = floatsd.encode(w)

    # analytic: weight bytes per matmul (the HBM-traffic claim, DESIGN.md 3.1)
    bytes_bf16 = K * N * 2
    bytes_fsd8 = K * N * 1 + 4  # codes + one int32 bias
    # FloatSD4: 2 codes/byte along K + one int8 exponent per 32-row group
    bytes_fsd4 = -(-K // 2) * N + -(-K // floatsd4.GROUP) * N
    vmem_ws = bm * bk * 1 + bk * bn * 1 + bm * bn * 4  # x-codes-acc tile set

    f_q = jax.jit(lambda x, c, b: floatsd_matmul_ref(x, c, b))
    f_d = jax.jit(lambda x, w: jnp.dot(x, w))
    t_q = _time(f_q, x, codes, bias)
    t_d = _time(f_d, x, w)

    B, H = 256, 1024
    z = jnp.asarray(rng.standard_normal((B, 4 * H)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((B, H)).astype(np.float32))
    f_cell = jax.jit(lambda z, c: lstm_cell_ref(z, c, True))
    t_cell = _time(f_cell, z, c)
    # fused: read z (4H) + c (H), write h (H) + c (H)  = 7H per row
    # unfused XLA: each of sigmoid x3 / tanh x2 / mul x3 / add x1 round-trips
    hbm_fused = B * (4 * H + 3 * H) * 4
    hbm_unfused = B * H * 4 * (4 + 3 * 2 + 2 * 2 + 3 * 2 + 1 * 2)  # op-by-op r/w

    out = {
        "matmul_weight_bytes_bf16": bytes_bf16,
        "matmul_weight_bytes_floatsd8": bytes_fsd8,
        "matmul_weight_bytes_floatsd4": bytes_fsd4,
        "weight_traffic_ratio": round(bytes_bf16 / bytes_fsd8, 3),
        "weight_traffic_ratio_fsd4": round(bytes_bf16 / bytes_fsd4, 3),
        "vmem_working_set_bytes": vmem_ws,
        "cpu_ms_floatsd_matmul_oracle": round(t_q * 1e3, 2),
        "cpu_ms_dense_matmul": round(t_d * 1e3, 2),
        "lstm_cell_hbm_bytes_fused": hbm_fused,
        "lstm_cell_hbm_bytes_unfused": hbm_unfused,
        "lstm_cell_fusion_traffic_ratio": round(hbm_unfused / hbm_fused, 2),
        "cpu_ms_lstm_cell_oracle": round(t_cell * 1e3, 2),
    }
    if verbose:
        print(f"  floatsd_matmul [{M}x{K}x{N}] weight HBM bytes: "
              f"bf16 {bytes_bf16/2**20:.1f}MiB -> fsd8 {bytes_fsd8/2**20:.1f}MiB "
              f"({out['weight_traffic_ratio']}x) -> fsd4 "
              f"{bytes_fsd4/2**20:.1f}MiB ({out['weight_traffic_ratio_fsd4']}x)")
        print(f"    VMEM working set ({bm},{bn},{bk}) tiling: {vmem_ws/2**20:.2f} MiB (<16 MiB)")
        print(f"    CPU oracle: quantized {out['cpu_ms_floatsd_matmul_oracle']}ms "
              f"vs dense {out['cpu_ms_dense_matmul']}ms")
        print(f"  lstm_cell [B={B},H={H}] HBM traffic fused/unfused: "
              f"{hbm_fused/2**20:.1f}/{hbm_unfused/2**20:.1f} MiB "
              f"({out['lstm_cell_fusion_traffic_ratio']}x saved)  "
              f"CPU oracle {out['cpu_ms_lstm_cell_oracle']}ms")
    return out


def run_dispatch(backend: str, *, m=256, k=512, n=512, b=64, h=512,
                 iters: int = 3, verbose: bool = True, reset: bool = True) -> dict:
    """Time the dispatched hot-path ops under one backend and report the
    resolver's decisions. On CPU the pallas numbers are interpret-mode
    (validation, not speed); on TPU they are the compiled kernels.

    Measured wall-time is fed back into the dispatch stats
    (``STATS.add_time``) so the cost ledger can join predicted FLOPs/bytes
    with a measured rate — microbenchmark granularity is the only place
    per-op wall attribution is honest (one op per timed region)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32) * 0.05)
    codes, bias = floatsd.encode(w)
    w4 = kd.pack4(w)
    z = jnp.asarray(rng.standard_normal((b, 4 * h)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((b, h)).astype(np.float32))

    out = {"backend": backend}
    with kd.use_backend(backend):
        if reset:
            kd.STATS.reset()
        # jit the dispatched call like the real hot paths do (the resolver
        # runs at trace time, under this backend context)
        t_mm = _time(jax.jit(lambda a: kd.matmul(a, codes, bias)), x, iters=iters)
        d_mm = kd.STATS.last["floatsd_matmul"]
        kd.STATS.add_time("floatsd_matmul", d_mm.backend, t_mm)
        t_mm4 = _time(jax.jit(lambda a: kd.matmul4(a, w4)), x, iters=iters)
        d_mm4 = kd.STATS.last["floatsd4_matmul"]
        kd.STATS.add_time("floatsd4_matmul", d_mm4.backend, t_mm4)
        t_cell = _time(jax.jit(lambda zz: kd.lstm_cell(zz, c)), z, iters=iters)
        d_cell = kd.STATS.last["lstm_cell"]
        kd.STATS.add_time("lstm_cell", d_cell.backend, t_cell)
    out.update(
        ms_matmul=round(t_mm * 1e3, 2),
        ms_matmul4=round(t_mm4 * 1e3, 2),
        ms_lstm_cell=round(t_cell * 1e3, 2),
        matmul_ran=d_mm.backend,
        matmul4_ran=d_mm4.backend,
        lstm_cell_ran=d_cell.backend,
        interpret=d_mm.interpret,
    )
    if verbose:
        mode = " (interpret)" if d_mm.backend == "pallas" and d_mm.interpret else ""
        print(f"  [{backend:6}] matmul[{m}x{k}x{n}] {out['ms_matmul']:>8}ms "
              f"ran={d_mm.backend}{mode} | matmul4 {out['ms_matmul4']:>8}ms "
              f"ran={d_mm4.backend} | lstm_cell[B={b},H={h}] "
              f"{out['ms_lstm_cell']:>8}ms ran={d_cell.backend}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["ref", "pallas", "auto", "both"],
                    default="both",
                    help="dispatch backend to measure; 'both' reports the "
                         "ref-vs-pallas delta")
    ap.add_argument("--mkn", type=int, nargs=3, default=[256, 512, 512],
                    metavar=("M", "K", "N"))
    ap.add_argument("--bh", type=int, nargs=2, default=[64, 512],
                    metavar=("B", "H"))
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--ledger", action="store_true",
                    help="print the predicted-vs-measured cost ledger "
                         "accumulated across the dispatched runs")
    ap.add_argument("--ledger-out", metavar="PATH",
                    help="dump the cost ledger as JSON (check_bench.py "
                         "input / CI artifact)")
    args = ap.parse_args()

    run()
    m, k, n = args.mkn
    b, h = args.bh
    print("dispatched hot-path ops per backend:")
    backends = ["ref", "pallas"] if args.backend == "both" else [args.backend]
    want_ledger = args.ledger or args.ledger_out
    if want_ledger:
        kd.STATS.reset()  # one ledger across all backends, reset once
    rows = [
        run_dispatch(be, m=m, k=k, n=n, b=b, h=h, iters=args.iters,
                     reset=not want_ledger)
        for be in backends
    ]
    if len(rows) == 2:
        r, p = rows
        print(f"  ref-vs-pallas delta: matmul {p['ms_matmul']/max(r['ms_matmul'],1e-9):.2f}x, "
              f"matmul4 {p['ms_matmul4']/max(r['ms_matmul4'],1e-9):.2f}x, "
              f"lstm_cell {p['ms_lstm_cell']/max(r['ms_lstm_cell'],1e-9):.2f}x "
              f"({'interpret-mode validation, not speed' if p['interpret'] else 'compiled'})")
    if args.ledger:
        print("\ncost ledger (predicted analytical vs measured):")
        print(kd.LEDGER.table())
    if args.ledger_out:
        kd.LEDGER.dump(args.ledger_out, meta={
            "source": "bench_kernels", "mkn": [m, k, n], "bh": [b, h],
            "iters": args.iters, "backends": backends,
        })
        print(f"ledger JSON written to {args.ledger_out}")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
