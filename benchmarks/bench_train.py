"""Training-step benchmark: the fused quantized-BPTT path vs the autodiff
baseline, measured — warm step time, tokens/s, and peak residual bytes.

No TPU in this container, so ``--backend pallas`` runs the kernels in
interpret mode (a correctness trajectory, not a speed claim); the ref
backend numbers are the CPU perf trajectory and what CI's bench-smoke job
records. Three measurements per (backend, seq) point, fused and baseline:

  warm_step_s     mean wall time per step EXCLUDING the first (compile) step
  tokens_per_s    batch * seq / warm_step_s
  residual_bytes  bytes of the saved forward->backward residuals, measured
                  by materializing jax.vjp and summing the closure leaves —
                  the quantity the recompute-gates backward contract shrinks
  temp_bytes      XLA's compiled-step temp allocation (memory_analysis)

Plus the acceptance trajectory: the fused loss curve must be bit-identical
across two runs on ref (deterministic recompute), and ref-vs-pallas
divergence over the measured steps is reported when --backend both.

A separate telemetry phase (``--telemetry-steps``, default 50; 0 skips)
runs a telemetry-enabled step with the in-kernel FP8 flush counters on
and records the quantization-health aggregate (FP8 saturation/underflow,
FloatSD carry/clamp, loss-scale events, per-layer grad norms) under the
``"telemetry"`` key of BENCH_train.json. It is deliberately NOT the
timed run: the perf numbers stay free of telemetry overhead.

    PYTHONPATH=src python benchmarks/bench_train.py --steps 30 --seq 128
    PYTHONPATH=src python benchmarks/bench_train.py --backend both --steps 5
    PYTHONPATH=src python benchmarks/bench_train.py --seqs 64,128,256
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def _build(vocab, emb, hidden, layers):
    from repro.models.lstm_models import WikiText2LM

    return WikiText2LM(vocab=vocab, emb=emb, hidden=hidden, n_layers=layers)


def _batches(batch, seq, vocab, seed=0):
    from repro.data import synthetic

    return synthetic.wikitext2(batch=batch, seq=seq, vocab=vocab, seed=seed).batches


def residual_bytes(model, params, batch, policy):
    """Bytes of forward residuals saved for the backward pass: materialize
    the VJP eagerly and sum its closure leaves. Under the fused cell VJP
    only (z, c_prev) per step survive; under remat only the carry."""
    _, vjp_fn = jax.vjp(lambda p: model.loss(p, batch, policy), params)
    return int(sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(vjp_fn)
        if hasattr(l, "size")
    ))


def _measure(model, policy, batch_iter, batch_dims, steps, fused, backend,
             seed=0):
    """One (variant, backend) measurement; returns metrics + loss curve."""
    from repro.kernels import dispatch as kd
    from repro.optim import sgd
    from repro.optim.train_state import init_state, make_train_step

    b, s = batch_dims
    opt = sgd(0.9)
    params = model.init(jax.random.PRNGKey(seed))

    with kd.use_backend(backend):
        state = init_state(params, opt, policy)
        step_fn = make_train_step(model.loss, opt, policy, lr=0.5, fused=fused,
                                  donate=True)
        batches = [
            {k: jnp.asarray(v) for k, v in next(batch_iter).items()}
            for _ in range(steps)
        ]
        t0 = time.perf_counter()
        state, m = step_fn(state, batches[0])
        jax.block_until_ready(m["loss"])
        compile_s = time.perf_counter() - t0
        losses = [float(m["loss"])]
        ts = []
        for bt in batches[1:]:
            t1 = time.perf_counter()
            state, m = step_fn(state, bt)
            losses.append(float(m["loss"]))  # host sync per step
            ts.append(time.perf_counter() - t1)
        # median: robust to scheduler noise on a shared container
        warm = float(np.median(ts)) if ts else compile_s

        # residual footprint (not timed; eager vjp on one batch)
        run_policy = (
            policy.replace(grad_quant="fp8_kernel")
            if fused and policy.grad_quant == "fp8"
            else policy
        )
        res_bytes = residual_bytes(model, params, batches[0], run_policy)

        # XLA temp allocation of the compiled step (secondary; CPU backend)
        try:
            state2 = init_state(params, opt, policy)
            comp = step_fn.lower(state2, batches[0]).compile()
            ma = comp.memory_analysis()
            temp_bytes = int(ma.temp_size_in_bytes) if ma else None
        except Exception:
            temp_bytes = None

    return {
        "compile_s": round(compile_s, 3),
        "warm_step_s": round(warm, 4),
        "tokens_per_s": round(b * s / warm, 1),
        "residual_bytes": res_bytes,
        "temp_bytes": temp_bytes,
        "losses": [round(l, 6) for l in losses],
    }


def _telemetry_run(model, policy, batch_iter, steps, seed=0):
    """Quantization-health pass: telemetry-enabled step + kernel FP8 flush
    counters over ``steps`` steps on the ref backend. Separate from the
    timed measurement so those numbers stay telemetry-free."""
    from repro.obs.telemetry import KERNEL_STATS, TelemetryLogger
    from repro.optim import sgd
    from repro.optim.train_state import init_state, make_train_step

    opt = sgd(0.9)
    params = model.init(jax.random.PRNGKey(seed))
    state = init_state(params, opt, policy)
    KERNEL_STATS.reset()
    KERNEL_STATS.enable()  # trace-time gate: before the first step compiles
    try:
        step_fn = make_train_step(model.loss, opt, policy, lr=0.5,
                                  donate=True, telemetry=True)
        logger = TelemetryLogger()
        for i in range(1, steps + 1):
            bt = {k: jnp.asarray(v) for k, v in next(batch_iter).items()}
            state, m = step_fn(state, bt)
            logger.update(i, m)
        rec = logger.emit(steps)
    finally:
        KERNEL_STATS.disable()
    return rec.to_dict()


def run(backends=("ref",), seqs=(128,), steps=10, batch=16, vocab=2048,
        emb=256, hidden=256, layers=2, policy_name="floatsd8_table6",
        out=None, verbose=True, telemetry_steps=50):
    from repro.core.policy import get_policy
    from repro.kernels import dispatch as kd

    policy = get_policy(policy_name)
    model = _build(vocab, emb, hidden, layers)
    # fresh cost ledger for this run: the report carries the predicted
    # per-(op, backend) totals the training steps traced (no wall feed —
    # per-op wall attribution is only honest in bench_kernels' one-op
    # timed regions)
    kd.STATS.reset()
    results = []
    for seq in seqs:
        for backend in backends:
            fused = _measure(model, policy, _batches(batch, seq, vocab),
                             (batch, seq), steps, True, backend)
            base = _measure(model, policy, _batches(batch, seq, vocab),
                            (batch, seq), steps, False, backend)
            # determinism: same init, same data -> bit-identical curve
            rerun = _measure(model, policy, _batches(batch, seq, vocab),
                             (batch, seq), min(steps, 5), True, backend)
            deterministic = rerun["losses"] == fused["losses"][: len(rerun["losses"])]
            entry = {
                "backend": backend,
                "seq": seq,
                "batch": batch,
                "fused": fused,
                "baseline": base,
                "speedup": round(base["warm_step_s"] / fused["warm_step_s"], 3),
                "residual_ratio": round(
                    base["residual_bytes"] / max(fused["residual_bytes"], 1), 3
                ),
                "deterministic": deterministic,
            }
            results.append(entry)
            if verbose:
                print(
                    f"[{backend:6s} seq={seq:4d}] warm {base['warm_step_s']*1e3:8.1f}ms -> "
                    f"{fused['warm_step_s']*1e3:8.1f}ms  ({entry['speedup']:.2f}x)  "
                    f"residuals {base['residual_bytes']/2**20:7.2f}MiB -> "
                    f"{fused['residual_bytes']/2**20:7.2f}MiB  "
                    f"({entry['residual_ratio']:.2f}x)  deterministic={deterministic}",
                    flush=True,
                )
    # cross-backend loss divergence (the pallas-interpret acceptance bound)
    divergence = {}
    by_key = {(r["backend"], r["seq"]): r for r in results}
    for seq in seqs:
        if ("ref", seq) in by_key and ("pallas", seq) in by_key:
            a = np.asarray(by_key[("ref", seq)]["fused"]["losses"])
            c = np.asarray(by_key[("pallas", seq)]["fused"]["losses"])
            n = min(a.size, c.size)
            rel = float(np.max(np.abs(a[:n] - c[:n]) / np.maximum(np.abs(a[:n]), 1e-9)))
            divergence[str(seq)] = rel
            if verbose:
                print(f"[seq={seq}] ref vs pallas-interpret max relative "
                      f"loss divergence over {n} steps: {rel:.2e}", flush=True)
    report = {
        "bench": "bench_train",
        "task": "wikitext2-synthetic",
        "model": {"vocab": vocab, "emb": emb, "hidden": hidden,
                  "layers": layers},
        "policy": policy_name,
        "steps": steps,
        # mirror nn/lstm.BPTT_REMAT's default (env unset -> remat ON)
        "remat": os.environ.get("REPRO_BPTT_REMAT", "1") != "0",
        "results": results,
        "ref_vs_pallas_loss_divergence": divergence,
        "ledger": kd.LEDGER.rows(),
    }
    if telemetry_steps > 0:
        tel = _telemetry_run(
            model, policy, _batches(batch, seqs[0], vocab),
            telemetry_steps,
        )
        report["telemetry"] = tel
        if verbose:
            k = tel.get("kernel", {}).get("floatsd_matmul_dw", {})
            print(
                f"[telemetry {telemetry_steps} steps] fp8 sat "
                f"{tel['fp8_sat_frac']:.2e} under {tel['fp8_underflow_frac']:.2e} "
                f"zero {tel['fp8_zero_frac']:.3f} | sd carry "
                f"{tel['sd_carry_frac']:.3f} clamp {tel['sd_clamp_frac']:.2e} | "
                f"scale {tel['loss_scale']:.0f} "
                f"({tel['nonfinite_steps']} skipped) | kernel dw flushes "
                f"{k.get('calls', 0)} (zero_frac {k.get('zero_frac', 0):.3f})",
                flush=True,
            )
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        if verbose:
            print(f"wrote {out}", flush=True)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas", "both"])
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seqs", default=None,
                    help="comma-separated seq sweep (overrides --seq)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--emb", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--policy", default="floatsd8_table6")
    ap.add_argument("--out", default="BENCH_train.json")
    ap.add_argument("--telemetry-steps", type=int, default=50,
                    help="steps for the quantization-health telemetry pass "
                    "(0 skips it; never part of the timed measurement)")
    a = ap.parse_args()
    backends = ("ref", "pallas") if a.backend == "both" else (a.backend,)
    seqs = tuple(int(s) for s in a.seqs.split(",")) if a.seqs else (a.seq,)
    run(backends=backends, seqs=seqs, steps=a.steps, batch=a.batch,
        vocab=a.vocab, emb=a.emb, hidden=a.hidden, layers=a.layers,
        policy_name=a.policy, out=a.out, telemetry_steps=a.telemetry_steps)


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
