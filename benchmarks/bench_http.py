"""End-to-end HTTP serving benchmark: open-loop load through a real socket.

The first benchmark that exercises the ENTIRE stack across a network
boundary — packed FloatSD8 codes → dispatched kernels → batching engine →
FP8 prefix cache → router → HTTP/SSE server → TCP → asyncio client — and
measures what a caller actually sees: TTFT (submit → first SSE token),
TPOT (mean inter-token gap), and wall-clock throughput.

Arrivals are **open-loop**: request *i* fires at ``i / rate`` seconds
regardless of completions (closed-loop clients hide queueing delay by
self-throttling; open-loop is the honest way to measure a service under
a target arrival rate). Every request is measured through
``/v1/stream`` so the per-token timestamps are client-side arrival
times, identical methodology for the in-process baseline.

Phases (``--workload all``, the default, runs every one):

* ``inproc_uniform`` — the same open-loop workload driven directly on
  ``AsyncRouter.stream`` (no socket). The HTTP delta vs this baseline is
  the cost of the network boundary.
* ``http_uniform``   — same prompts over the socket; asserts 100% token
  agreement with the in-process run (fresh identical routers, greedy
  decoding).
* ``http_zipf_cold`` / ``http_zipf_warm`` — shared-system-prompt
  workload (``zipf_prefix_prompts``) served cold (no cache) vs through a
  pre-warmed FP8 prefix cache; prefill-step counts are scraped from the
  server's own ``/metrics`` endpoint, and warm-vs-cold token agreement
  is asserted (the model is briefly pretrained so greedy margins are
  decisive — see bench_serving.py).
* ``http_zipf_warm_stress`` / ``http_zipf_warm_v2`` — the warm-tail
  experiment (EXPERIMENTS hillclimb #6 measured warm p95 TTFT *worse*
  than cold under load: skipping prefill admits the zipf head faster
  than lanes drain it). Both phases replay the warm workload at
  ``--stress-rate`` arrivals on identically re-warmed caches; stress is
  the FIFO baseline, v2 runs scheduler v2 (``sjf_work``
  remaining-work-first admission on router and engines + lane
  preemption enabled). The v2-vs-cold token agreement assertion keeps
  the scheduling change honest: reordering and FP8 snapshot restores
  must not flip a single greedy token.

Every HTTP request streams with ``debug=True``, so the terminal SSE
``done`` event carries the server-side phase breakdown
(queue/prefill/decode ms + cache savings) — summarized per phase as
TTFT-decomposition columns, which is what turns "warm p95 improved"
into "warm p95 improved because prefill_ms collapsed". Each HTTP phase
also pulls ``GET /admin/trace`` before drain; ``--trace-out`` writes the
last one (the warm zipf phase under the default workload) as
Perfetto-loadable Chrome trace-event JSON.

Writes ``BENCH_http.json`` (tracked in EXPERIMENTS.md hillclimb #6):

    PYTHONPATH=src python benchmarks/bench_http.py --requests 24 --rate 8
    PYTHONPATH=src python benchmarks/bench_http.py --workload zipf-prefix
"""
from __future__ import annotations

import argparse
import asyncio
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import get_policy
from repro.models.lstm_models import WikiText2LM
from repro.serving import (
    PrefixCache,
    Router,
    synthetic_prompts,
    zipf_prefix_prompts,
)
from repro.serving.frontend import AsyncRouter
from repro.serving.http import Client, HttpError, HttpServer


def pretrain(model, policy, steps, seed=0):
    """Brief pretrain for decisive greedy margins (see bench_serving)."""
    from repro.data import synthetic
    from repro.optim import sgd
    from repro.optim.train_state import init_state, make_train_step

    data = synthetic.wikitext2(batch=32, seq=24, vocab=model.vocab)
    opt = sgd(0.9)
    state = init_state(model.init(jax.random.PRNGKey(seed)), opt, policy)
    step_fn = jax.jit(make_train_step(model.loss, opt, policy, lr=1.0))
    for _ in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(data.batches).items()}
        state, _ = step_fn(state, batch)
    return state.params


def build_router(model, params, policy, args, cache=None, max_queue=None,
                 admission="fifo", engine_kw=None):
    return Router.build(
        model, params, policy,
        replicas=args.replicas,
        prefix_cache=cache,
        router_kw=dict(
            admission=admission,
            max_queue=max_queue if max_queue is not None else args.requests,
        ),
        lanes=args.batch,
        chunk=args.chunk,
        **(engine_kw or {}),
    )


# -- measurement core -------------------------------------------------------


async def _fire(delay, coro):
    await asyncio.sleep(delay)
    return await coro


def _record(t_submit, toks, times):
    return {"t_submit": t_submit, "tokens": toks, "times": times}


def _warm_prompt(chunk):
    """Throwaway request that compiles both jitted step shapes (a prompt
    wider than one chunk exercises S=chunk prefill AND S=1 decode) so the
    measured TTFTs are serving latency, not XLA compile time. The token
    value 1 repeated never collides with sampled workload prefixes."""
    return np.ones(chunk + 2, np.int32)


async def run_inproc_phase(router, prompts, rate, max_new, tenants, chunk):
    """Open-loop arrivals driven straight on AsyncRouter.stream."""
    ar = AsyncRouter(router)
    await ar.generate(_warm_prompt(chunk), max_new=2)

    async def one(i, prompt):
        t_submit = time.monotonic()
        toks, times = [], []
        async for tok in ar.stream(
            prompt, max_new=max_new, tenant=f"tenant{i % tenants}"
        ):
            toks.append(int(tok))
            times.append(time.monotonic())
        return _record(t_submit, toks, times)

    t0 = time.monotonic()
    results = await asyncio.gather(
        *(
            asyncio.create_task(_fire(i / rate, one(i, p)))
            for i, p in enumerate(prompts)
        )
    )
    return results, time.monotonic() - t0, None, None


async def run_http_phase(router, prompts, rate, max_new, tenants, chunk):
    """Open-loop arrivals through a real ephemeral-port TCP socket. The
    returned counters are scraped from the server's own /metrics endpoint,
    diffed around the measurement window so the jit-warmup request is
    excluded; the returned trace is Chrome trace-event JSON pulled from
    /admin/trace before drain, cleared after warmup so it covers exactly
    the measurement window."""
    server = await HttpServer(router, port=0).start()
    serve_task = asyncio.create_task(server.serve_forever())
    admin = Client(server.host, server.port)
    await admin.generate(_warm_prompt(chunk), max_new=2)  # compile via socket
    baseline = _scrape_counters(await admin.metrics())
    from repro.obs.trace import TRACER

    TRACER.clear()  # trace the measurement window, not the warmup

    async def one(i, prompt):
        t_submit = time.monotonic()
        toks, times, phases = [], [], None
        try:
            async with Client(
                server.host, server.port, tenant=f"tenant{i % tenants}"
            ) as c:
                async for ev, data in c.stream(
                    prompt, max_new=max_new, debug=True
                ):
                    if ev == "message":
                        toks.append(data["token"])
                        times.append(time.monotonic())
                    elif ev == "done":
                        phases = data.get("phases")
        except HttpError as e:
            # summarize() derives the rejected count from empty `times`
            return {"t_submit": t_submit, "tokens": [], "times": [],
                    "rejected": e.body.get("error", e.status)}
        rec = _record(t_submit, toks, times)
        rec["phases"] = phases
        return rec

    t0 = time.monotonic()
    results = await asyncio.gather(
        *(
            asyncio.create_task(_fire(i / rate, one(i, p)))
            for i, p in enumerate(prompts)
        )
    )
    wall = time.monotonic() - t0
    final = _scrape_counters(await admin.metrics())  # BEFORE drain shuts us down
    trace = await admin.trace()
    await admin.drain()
    await admin.close()
    await asyncio.wait_for(serve_task, timeout=120)
    counters = {k: final[k] - baseline.get(k, 0) for k in final}
    return results, wall, counters, trace


_COUNTERS = (
    ("prefill_steps", "repro_prefill_steps_total"),
    ("decode_steps", "repro_decode_steps_total"),
    ("cache_hits", "repro_cache_hits_total"),
    ("prefill_tokens_saved", "repro_prefill_tokens_saved_total"),
    # replica-health counters: 0 everywhere except the chaos phase
    ("ejections", "repro_replica_ejections_total"),
    ("resubmits", "repro_resubmits_total"),
    ("retries", "repro_retries_total"),
    ("numeric_errors", "repro_numeric_errors_total"),
)


def _scrape_counters(metrics_text):
    out = {}
    for key, metric in _COUNTERS:
        m = re.search(rf"^{metric} ([0-9.e+]+)$", metrics_text, re.M)
        out[key] = int(float(m.group(1))) if m else 0
    return out


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


def summarize(results, wall, counters=None):
    served = [r for r in results if r["times"]]
    ttfts = [r["times"][0] - r["t_submit"] for r in served]
    tpots = [
        (r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1)
        for r in served
        if len(r["times"]) > 1
    ]
    n_tokens = sum(len(r["tokens"]) for r in served)
    out = {
        "requests": len(results),
        "served": len(served),
        "rejected": len(results) - len(served),
        "wall_s": round(wall, 3),
        "gen_tokens": n_tokens,
        "gen_tok_per_s": round(n_tokens / wall, 2) if wall else 0.0,
        "ttft_p50_ms": round(_pct(ttfts, 50) * 1e3, 2),
        "ttft_p95_ms": round(_pct(ttfts, 95) * 1e3, 2),
        "ttft_mean_ms": round(float(np.mean(ttfts)) * 1e3, 2) if ttfts else 0.0,
        "tpot_mean_ms": round(float(np.mean(tpots)) * 1e3, 2) if tpots else 0.0,
        "tpot_p95_ms": round(_pct(tpots, 95) * 1e3, 2),
    }
    if counters is not None:
        out.update(counters)
    # server-side TTFT decomposition (debug=True phase breakdowns): where
    # did the time go — queued behind other requests, prefilling, decoding?
    breakdown = [r["phases"] for r in served if r.get("phases")]
    if breakdown:
        for key in ("queue_ms", "prefill_ms", "decode_ms"):
            vals = [b[key] for b in breakdown]
            out[f"{key[:-3]}_p50_ms"] = round(_pct(vals, 50), 2)
            out[f"{key[:-3]}_p95_ms"] = round(_pct(vals, 95), 2)
        out["cache_hit_requests"] = sum(bool(b["cache_hit"]) for b in breakdown)
        out["cache_saved_steps"] = sum(b["cache_saved_steps"] for b in breakdown)
    return out


def tokens_of(results):
    return [tuple(r["tokens"]) for r in results]


def agreement(a, b):
    return sum(x == y for x, y in zip(a, b)) / max(len(a), 1)


def print_phase(name, s):
    extra = ""
    if "prefill_steps" in s:
        extra = (f" | prefill {s['prefill_steps']} decode {s['decode_steps']}"
                 f" | cache hits {s.get('cache_hits', 0)}"
                 f" saved {s.get('prefill_tokens_saved', 0)} tok")
    print(
        f"{name:18} {s['served']}/{s['requests']} served in {s['wall_s']:6.1f}s"
        f" | ttft p50 {s['ttft_p50_ms']:7.1f}ms p95 {s['ttft_p95_ms']:7.1f}ms"
        f" | tpot {s['tpot_mean_ms']:6.1f}ms"
        f" | {s['gen_tok_per_s']:6.1f} gen tok/s{extra}",
        flush=True,
    )
    if "queue_p95_ms" in s:
        print(
            f"{'':18} breakdown p50/p95:"
            f" queue {s['queue_p50_ms']:6.1f}/{s['queue_p95_ms']:6.1f}ms"
            f" | prefill {s['prefill_p50_ms']:6.1f}/{s['prefill_p95_ms']:6.1f}ms"
            f" | decode {s['decode_p50_ms']:6.1f}/{s['decode_p95_ms']:6.1f}ms"
            f" | cache-hit reqs {s['cache_hit_requests']}"
            f" (saved {s['cache_saved_steps']} steps)",
            flush=True,
        )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=8.0,
                    help="open-loop arrival rate (requests/s)")
    ap.add_argument("--stress-rate", type=float, default=24.0,
                    help="arrival rate for the warm-tail stress phases "
                    "(fast enough that warm admissions outpace lane drain)")
    ap.add_argument("--batch", type=int, default=4, help="lanes per replica")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=4000)
    ap.add_argument("--d-model", type=int, default=192)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pretrain-steps", type=int, default=200,
                    help="zipf phases: pretrain for decisive greedy margins")
    ap.add_argument("--workload",
                    choices=["uniform", "zipf-prefix", "chaos", "all"],
                    default="all",
                    help="'chaos' (opt-in, not part of 'all') replays the "
                    "zipf workload at --stress-rate on 2 replicas, kills "
                    "replica 1 mid-run via a seeded fault plan, and "
                    "asserts zero lost requests + 100%% token agreement "
                    "with an identical fault-free reference run")
    ap.add_argument("--crash-at", type=int, default=5,
                    help="chaos workload: replica 1 crashes on its Nth "
                    "device step")
    ap.add_argument("--out", default="BENCH_http.json")
    ap.add_argument("--trace-out", default="BENCH_http_trace.json",
                    help="write the last HTTP phase's /admin/trace export "
                    "(Chrome trace-event JSON; open in Perfetto); '' skips")
    args = ap.parse_args()

    policy = get_policy("floatsd8_table6")
    model = WikiText2LM(
        vocab=args.vocab, emb=args.d_model, hidden=args.d_model, n_layers=2
    )
    rng = np.random.default_rng(args.seed)
    phases: dict = {}
    agree: dict = {}
    last_trace = None

    def run(phase_coro):
        return asyncio.run(phase_coro)

    if args.workload in ("uniform", "all"):
        params = model.init(jax.random.PRNGKey(args.seed))
        prompts = synthetic_prompts(args.requests, args.vocab, rng)

        print(f"== uniform workload: {args.requests} requests @ "
              f"{args.rate}/s, max_new={args.max_new} ==", flush=True)
        results, wall, _, _ = run(
            run_inproc_phase(
                build_router(model, params, policy, args),
                prompts, args.rate, args.max_new, args.tenants, args.chunk,
            )
        )
        phases["inproc_uniform"] = summarize(results, wall)
        inproc_tokens = tokens_of(results)
        print_phase("inproc_uniform", phases["inproc_uniform"])

        results, wall, counters, last_trace = run(
            run_http_phase(
                build_router(model, params, policy, args),
                prompts, args.rate, args.max_new, args.tenants, args.chunk,
            )
        )
        phases["http_uniform"] = summarize(results, wall, counters)
        print_phase("http_uniform", phases["http_uniform"])
        agree["http_vs_inproc"] = agreement(tokens_of(results), inproc_tokens)
        print(f"token agreement http vs in-process: "
              f"{agree['http_vs_inproc']:.0%}", flush=True)

    if args.workload in ("zipf-prefix", "all"):
        print(f"== zipf-prefix workload: pretraining "
              f"{args.pretrain_steps} steps ==", flush=True)
        params = pretrain(model, policy, args.pretrain_steps, seed=args.seed)
        wkw = dict(
            n_prefixes=4, prefix_len=3 * args.chunk, suffix_lo=2,
            suffix_hi=args.chunk + 2, prefix_seed=args.seed,
        )
        warmup = zipf_prefix_prompts(
            args.requests, args.vocab, np.random.default_rng(args.seed + 1), **wkw
        )
        measure = zipf_prefix_prompts(
            args.requests, args.vocab, np.random.default_rng(args.seed + 2), **wkw
        )
        results, wall, counters, _ = run(
            run_http_phase(
                build_router(model, params, policy, args),
                measure, args.rate, args.max_new, args.tenants, args.chunk,
            )
        )
        phases["http_zipf_cold"] = summarize(results, wall, counters)
        cold_tokens = tokens_of(results)
        print_phase("http_zipf_cold", phases["http_zipf_cold"])

        def warmed_cache():
            """Fresh, identically-populated cache per phase: reusing one
            cache would let later phases profit from entries the earlier
            measured runs inserted, corrupting the A/B."""
            cache = PrefixCache(block=args.chunk)
            warm_pass = build_router(model, params, policy, args, cache=cache)
            for p in warmup:  # populate: same system prompts, fresh suffixes
                warm_pass.submit(p, max_new=args.max_new)
            warm_pass.drain()
            return cache

        results, wall, counters, last_trace = run(
            run_http_phase(
                build_router(model, params, policy, args, cache=warmed_cache()),
                measure, args.rate, args.max_new, args.tenants, args.chunk,
            )
        )
        phases["http_zipf_warm"] = summarize(results, wall, counters)
        print_phase("http_zipf_warm", phases["http_zipf_warm"])
        agree["warm_vs_cold"] = agreement(tokens_of(results), cold_tokens)
        saved = 1 - (
            phases["http_zipf_warm"]["prefill_steps"]
            / max(phases["http_zipf_cold"]["prefill_steps"], 1)
        )
        print(
            f"warm cache over HTTP: {saved:.0%} fewer prefill steps, "
            f"token agreement warm vs cold {agree['warm_vs_cold']:.0%}",
            flush=True,
        )

        # -- warm-tail stress A/B: FIFO baseline vs scheduler v2 --------
        print(f"== warm-tail stress: {args.requests} requests @ "
              f"{args.stress_rate}/s ==", flush=True)
        results, wall, counters, _ = run(
            run_http_phase(
                build_router(model, params, policy, args, cache=warmed_cache()),
                measure, args.stress_rate, args.max_new, args.tenants,
                args.chunk,
            )
        )
        phases["http_zipf_warm_stress"] = summarize(results, wall, counters)
        print_phase("http_zipf_warm_stress", phases["http_zipf_warm_stress"])

        v2_router = build_router(
            model, params, policy, args, cache=warmed_cache(),
            # router and engines share the policy so the engines'
            # preemption peek compares against the ordering the router
            # dispatches under (same pairing as launch/serve --preempt)
            admission="sjf_work",
            engine_kw=dict(admission="sjf_work", preempt=True),
        )
        results, wall, counters, last_trace = run(
            run_http_phase(
                v2_router, measure, args.stress_rate, args.max_new,
                args.tenants, args.chunk,
            )
        )
        phases["http_zipf_warm_v2"] = summarize(results, wall, counters)
        print_phase("http_zipf_warm_v2", phases["http_zipf_warm_v2"])
        agree["warm_v2_vs_cold"] = agreement(tokens_of(results), cold_tokens)
        print(
            f"scheduler v2 at {args.stress_rate}/s: warm p95 TTFT "
            f"{phases['http_zipf_warm_stress']['ttft_p95_ms']:.1f}ms (fifo) "
            f"-> {phases['http_zipf_warm_v2']['ttft_p95_ms']:.1f}ms "
            f"(sjf_work+preempt), token agreement v2 vs cold "
            f"{agree['warm_v2_vs_cold']:.0%}",
            flush=True,
        )

    if args.workload == "chaos":
        import copy

        from repro.faults import FAULTS

        cargs = copy.copy(args)
        cargs.replicas = max(2, args.replicas)  # someone must survive
        print(f"== chaos workload: {args.requests} requests @ "
              f"{args.stress_rate}/s on {cargs.replicas} replicas, "
              f"replica 1 crashes on step {args.crash_at} ==", flush=True)
        params = pretrain(model, policy, args.pretrain_steps, seed=args.seed)
        prompts = zipf_prefix_prompts(
            args.requests, args.vocab, np.random.default_rng(args.seed + 2),
            n_prefixes=4, prefix_len=3 * args.chunk, suffix_lo=2,
            suffix_hi=args.chunk + 2, prefix_seed=args.seed,
        )
        # fault-free reference: greedy decode is deterministic per prompt,
        # so the chaos run's survivors must reproduce these tokens exactly
        # even after an eject/resubmit moved them across replicas
        results, wall, counters, _ = run(
            run_http_phase(
                build_router(model, params, policy, cargs),
                prompts, args.stress_rate, args.max_new, args.tenants,
                args.chunk,
            )
        )
        phases["http_chaos_ref"] = summarize(results, wall, counters)
        ref_tokens = tokens_of(results)
        print_phase("http_chaos_ref", phases["http_chaos_ref"])

        FAULTS.arm(f"seed={args.seed};replica_crash@{args.crash_at}:key=1")
        try:
            results, wall, counters, last_trace = run(
                run_http_phase(
                    build_router(model, params, policy, cargs),
                    prompts, args.stress_rate, args.max_new, args.tenants,
                    args.chunk,
                )
            )
        finally:
            FAULTS.disarm()
        phases["http_chaos"] = summarize(results, wall, counters)
        print_phase("http_chaos", phases["http_chaos"])
        agree["chaos_vs_ref"] = agreement(tokens_of(results), ref_tokens)
        s = phases["http_chaos"]
        print(
            f"chaos: availability {s['served']}/{s['requests']}, "
            f"ejections {s.get('ejections', 0)}, "
            f"resubmits {s.get('resubmits', 0)}, "
            f"retries {s.get('retries', 0)}, p95 TTFT "
            f"{phases['http_chaos_ref']['ttft_p95_ms']:.1f}ms (fault-free) "
            f"-> {s['ttft_p95_ms']:.1f}ms (1 of {cargs.replicas} replicas "
            f"killed), token agreement {agree['chaos_vs_ref']:.0%}",
            flush=True,
        )

    out = {
        "bench": "http",
        "config": {
            "requests": args.requests, "rate_per_s": args.rate,
            "stress_rate_per_s": args.stress_rate,
            "batch": args.batch, "replicas": args.replicas,
            "chunk": args.chunk, "max_new": args.max_new,
            "vocab": args.vocab, "d_model": args.d_model,
            "tenants": args.tenants, "seed": args.seed,
            "pretrain_steps": args.pretrain_steps,
            "workload": args.workload,
            "backend": "ref (CPU dev container)",
        },
        "phases": phases,
        "agreement": agree,
    }
    if "inproc_uniform" in phases and "http_uniform" in phases:
        out["http_overhead"] = {
            "ttft_p50_ms_delta": round(
                phases["http_uniform"]["ttft_p50_ms"]
                - phases["inproc_uniform"]["ttft_p50_ms"], 2,
            ),
            "tpot_mean_ms_delta": round(
                phases["http_uniform"]["tpot_mean_ms"]
                - phases["inproc_uniform"]["tpot_mean_ms"], 2,
            ),
        }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}", flush=True)
    if args.trace_out and last_trace is not None:
        with open(args.trace_out, "w") as f:
            json.dump(last_trace, f)
            f.write("\n")
        n_ev = len(last_trace.get("traceEvents", []))
        print(f"wrote {args.trace_out} ({n_ev} trace events; open in "
              f"https://ui.perfetto.dev)", flush=True)

    failures = []
    if agree.get("http_vs_inproc", 1.0) != 1.0:
        failures.append("http vs in-process token agreement != 100%")
    if agree.get("warm_vs_cold", 1.0) != 1.0:
        failures.append("warm vs cold token agreement != 100%")
    if agree.get("warm_v2_vs_cold", 1.0) != 1.0:
        failures.append("scheduler-v2 warm vs cold token agreement != 100%")
    if "http_chaos" in phases:
        s = phases["http_chaos"]
        if s["served"] != s["requests"]:
            failures.append(
                f"chaos: {s['requests'] - s['served']} requests lost "
                "(every request must survive the replica kill)"
            )
        if s.get("ejections", 0) < 1:
            failures.append("chaos: replica kill did not record an ejection")
        if agree.get("chaos_vs_ref", 1.0) != 1.0:
            failures.append("chaos vs fault-free token agreement != 100%")
    if failures:
        raise SystemExit("; ".join(failures))


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
