"""Batched serving CLI — thin wrapper over ``repro.serving``.

Continuous batching over a fixed pool of decode lanes, chunked prefill,
FIFO / shortest-prompt-first / earliest-deadline-first admission, and
weights served from packed uint8 FloatSD8 codes (1 byte/weight,
decode-at-use — the paper PE's deployment format). ``--frontend`` layers
the multi-tenant request router and the FP8 LSTM-state prefix cache on
top: engine replicas share one cache, requests carry tenants, and the
report includes hit rates and per-tenant latency percentiles. See
src/repro/serving/README.md for the engine and frontend lifecycles.

``--http`` goes one step further: instead of draining a synthetic
workload, the router is put behind the stdlib HTTP/SSE server
(serving/http/) and serves real sockets until POST /admin/drain — the
network-facing deployment of the whole stack. See serving/README.md §HTTP
for the endpoint reference and runbook.

  PYTHONPATH=src python -m repro.launch.serve --requests 32 --batch 8 \
      --max-new 32 --policy floatsd8_table6            # reduced config
  ... --full                                            # paper-scale LM (50.9M params, tied)
  ... --chunk 1 --dense                                 # seed-equivalent loop
  ... --frontend --replicas 2 --workload zipf-prefix    # router + cache
  ... --http --port 8000 --replicas 2                   # network service
  ... --http --admission sjf_work --preempt             # scheduler v2
"""
from __future__ import annotations

import argparse
import asyncio

import jax
import numpy as np

from ..configs.base import get_config
from ..core.policy import get_policy
from ..models import build
from . import compile_cache
from ..serving import (
    ADMISSION_POLICIES,
    HttpServer,
    PrefixCache,
    Router,
    ServeEngine,
    synthetic_prompts,
    zipf_prefix_prompts,
)


def _serve_http(router: Router, args) -> None:
    """Run the HTTP/SSE service until /admin/drain (or Ctrl-C), then print
    the final router report."""

    async def run():
        server = await HttpServer(
            router, host=args.host, port=args.port,
            default_max_new=args.max_new, trace=not args.no_trace,
        ).start()
        print(
            f"http: listening on http://{server.host}:{server.port} "
            f"({args.replicas} replica(s) x {args.batch} lanes, "
            f"admission={args.admission}); POST /admin/drain to stop",
            flush=True,
        )
        try:
            await server.serve_forever()
        except (KeyboardInterrupt, asyncio.CancelledError):
            pass

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    rep = router.report()
    print(
        f"http: served {rep['requests']} requests, "
        f"{rep['emitted_tokens']} tokens over {rep['replicas']} replica(s) | "
        f"cache hit rate {rep['cache_hit_rate']:.0%} "
        f"({rep['prefill_tokens_saved']} prefill tok saved) | "
        f"rejections {rep['rejections']}",
        flush=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lstm_wikitext2")
    ap.add_argument("--policy", default="floatsd8_table6")
    ap.add_argument("--batch", type=int, default=8, help="decode lanes per engine")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk: prompt tokens consumed per step")
    ap.add_argument("--admission", default="fifo", choices=ADMISSION_POLICIES)
    ap.add_argument("--preempt", action="store_true",
                    help="scheduler v2: let engines preempt the "
                         "longest-remaining decoding lane (FP8 state "
                         "snapshot, resumed later) when the queue head "
                         "owes much less work — pair with "
                         "--admission sjf_work for the warm-tail win")
    ap.add_argument("--admit-pace", type=int, default=None,
                    help="scheduler v2: cap lane admissions per engine "
                         "step (spreads a warm burst; default unlimited)")
    ap.add_argument("--dense", action="store_true",
                    help="serve dense f32 weights (fake-quant at use) "
                         "instead of packed uint8 codes")
    ap.add_argument("--weight-format", choices=("floatsd8", "floatsd4"),
                    default="floatsd8",
                    help="packed serving format: floatsd8 (1 byte/weight, "
                         "output-identical to training) or floatsd4 "
                         "(2 codes/byte + group exponents, ~half the "
                         "resident bytes, re-quantized from the FloatSD8 "
                         "master)")
    ap.add_argument("--full", action="store_true", help="paper-scale model")
    ap.add_argument("--seed", type=int, default=0)
    # frontend (router + prefix cache) options
    ap.add_argument("--frontend", action="store_true",
                    help="serve through the multi-tenant router with a "
                         "shared FP8 LSTM-state prefix cache")
    ap.add_argument("--replicas", type=int, default=1,
                    help="frontend: engine replicas behind the router")
    ap.add_argument("--cache-mb", type=float, default=64.0,
                    help="frontend: prefix-cache byte budget (MiB); 0 "
                         "disables the cache")
    ap.add_argument("--tenants", type=int, default=2,
                    help="frontend: requests round-robin over this many "
                         "synthetic tenants")
    ap.add_argument("--workload", choices=["uniform", "zipf-prefix"],
                    default="uniform",
                    help="uniform prompt lengths, or shared-system-prompt "
                         "(zipf over a small prefix pool — what the prefix "
                         "cache is for)")
    # http (network service) options — implies the frontend router
    ap.add_argument("--http", action="store_true",
                    help="serve the frontend router over HTTP/SSE "
                         "(/v1/generate, /v1/stream, /healthz, /metrics, "
                         "/admin/drain) instead of draining a synthetic "
                         "workload; runs until POST /admin/drain")
    ap.add_argument("--host", default="127.0.0.1",
                    help="http: bind address")
    ap.add_argument("--port", type=int, default=8000,
                    help="http: bind port (0 picks an ephemeral port, "
                         "printed on startup)")
    ap.add_argument("--no-trace", action="store_true",
                    help="http: disable the request-lifecycle tracer "
                         "(GET /admin/trace then exports an empty trace)")
    args = ap.parse_args()
    compile_cache.enable()
    if args.http:
        args.frontend = True  # the HTTP layer sits on the router

    cfg = get_config(args.arch)
    if cfg.family == "lstm" and not args.full:
        import dataclasses

        cfg = dataclasses.replace(cfg, d_model=192, vocab=4000, n_layers=2)
    elif cfg.family != "lstm":
        cfg = cfg.reduced()
    policy = get_policy(args.policy)
    model = build(cfg)
    rng = np.random.default_rng(args.seed)
    params = model.init(jax.random.PRNGKey(args.seed))

    if args.workload == "zipf-prefix":
        prompts = zipf_prefix_prompts(
            args.requests, cfg.vocab, rng, prefix_len=3 * args.chunk,
            prefix_seed=args.seed,
        )
    else:
        prompts = synthetic_prompts(args.requests, cfg.vocab, rng)

    engine_kw = dict(
        lanes=args.batch,
        chunk=args.chunk,
        packed=not args.dense,
        weight_format=args.weight_format,
        cache_len=None if cfg.family == "lstm" else 2048,
        # engines share the admission policy so the preemption check peeks
        # at the same ordering the router dispatches under
        admission=args.admission,
        preempt=args.preempt,
        admit_pace=args.admit_pace,
    )

    if args.frontend:
        if cfg.family != "lstm":
            # Non-LSTM caches are not lane-major, so replicas cannot re-arm
            # lanes (at most `lanes` requests per engine) and there is no
            # constant-size state to prefix-cache; failing here beats a
            # RuntimeError mid-drain after partial service.
            raise SystemExit(
                "--frontend serves LSTM-family models (continuous lane "
                "re-arming + prefix cache need lane-major recurrent state); "
                f"arch {args.arch!r} is family {cfg.family!r} — use the "
                "plain engine path instead"
            )
        cache = (
            PrefixCache(budget_bytes=int(args.cache_mb * 2**20), block=args.chunk)
            if args.cache_mb > 0
            else None
        )
        router = Router.build(
            model, params, policy,
            replicas=args.replicas,
            prefix_cache=cache,
            router_kw=dict(admission=args.admission, max_queue=args.requests),
            **engine_kw,
        )
        if args.http:
            _serve_http(router, args)
            return
        for i, p in enumerate(prompts):
            router.submit(p, max_new=args.max_new, tenant=f"tenant{i % args.tenants}")
        router.drain()
        rep = router.report()
        print(
            f"frontend: {rep['requests']} requests over {rep['replicas']} "
            f"replica(s), {rep['steps']} steps "
            f"({rep['prefill_steps']} prefill / {rep['decode_steps']} decode), "
            f"cache hit rate {rep['cache_hit_rate']:.0%} "
            f"({rep['prefill_tokens_saved']} prefill tok saved), "
            f"rejections {rep['rejections']}",
            flush=True,
        )
        for tenant, t in rep["tenants"].items():
            print(
                f"  {tenant}: {t['completed']} done / {t['tokens']} tok | "
                f"ttft p95 {t.get('ttft_p95_s', 0.0)*1e3:.0f}ms | "
                f"latency p95 {t.get('latency_p95_s', 0.0)*1e3:.0f}ms",
                flush=True,
            )
        return

    engine = ServeEngine(model, params, policy, **engine_kw)
    if engine.store is not None:
        s = engine.store
        print(
            f"weights: {s.dense_nbytes/2**20:.1f} MiB dense -> "
            f"{s.packed_nbytes/2**20:.1f} MiB packed "
            f"{'FloatSD4' if s.fmt == 'floatsd4' else 'FloatSD8'} "
            f"({s.compression:.2f}x smaller, {s.n_packed} tensors packed)",
            flush=True,
        )
    engine.submit_all(prompts, max_new=args.max_new)
    metrics = engine.run()
    print(metrics.format(), flush=True)


if __name__ == "__main__":
    main()
