"""Serving cells: an open-loop traffic mix through the program's in-process
``Router`` over one ``ServeEngine``, built as ``launch/serve --frontend``
builds it.

Set-up: weights from the seed on the device, packed by the engine
(``WeightStore.pack``), then one warm-up request per lane, which runs both
step widths and every per-lane path the window uses. Window: requests are
submitted when due (the schedule's clock, not the send), the router is
pumped one batched step at a time, and every token is stamped when the
router delivers it. After the window no request is sent; the router
drains what is in flight. Then a sample of finished requests, drawn from
the seed and holding the longest, is run through the plain reference, and
every op dispatched from the warm-up to the end of the drain must have
run as compiled Pallas.

Memory: the peak that packing sets in set-up is no part of what serving
holds, so the window samples the bytes in use between steps; the
process's peak counts only where the window raised it.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from bench.harness import compiles, device, traffic
from bench.harness import trace as T

DRAIN_S = 120.0      # the longest wait for in-flight requests after the window
MEM_EVERY_S = 0.25   # how often the window samples the bytes in use


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None


def _ms(x):
    return None if x is None else 1e3 * x


def setup(r) -> dict:
    """The router over one engine, warmed up; weights kept for the reference."""
    import jax
    from repro.core.policy import get_policy
    from repro.kernels import dispatch
    from repro.serving import PrefixCache, Router

    tm = {"driver": time.monotonic()}
    cfg, eng = r.cfg, r.mix["engine"]
    lanes, chunk = eng["lanes"], eng["chunk"]
    models = r.module("models", cfg["model"])
    model = models.program_model(cfg)
    weights = jax.block_until_ready(models.make_weights(cfg, r.seed))
    tm["weights"] = time.monotonic()
    policy = get_policy(r.overrides.get("policy", cfg["policy"]))
    cache = (PrefixCache(budget_bytes=int(eng["prefix_cache_mb"] * 2 ** 20), block=chunk)
             if eng["prefix_cache_mb"] > 0 else None)
    router = Router.build(
        model, models.program_params(model, weights), policy, replicas=1,
        prefix_cache=cache,
        router_kw=dict(admission=eng["admission"], max_queue=1 << 30),
        lanes=lanes, chunk=chunk, packed=True,
        weight_format=cfg["weight_format"], admission=eng["admission"])
    tm["build_pack"] = time.monotonic()
    dispatch.STATS.reset()
    for p in traffic.warmup_requests(r.seed, cfg["vocab"], lanes, chunk):
        router.submit(p, max_new=3)
    router.drain()
    tm["warmup"] = time.monotonic()
    parts, last = {"to_driver": tm["driver"] - r.t_start}, tm["driver"]
    for k in ("weights", "build_pack", "warmup"):
        parts[k], last = tm[k] - last, tm[k]
    return {"router": router, "engine": router.engines[0], "weights": weights,
            "devices": r.devices, "setup_parts": parts}


def _counts(engine):
    m = engine.metrics
    return {"prefill": m.prefill_steps, "decode": m.decode_steps,
            "through_lstm": m.useful_slots, "emitted": m.emitted}


def open_loop(st: dict, sched: dict, seconds: float, trace: bool = False) -> dict:
    """Submit each request when due, pump until ``seconds`` have passed,
    then drain. Returns per-request records and what the window counted."""
    from repro.obs.trace import TRACER

    router, engine = st["router"], st["engine"]
    due, prompts, max_new = sched["due"], sched["prompts"], sched["max_new"]
    n = len(due)
    recs = [{"times": [], "ticket": None} for _ in range(n)]
    trace_at, trace_len = 0.0, seconds
    traced, spans, in_trace = {}, [], None
    stack = contextlib.ExitStack()
    counter = st.setdefault("counter", compiles.Counter())
    c0, w0 = counter.count, _counts(engine)
    n0 = len(counter.names)
    lag, dropped = [], [0]
    devs = st.get("devices")
    mem, t_mem = [], -MEM_EVERY_S
    peak0 = device.memory_peak_bytes(devs) if devs else None
    i = 0
    t0 = time.monotonic()

    def stop_trace():
        nonlocal spans
        TRACER.disable()
        spans = TRACER.events()
        dropped[0] = TRACER.dropped
        in_trace["after"] = _counts(engine)
        stack.close()

    while True:
        now = time.monotonic()
        el = now - t0
        if el >= seconds:
            break
        if devs and el - t_mem >= MEM_EVERY_S:
            mem.append(device.memory_in_use_bytes(devs))
            t_mem = el
        if trace and in_trace is None and el >= trace_at:
            stack.enter_context(T.capture(traced))
            TRACER.clear()
            TRACER.enable()
            in_trace = {"t": now, "before": _counts(engine)}
        elif in_trace and "after" not in in_trace and now - in_trace["t"] >= trace_len:
            stop_trace()
        while i < n and due[i] <= el:
            rec = recs[i]
            rec["ticket"] = router.submit(
                prompts[i], max_new=max_new[i],
                on_token=lambda tok, t=rec["times"]: t.append(time.monotonic()))
            lag.append(time.monotonic() - (t0 + due[i]))
            i += 1
        tracing = in_trace is not None and "after" not in in_trace
        if not router.idle:
            with T.span("bench.pump") if tracing else contextlib.nullcontext():
                router.pump()
        else:
            wait = (due[i] if i < n else seconds) - (time.monotonic() - t0)
            if wait > 0:
                with T.span("bench.idle") if tracing else contextlib.nullcontext():
                    time.sleep(min(wait, 0.001))
    if in_trace is not None and "after" not in in_trace:
        stop_trace()
    w1 = _counts(engine)
    backlog = len(router._queue) + sum(len(e.scheduler) for e in router.engines)
    t_drain = time.monotonic()
    while not router.idle and time.monotonic() - t_drain < DRAIN_S:
        router.pump()
    peak1 = device.memory_peak_bytes(devs) if devs else None
    mem = [m for m in mem if m is not None]
    raised = peak1 if peak0 is not None and peak1 is not None and peak1 > peak0 else 0
    return {
        "memory_window_bytes": max(mem + [raised]) if mem else None,
        "memory_after_setup_bytes": mem[0] if mem else None,
        "memory_process_peak_bytes": peak1,
        "recs": recs[:i], "due": due[:i], "t0": t0, "seconds": seconds,
        "lag": lag, "compiles": counter.count - c0,
        "compiled": sorted(set(counter.names[n0:])),
        "window": {k: w1[k] - w0[k] for k in w0},
        "backlog_at_close": backlog, "drain_s": time.monotonic() - t_drain,
        "trace": traced.get("trace"), "spans": spans, "dropped": dropped[0],
        "traced_steps": (None if not in_trace else
                         {k: in_trace["after"][k] - in_trace["before"][k]
                          for k in ("prefill", "decode")}),
    }


def summarize(w: dict) -> dict:
    """End-to-end numbers of one open-loop window."""
    t0, t_end = w["t0"], w["t0"] + w["seconds"]
    ttft, gaps, qwait, done = [], [], [], []
    for k, rec in enumerate(w["recs"]):
        t_due = t0 + w["due"][k]
        tk = rec["ticket"]
        if not (tk is not None and tk.status == "done" and rec["times"]):
            ttft.append(float("inf"))     # a failed request misses every limit
            continue
        ts = rec["times"]
        ttft.append(ts[0] - t_due)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if b <= t_end)
        qwait.append(tk.req.t_admit - t_due)
        done.append(k)
    return {"ttft": ttft, "gaps": gaps, "qwait": qwait, "done": done,
            "failed": len(w["recs"]) - len(done)}


def run(r) -> None:
    st = setup(r)
    setup_s = time.monotonic() - r.t_start
    sched = traffic.serve_schedule(r.mix, r.seed, r.seconds, r.cfg["vocab"])
    w = open_loop(st, sched, r.seconds, trace=r.trace)
    s = summarize(w)
    on_oracle = compiles.oracle_ops()   # warm-up, window and drain
    r.result.update(
        e2e={"ttft_p95_ms": _ms(_percentile(s["ttft"], 95)),
             "itl_mean_ms": _ms(float(np.mean(s["gaps"])) if s["gaps"] else None),
             "itl_p95_ms": _ms(_percentile(s["gaps"], 95)),
             "setup_s": setup_s},
        attempted=len(w["recs"]), failed=s["failed"],
        memory_peak_bytes=w["memory_window_bytes"], trace=w["trace"],
        extra={"setup_parts": st["setup_parts"],
               "memory_after_setup_bytes": w["memory_after_setup_bytes"],
               "memory_process_peak_bytes": w["memory_process_peak_bytes"],
               "itl_gaps": len(s["gaps"]), "compiles_in_window": w["compiles"],
               "compiled_in_window": w["compiled"], "trace_events_dropped": w["dropped"],
               "generator_lag_ms_p95": _ms(_percentile(w["lag"], 95)),
               "backlog_at_close": w["backlog_at_close"], "drain_s": w["drain_s"],
               "steps_prefill": w["window"]["prefill"],
               "steps_decode": w["window"]["decode"]},
    )
    r.layer = {"queue_wait_s": s["qwait"], "spans": w["spans"],
               "traced_steps": w["traced_steps"], "window_s": r.seconds,
               "lanes": r.mix["engine"]["lanes"], "chunk": r.mix["engine"]["chunk"],
               "window_tokens": w["window"]}

    # -- correct: a sample of what was served, against the reference ------
    sample = _sample(r, w["recs"], s["done"], sched["prompts"])
    weights = st["weights"]
    st.clear()
    del w
    gc.collect()
    ref = r.module("reference", r.cfg["reference"])
    gap_served, _ = ref.serve_gaps(r.cfg, weights, sample)
    lim = r.limits["logit_gap"]
    gap = float(np.max(gap_served)) if len(gap_served) else float("inf")
    checks = {"logit_gap": {"value": gap, "limit": lim, "ok": gap <= lim}}
    if not r.overrides.get("allow_cpu"):
        checks["oracle_ops"] = {"value": on_oracle, "limit": 0, "ok": on_oracle == 0}
    r.result["checks"] = checks
    r.result["extra"].update(sample_requests=len(sample),
                             sample_tokens=int(len(gap_served)))


def _sample(r, recs, done, prompts):
    """``sample_requests`` finished requests, whole: the longest, and the
    rest drawn from the seed, so that many lanes are checked."""
    if not done:
        return []
    rng = np.random.default_rng([r.seed, 2])
    size = {k: len(prompts[k]) + len(recs[k]["ticket"].tokens) for k in done}
    longest = max(done, key=lambda k: size[k])
    order = [longest] + [int(k) for k in rng.permutation(done) if k != longest]
    return [(prompts[k].tolist(), list(recs[k]["ticket"].tokens))
            for k in order[: r.limits["sample_requests"]]]
