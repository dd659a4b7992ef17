"""HTTP/SSE serving layer tests: ephemeral-port server over a real
socket — happy path + token agreement vs the in-process AsyncRouter, SSE
wire framing, the four reject-reason → distinct-status mappings under
induced overload, concurrent tenants, drain semantics, and a /metrics
scrape that parses as Prometheus text exposition."""
import asyncio
import json
import re

import jax
import numpy as np
import pytest

from repro.core.policy import get_policy
from repro.models.lstm_models import WikiText2LM
from repro.serving import PrefixCache, Router, ServeEngine
from repro.serving.frontend import AsyncRouter
from repro.serving.http import Client, HttpError, HttpServer, REASON_STATUS
from repro.serving.http.protocol import HttpRequest, ProtocolError

POLICY = get_policy("floatsd8_table6")


def tiny_model():
    return WikiText2LM(vocab=300, emb=32, hidden=32, n_layers=2)


_PARAMS = {}


def tiny_params(model, seed=0):
    key = (model.vocab, model.emb, model.hidden, model.n_layers, seed)
    if key not in _PARAMS:
        _PARAMS[key] = model.init(jax.random.PRNGKey(seed))
    return _PARAMS[key]


def make_router(replicas=1, lanes=2, chunk=4, cache=None, **router_kw):
    model = tiny_model()
    params = tiny_params(model)
    engines = [
        ServeEngine(model, params, POLICY, lanes=lanes, chunk=chunk,
                    prefix_cache=cache)
        for _ in range(replicas)
    ]
    return Router(engines, **router_kw)


async def start_server(router, **kw):
    server = await HttpServer(router, port=0, **kw).start()
    task = asyncio.create_task(server.serve_forever())
    return server, task


async def stop_server(server, task):
    server.shutdown()
    await asyncio.wait_for(task, timeout=30)


def prompts_for(model, n, length=6, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, model.vocab, length).astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# happy path + agreement with the in-process router
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_generate_over_socket_agrees_with_in_process_router():
    """The acceptance bar: /v1/generate through a real TCP socket returns
    exactly the tokens the in-process AsyncRouter produces for the same
    prompts (same params, fresh identical routers)."""
    prompts = prompts_for(tiny_model(), 3, seed=3)

    async def via_http():
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port) as c:
                out = [await c.generate(p, max_new=5) for p in prompts]
            return out
        finally:
            await stop_server(server, task)

    async def via_router():
        ar = AsyncRouter(make_router())
        return [await ar.generate(p, max_new=5) for p in prompts]

    http_out = asyncio.run(via_http())
    tickets = asyncio.run(via_router())

    for resp, ticket in zip(http_out, tickets):
        assert resp["tokens"] == ticket.tokens and len(resp["tokens"]) == 5
        assert resp["n_tokens"] == 5
        assert 0 <= resp["ttft_ms"] <= resp["latency_ms"]
        assert resp["tenant"] == "default"


def test_sse_stream_framing_and_generate_consistency():
    """SSE frames parse (index/token per frame, terminal done event) and
    the streamed tokens equal /v1/generate's for the same prompt; the raw
    wire bytes follow the documented event-stream framing."""
    [prompt] = prompts_for(tiny_model(), 1, seed=4)

    async def main():
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port) as c:
                gen = await c.generate(prompt, max_new=4)
                events = [ev async for ev in c.stream(prompt, max_new=4)]

            # raw-socket view of the same stream: exact wire framing
            reader, writer = await asyncio.open_connection(
                server.host, server.port
            )
            body = json.dumps({"prompt": prompt.tolist(), "max_new": 2})
            writer.write(
                (
                    "POST /v1/stream HTTP/1.1\r\nHost: t\r\n"
                    "Content-Type: application/json\r\n"
                    f"Content-Length: {len(body)}\r\n\r\n{body}"
                ).encode()
            )
            await writer.drain()
            raw = await reader.read()  # server closes after the stream
            writer.close()
            return gen, events, raw
        finally:
            await stop_server(server, task)

    gen, events, raw = asyncio.run(main())

    start, *token_events, done = events
    assert start[0] == "start" and isinstance(start[1]["rid"], int)
    assert done[0] == "done" and done[1]["n_tokens"] == 4
    assert [e for e, _ in token_events] == ["message"] * 4
    assert [d["index"] for _, d in token_events] == [0, 1, 2, 3]
    assert [d["token"] for _, d in token_events] == gen["tokens"]
    assert done[1]["ttft_ms"] <= done[1]["latency_ms"]

    head, _, payload = raw.partition(b"\r\n\r\n")
    assert b"200" in head.split(b"\r\n")[0]
    assert b"content-type: text/event-stream" in head.lower()
    assert b"connection: close" in head.lower()
    frames = [f for f in payload.decode().split("\n\n") if f]
    assert len(frames) == 4  # start + 2 tokens + done
    assert frames[0].startswith("event: start\ndata: ")
    for f in frames[1:-1]:
        assert f.startswith("data: ")
        json.loads(f.split("data: ", 1)[1])
    assert frames[-1].startswith("event: done\ndata: ")


# ---------------------------------------------------------------------------
# reject reasons -> distinct status codes
# ---------------------------------------------------------------------------


def test_reject_reasons_map_to_distinct_status_codes():
    assert len(set(REASON_STATUS.values())) == 4  # distinct by construction

    async def main():
        statuses = {}
        # induced overload: a zero-length router queue bounces everything
        server, task = await start_server(make_router(max_queue=0))
        try:
            async with Client(server.host, server.port) as c:
                with pytest.raises(HttpError) as ei:
                    await c.generate([1, 2, 3], max_new=1)
                statuses["queue_full"] = (ei.value.status, ei.value.body["error"])
        finally:
            await stop_server(server, task)

        # tenant over quota (admission checks quota before validating the
        # request, so this needs its own non-overloaded router)
        server, task = await start_server(
            make_router(max_queue=8, tenant_quota=0)
        )
        try:
            async with Client(server.host, server.port) as c:
                with pytest.raises(HttpError) as ei:
                    await c.generate([1, 2, 3], max_new=1, tenant="t0")
                statuses["tenant_quota"] = (ei.value.status, ei.value.body["error"])
        finally:
            await stop_server(server, task)

        # empty prompt + dead-on-arrival deadline on a healthy router
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port) as c:
                with pytest.raises(HttpError) as ei:
                    await c.generate([], max_new=1)
                statuses["bad_request"] = (ei.value.status, ei.value.body["error"])
                with pytest.raises(HttpError) as ei:
                    await c.generate([1, 2, 3], max_new=1, deadline_ms=-1000)
                statuses["deadline_expired"] = (
                    ei.value.status, ei.value.body["error"],
                )
                # HTTP-level (pre-router) validation is 400 too
                status, _, body = await c.request(
                    "POST", "/v1/generate", {"max_new": 1}
                )
                assert status == 400
                assert "prompt" in json.loads(body)["detail"]
        finally:
            await stop_server(server, task)
        return statuses

    statuses = asyncio.run(main())
    for reason, (status, err) in statuses.items():
        assert status == REASON_STATUS[reason], (reason, status)
        assert err == reason
    assert len({s for s, _ in statuses.values()}) == 4  # distinct on the wire


@pytest.mark.slow
def test_stream_rejected_after_admission_sends_error_event():
    """A stream whose deadline expires while queued was admitted before
    the 200 preamble went out; the mapped status must arrive as a
    terminal SSE `error` event (the client raises HttpError from it)."""
    model = tiny_model()
    long_p, short_p = prompts_for(model, 2, seed=9)

    async def main():
        # one lane: the first request occupies it, the second queues
        server, task = await start_server(make_router(lanes=1))
        try:
            blocker = Client(server.host, server.port)
            victim = Client(server.host, server.port)
            gen = blocker.stream(long_p, max_new=64)
            await gen.__anext__()  # start event (pre-admission)
            await gen.__anext__()  # first token: lane busy for ~63 pumps
            try:
                with pytest.raises(HttpError) as ei:
                    # expires while queued: 63 pumps >> 5ms, but the
                    # submit itself happens microseconds after parse, so
                    # it is never dead-on-arrival
                    async for _ in victim.stream(
                        short_p, max_new=1, deadline_ms=5
                    ):
                        pass
                status = ei.value.status
                reason = ei.value.body["error"]
            finally:
                async for _ in gen:  # let the blocker finish
                    pass
                await blocker.close()
                await victim.close()
            return status, reason
        finally:
            await stop_server(server, task)

    status, reason = asyncio.run(main())
    assert status == REASON_STATUS["deadline_expired"] == 504
    assert reason == "deadline_expired"


def test_protocol_errors_and_unknown_routes():
    async def main():
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port) as c:
                s1, _, _ = await c.request("GET", "/nope")
                s2, _, _ = await c.request("GET", "/v1/generate")  # wrong verb
                # malformed JSON body
                reader, writer = await asyncio.open_connection(
                    server.host, server.port
                )
                writer.write(
                    b"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                    b"Content-Length: 4\r\n\r\n{oop"
                )
                await writer.drain()
                line = await reader.readline()
                writer.close()
                return s1, s2, line
        finally:
            await stop_server(server, task)

    s1, s2, line = asyncio.run(main())
    assert s1 == 404 and s2 == 405
    assert b"400" in line


def test_protocol_request_parsing_units():
    """protocol.py parsing units, no socket: header casing, query strip,
    json() validation."""
    req = HttpRequest(
        method="POST",
        target="/v1/generate?x=1",
        headers={"x-tenant": "a", "connection": "close"},
        body=b'{"prompt": [1]}',
    )
    assert req.path == "/v1/generate"
    assert not req.keep_alive
    assert req.json() == {"prompt": [1]}
    with pytest.raises(ProtocolError) as ei:
        HttpRequest("POST", "/", {}, b"[1, 2]").json()
    assert ei.value.status == 400
    with pytest.raises(ProtocolError):
        HttpRequest("POST", "/", {}, b"{nope").json()


# ---------------------------------------------------------------------------
# concurrency + tenants
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_concurrent_tenants_over_http():
    prompts = prompts_for(tiny_model(), 4, seed=5)

    async def main():
        server, task = await start_server(make_router(lanes=2))
        try:
            async def one(i, prompt):
                async with Client(
                    server.host, server.port, tenant=("a", "b")[i % 2]
                ) as c:
                    return await c.generate(prompt, max_new=3)

            results = await asyncio.gather(
                *(one(i, p) for i, p in enumerate(prompts))
            )
            async with Client(server.host, server.port) as c:
                health = await c.healthz()
            return results, health, server.router.report()
        finally:
            await stop_server(server, task)

    results, health, report = asyncio.run(main())
    assert all(len(r["tokens"]) == 3 for r in results)
    assert {r["tenant"] for r in results} == {"a", "b"}
    assert health["status"] == "ok" and health["inflight"] == 0
    assert health["free_lanes"] == health["lanes"] == 2
    assert report["tenants"]["a"]["completed"] == 2
    assert report["tenants"]["b"]["completed"] == 2


# ---------------------------------------------------------------------------
# drain semantics
# ---------------------------------------------------------------------------


def test_drain_stops_admission_finishes_inflight_and_exits():
    model = tiny_model()
    [prompt] = prompts_for(model, 1, seed=6)

    async def main():
        server, task = await start_server(make_router())
        admin = Client(server.host, server.port)
        streamer = Client(server.host, server.port)
        try:
            gen = streamer.stream(prompt, max_new=12)
            start = await gen.__anext__()
            assert start[0] == "start"
            first = await gen.__anext__()  # request is now in flight
            assert first[0] == "message"

            d = await admin.drain()
            assert d["status"] == "draining" and d["inflight"] == 1
            # admission is stopped: new work bounces with 503 draining
            with pytest.raises(HttpError) as ei:
                await admin.generate(prompt, max_new=1)
            assert ei.value.status == 503
            assert ei.value.body["error"] == "draining"
            health = await admin.healthz()
            assert health["status"] == "draining"
            # drain is idempotent
            assert (await admin.drain())["status"] == "draining"

            # ...but the in-flight stream runs to completion
            events = [first] + [ev async for ev in gen]
            *toks, done = events
            assert done[0] == "done" and len(toks) == 12

            # and the server exits cleanly once idle
            await asyncio.wait_for(task, timeout=30)
            return True
        finally:
            await admin.close()
            await streamer.close()
            if not task.done():
                await stop_server(server, task)

    assert asyncio.run(main())


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+]?[0-9.e+-]+(e[+-]?\d+)?$"
)


def test_metrics_scrape_parses_as_prometheus_text():
    [prompt] = prompts_for(tiny_model(), 1, seed=7)

    async def main():
        # admit_retries=0: each in-server retry is a fresh router
        # submission and would inflate the rejection counter below
        server, task = await start_server(
            make_router(cache=PrefixCache(block=4), max_queue=0),
            admit_retries=0,
        )
        # max_queue=0 also records one rejection for the counter below
        try:
            async with Client(server.host, server.port) as c:
                with pytest.raises(HttpError):
                    await c.generate(prompt, max_new=1)
                status, hdrs, data = await c.request("GET", "/metrics")
            return status, hdrs, data.decode()
        finally:
            await stop_server(server, task)

    status, hdrs, text = asyncio.run(main())
    assert status == 200
    assert hdrs["content-type"].startswith("text/plain; version=0.0.4")

    samples = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE "))
            continue
        assert _SAMPLE_RE.match(line), line
        name_labels, value = line.rsplit(" ", 1)
        samples[name_labels] = float(value)

    assert samples["repro_up"] == 1.0
    assert samples["repro_requests_total"] == 0.0
    assert samples["repro_free_lanes"] == 2.0
    assert samples['repro_rejections_total{reason="queue_full"}'] == 1.0
    # prefix-cache gauges present when a cache is attached
    assert "repro_cache_entries" in samples
    assert samples["repro_cache_budget_bytes"] > 0
    assert "repro_cache_hits_total" in samples


@pytest.mark.slow
def test_metrics_tenant_percentiles_after_traffic():
    [prompt] = prompts_for(tiny_model(), 1, seed=8)

    async def main():
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port, tenant="acme") as c:
                await c.generate(prompt, max_new=2)
                return await c.metrics()
        finally:
            await stop_server(server, task)

    text = asyncio.run(main())
    assert 'repro_tenant_completed_total{tenant="acme"} 1' in text
    assert 'repro_tenant_ttft_seconds{tenant="acme",quantile="0.95"}' in text
    assert 'repro_tenant_latency_seconds{tenant="acme",quantile="0.5"}' in text


def test_metrics_latency_histograms_and_cost_ledger_exposition():
    """TTFT/TPOT land as cumulative Prometheus histograms (monotone
    ``_bucket{le=...}`` series capped by +Inf == ``_count``) and the kernel
    cost ledger is exported per (op, backend)."""
    [prompt] = prompts_for(tiny_model(), 1, seed=9)

    async def main():
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port) as c:
                await c.generate(prompt, max_new=4)
                return await c.metrics()
        finally:
            await stop_server(server, task)

    text = asyncio.run(main())

    for name in ("repro_ttft_ms", "repro_tpot_ms"):
        buckets = []  # (le, value) in exposition order
        for line in text.splitlines():
            if line.startswith(f"{name}_bucket{{"):
                le = line.split('le="', 1)[1].split('"', 1)[0]
                buckets.append((le, float(line.rsplit(" ", 1)[1])))
        assert buckets, f"{name}_bucket series missing"
        assert buckets[-1][0] == "+Inf"
        values = [v for _, v in buckets]
        assert values == sorted(values), f"{name} buckets not cumulative"
        count = float(
            next(l for l in text.splitlines() if l.startswith(f"{name}_count"))
            .rsplit(" ", 1)[1]
        )
        assert values[-1] == count, f"{name} +Inf bucket != _count"
        assert count >= 1  # one request retired → at least one observation

    # the cost-model observatory: predicted-cost counters per (op, backend)
    assert 'repro_cost_flops_total{op="floatsd_matmul"' in text
    assert 'repro_cost_flops_total{op="lstm_cell"' in text
    assert "repro_cost_hbm_read_bytes_total{" in text
    assert "repro_cost_arithmetic_intensity{" in text


# ---------------------------------------------------------------------------
# observability: /admin/trace, debug phase breakdowns, scrape consistency
# ---------------------------------------------------------------------------

import importlib.util as _ilu
from pathlib import Path as _Path

from repro.obs.trace import TRACER

_spec = _ilu.spec_from_file_location(
    "check_trace", _Path(__file__).parent.parent / "scripts" / "check_trace.py"
)
_check_trace = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_check_trace)
validate_trace = _check_trace.validate_trace


def test_admin_trace_exports_valid_chrome_trace_with_lifecycle_spans():
    """GET /admin/trace after real traffic: the export validates (required
    keys, monotone ts, matched B/E) and carries the request-lifecycle
    span names end to end."""
    [prompt] = prompts_for(tiny_model(), 1, seed=11)

    async def main():
        server, task = await start_server(make_router(cache=PrefixCache(block=4)))
        TRACER.clear()
        try:
            async with Client(server.host, server.port) as c:
                await c.generate(prompt, max_new=3)
                return await c.trace()
        finally:
            await stop_server(server, task)

    trace = asyncio.run(main())
    assert validate_trace(trace) == []
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    for expected in ("http.request", "router.submit", "router.dispatch",
                     "router.pump", "engine.admit", "cache.lookup",
                     "engine.step", "engine.retire"):
        assert expected in names, expected
    # engine.step carries per-lane attribution and prefill/decode kind
    step_args = [e["args"] for e in evs
                 if e["name"] == "engine.step" and e["ph"] == "B"]
    assert any(a.get("kind") == "prefill" for a in step_args)
    assert any(a.get("kind") == "decode" for a in step_args)
    assert all("lanes" in a for a in step_args)


def test_admin_trace_samples_cost_tracks_once_at_export():
    """The cost.<op> counter tracks are written when the trace is exported,
    one sample per op after the last pump, not re-sampled every pump; the
    export still carries the tracks check_trace.py requires."""
    prompts = prompts_for(tiny_model(), 2, seed=13)

    async def main():
        server, task = await start_server(make_router(cache=PrefixCache(block=4)))
        TRACER.clear()
        try:
            async with Client(server.host, server.port) as c:
                for p in prompts:
                    await c.generate(p, max_new=3)
                return await c.trace()
        finally:
            await stop_server(server, task)

    trace = asyncio.run(main())
    assert validate_trace(trace) == []
    evs = trace["traceEvents"]
    costs = [e for e in evs if e["name"].startswith("cost.")]
    names = [e["name"] for e in costs]
    assert {"cost.floatsd_matmul", "cost.lstm_cell"} <= set(names)
    assert len(names) == len(set(names))
    last_pump = max(e["ts"] for e in evs if e["name"] == "router.pump")
    assert min(e["ts"] for e in costs) >= last_pump


def test_debug_flag_returns_phase_breakdown():
    """`"debug": true` adds the queue/prefill/decode decomposition to
    /v1/generate and to the SSE terminal done event; absent by default;
    non-bool debug is a 400."""
    [prompt] = prompts_for(tiny_model(), 1, seed=12)

    async def main():
        server, task = await start_server(make_router(cache=PrefixCache(block=4)))
        try:
            async with Client(server.host, server.port) as c:
                plain = await c.generate(prompt, max_new=3)
                dbg = await c.generate(prompt, max_new=3, debug=True)
                done = {}
                async for ev, data in c.stream(prompt, max_new=3, debug=True):
                    if ev == "done":
                        done = data
                status, _, _ = await c.request(
                    "POST", "/v1/generate",
                    {"prompt": prompt.tolist(), "debug": "yes"},
                )
            return plain, dbg, done, status
        finally:
            await stop_server(server, task)

    plain, dbg, done, bad_status = asyncio.run(main())
    assert "phases" not in plain
    assert bad_status == 400
    for resp in (dbg, done):
        ph = resp["phases"]
        for k in ("queue_ms", "prefill_ms", "decode_ms", "total_ms"):
            assert ph[k] >= 0.0, (k, ph)
        assert ph["queue_ms"] + ph["prefill_ms"] + ph["decode_ms"] == pytest.approx(
            ph["total_ms"], abs=0.1
        )
        assert ph["total_ms"] >= resp["ttft_ms"] - 0.1
    # third identical prompt hit the cache warmed by the first two
    assert done["phases"]["cache_hit"]
    assert done["phases"]["cache_saved_tokens"] > 0


def test_metrics_export_dispatch_and_trace_stats():
    """Satellite: kernels.dispatch.STATS and tracer aggregates surface in
    /metrics with op/backend and span-name labels."""
    [prompt] = prompts_for(tiny_model(), 1, seed=13)

    async def main():
        server, task = await start_server(make_router())
        try:
            async with Client(server.host, server.port) as c:
                await c.generate(prompt, max_new=2)
                return await c.metrics()
        finally:
            await stop_server(server, task)

    text = asyncio.run(main())
    assert "repro_trace_enabled 1" in text
    m = re.findall(r'repro_dispatch_decisions_total\{op="([^"]+)",backend="([^"]+)"\} (\d+)', text)
    assert m, "dispatch decisions missing from /metrics"
    assert all(int(v) > 0 for _, _, v in m)
    assert re.search(r'repro_trace_spans_total\{name="engine\.step"\} \d+', text)
    assert re.search(r'repro_trace_span_seconds_total\{name="engine\.step"\} \d', text)
    assert re.search(r'repro_request_phase_seconds\{phase="prefill",quantile="0\.95"\}', text)


@pytest.mark.slow
def test_metrics_scrape_consistent_under_concurrent_load():
    """Regression (scrape-path races): hammer /metrics while streams are
    in flight. Every scrape must parse as Prometheus text with sane,
    monotone counters — one locked Router.scrape() snapshot per scrape."""
    model = tiny_model()
    prompts = prompts_for(model, 8, seed=14)

    async def main():
        server, task = await start_server(
            make_router(lanes=2, cache=PrefixCache(block=4), max_queue=64)
        )
        try:
            scrapes = []
            done = asyncio.Event()

            async def scraper():
                async with Client(server.host, server.port) as c:
                    while not done.is_set():
                        scrapes.append(await c.metrics())
                        await asyncio.sleep(0.005)
                    scrapes.append(await c.metrics())

            async def one(i, p):
                async with Client(server.host, server.port) as c:
                    return [t async for t in _collect(c, p)]

            async def _collect(c, p):
                async for ev, data in c.stream(p, max_new=4):
                    if ev == "message":
                        yield data["token"]

            scrape_task = asyncio.create_task(scraper())
            outs = await asyncio.gather(*(one(i, p) for i, p in enumerate(prompts)))
            done.set()
            await scrape_task
            return outs, scrapes
        finally:
            await stop_server(server, task)

    outs, scrapes = asyncio.run(main())
    assert all(len(t) == 4 for t in outs)
    assert len(scrapes) >= 2
    last_requests = -1.0
    for text in scrapes:
        samples = {}
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            assert _SAMPLE_RE.match(line), line
            name_labels, value = line.rsplit(" ", 1)
            samples[name_labels] = float(value)
        assert samples["repro_up"] == 1.0
        # counters never go backwards across interleaved scrapes
        assert samples["repro_requests_total"] >= last_requests
        last_requests = samples["repro_requests_total"]
    assert scrapes[-1].count("repro_requests_total 8") == 1
