"""The map from the program's ring onto the profiler's clock, and the three
readers that use the ring: hand-made runs with known answers, and nothing
where the ring holds nothing to read."""
import types

import numpy as np
import pytest

from bench.harness import span_clock
from bench.harness import spec as S

A_NS, B = 7_000_000_000.0, 1.00002      # profiler ns = A + B * tracer ns
PUMPS, PERIOD_US, PUMP_US, WAIT_US, INSERT_US = 120, 2000, 1000, 400, 100
T0_US = 50_000_000


def _prof(us):
    return A_NS + B * 1000 * us


def _ring(n=PUMPS, kind=True):
    """n pumps: each a step whose wait is [200, 600) us into the pump, then
    a prefix-cache insert; one serve.queue sample each."""
    ev = []
    for i in range(n):
        t = T0_US + i * PERIOD_US

        def pair(name, a, b, **args):
            ev.append({"name": name, "ph": "B", "ts": a, "tid": 1, "args": args})
            ev.append({"name": name, "ph": "E", "ts": b, "tid": 1})

        ev.append({"name": "router.pump", "ph": "B", "ts": t, "tid": 1, "args": {}})
        pair("engine.wait", t + 200, t + 200 + WAIT_US)
        pair("cache.insert", t + 700, t + 700 + INSERT_US, **({"kind": "block"} if kind else {}))
        ev.append({"name": "serve.queue", "ph": "C", "ts": t + 900, "tid": 1, "args": {"queued": 0}})
        ev.append({"name": "router.pump", "ph": "E", "ts": t + PUMP_US, "tid": 1})
    return ev


def _host(n=PUMPS):
    """The bench.pump spans around the ring's pumps, 1 us wider each side."""
    out = []
    for i in range(n):
        t = T0_US + i * PERIOD_US
        a, b = _prof(t - 1), _prof(t + PUMP_US + 1)
        out.append(["bench.pump", int(a), int(b - a)])
    return out


def _trace(n=PUMPS):
    """Device busy exactly while the host waits in each pump."""
    ops = [["%fusion.1 = f32[] x", int(_prof(T0_US + i * PERIOD_US + 200)),
            int(B * 1000 * WAIT_US)] for i in range(n)]
    t0, t1 = _prof(T0_US - 500), _prof(T0_US + n * PERIOD_US)
    return {"window": [int(t0), int(t1)], "devices": {"/device:TPU:0": ops},
            "host": _host(n)}


def _run(trace, events, window_s=None):
    return types.SimpleNamespace(result={"trace": trace},
                                 layer={"spans": events, "window_s": window_s})


def test_fit_recovers_a_known_clock_map():
    clock = span_clock.fit(_host(), _ring())
    assert clock["pairs"] == PUMPS
    assert clock["b"] == pytest.approx(B, rel=1e-9)
    assert clock["a"] == pytest.approx(A_NS, rel=1e-6)
    assert clock["residual_p99_ns"] < 1000
    t = T0_US + 17 * PERIOD_US + 123
    assert span_clock.to_profiler(clock, t) == pytest.approx(_prof(t), abs=500)


def test_fit_aligns_the_newest_pumps_when_the_ring_lost_the_oldest():
    ring = _ring()
    cut = ring.index(next(e for e in ring if e["name"] == "router.pump" and e["ph"] == "E"))
    clock = span_clock.fit(_host(), ring[cut:])   # first pump's B evicted
    assert clock is not None and clock["pairs"] == PUMPS - 1


def test_fit_gives_nothing_on_too_few_or_scrambled_pairs():
    n = span_clock.MIN_PAIRS - 1
    assert span_clock.fit(_host(n), _ring(n)) is None
    host = _host()
    rng = np.random.default_rng(0)
    jitter = rng.integers(-300_000, 300_000, len(host))   # +-300 us per pump
    scrambled = [[n_, s + int(j), d] for (n_, s, d), j in zip(host, jitter)]
    assert span_clock.fit(scrambled, _ring()) is None


def test_idle_in_pump_share():
    tr = _trace()
    # per pump 1000 us inside, 400 us of it busy: 600 us idle
    want = 100 * PUMPS * 600 * 1000 * B / (tr["window"][1] - tr["window"][0])
    got = S.metric_reader("device.idle_in_pump_share.serve")(_run(tr, _ring()))
    assert got == pytest.approx(want, rel=1e-4)


def test_pump_host_ms():
    got = S.metric_reader("router.pump_host_ms.serve")(_run(_trace(), _ring()))
    assert got == pytest.approx((PUMP_US - WAIT_US) / 1e3)


def test_cache_insert_share():
    window_s = PUMPS * PERIOD_US / 1e6
    got = S.metric_reader("cache.insert_share.serve")(_run(_trace(), _ring(), window_s))
    assert got == pytest.approx(100 * PUMPS * INSERT_US / 1e6 / window_s)
    # inserts without a kind (a program that spans only one site) read nothing
    assert S.metric_reader("cache.insert_share.serve")(
        _run(_trace(), _ring(kind=False), window_s)) is None


@pytest.mark.parametrize("name", ["device.idle_in_pump_share.serve",
                                  "router.pump_host_ms.serve",
                                  "cache.insert_share.serve"])
def test_reader_gives_nothing_on_an_empty_ring(name):
    assert S.metric_reader(name)(_run(_trace(), [], 1.0)) is None


def test_pump_stalls_names_the_span_over_the_longest_idle():
    from bench.tools import pump_stalls

    ring, host = _ring(), _host()
    # the 5th pump runs 500 us longer, the device idle, a GC in the middle
    t = T0_US + 5 * PERIOD_US
    end = next(e for e in ring if e["name"] == "router.pump" and e["ph"] == "E"
               and e["ts"] == t + PUMP_US)
    end["ts"] = t + PUMP_US + 500
    a = _prof(t - 1)
    host[5] = ["bench.pump", int(a), int(_prof(t + PUMP_US + 501) - a)]
    ring += [{"name": "runtime.gc", "ph": "B", "ts": t + 1000, "tid": 1,
              "args": {"generation": 2}},
             {"name": "runtime.gc", "ph": "E", "ts": t + 1100, "tid": 1,
              "args": {"collected": 3}}]
    trace = {**_trace(), "host": host}
    out = pump_stalls.analyse(trace, ring, top=3)
    assert out["fit"]["pairs"] == PUMPS and len(out["pumps"]) == 3
    assert out["gc"] == {"2": {"count": 1, "total_ms": pytest.approx(0.1 * B),
                               "max_ms": pytest.approx(0.1 * B)}}
    row = out["pumps"][0]
    assert row["pump_ms"] == pytest.approx(1.5 * B, rel=1e-4)
    assert row["idle_ms"] == pytest.approx(1.1 * B, rel=1e-4)
    # idle 0-200 and 600-1500 us into the pump: the GC covers the longer
    assert row["longest_idle_ms"] == pytest.approx(0.9 * B, rel=1e-4)
    assert row["covered_by"] == "runtime.gc"
    assert row["covered_args"] == {"generation": 2, "collected": 3}
    assert row["spans_ms"]["cache.insert"] == pytest.approx(0.1 * B, rel=1e-4)
