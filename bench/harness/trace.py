"""Reduction of a profiler trace to the benchmark's device numbers.

``capture`` runs the JAX profiler around a window and ``extract`` turns
its ``.xplane.pb`` into plain lists: per device, the events of its op line
(name, start ns, duration ns); on the host, the spans (name, start,
duration). Everything else here works on those lists, so the recorded
trace in ``bench/tests/data`` exercises the same arithmetic as a chip run.

Definitions:
* busy: the union of a device's op intervals inside the window; idle share
  = 1 - busy / window, averaged over the devices;
* kernel time: the summed durations of the op events whose name matches;
* idle gaps: the stretches of the window with no op on a device, each
  labelled by the innermost host span of the benchmark (``bench.*``) that
  covers its midpoint.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

# the per-device line that holds one event per executed XLA op / kernel
OP_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"(\.\d+)+$")
_INST = re.compile(r"^%?([\w.\-]+)")


@contextlib.contextmanager
def capture(out: dict):
    """Profile the enclosed block inside a ``bench.window`` host span; on
    exit ``out["trace"]`` holds ``extract``'s result. The raw trace is
    written under the temporary directory and removed after reading."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        # host spans come from TraceAnnotation alone: level 1 keeps them
        # and drops JAX's own dispatch events, which slow a host-bound run
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        out["trace"] = extract(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def extract(path: str) -> dict:
    """{"window": [t0, t1], "devices": {plane: [[name, t, dur], ...]},
    "host": [[name, t, dur], ...]} from one xplane file (ns, profiler clock)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = [[e.name, e.start_ns, e.duration_ns]
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns, e.duration_ns])
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    t0, dur = win[0][1], win[0][2]
    return {"window": [t0, t0 + dur], "devices": devices,
            "host": [h for h in host if h[0] != WINDOW_SPAN]}


def op_name(name: str) -> str:
    """The op of an event: the HLO instruction's name (an event may carry
    the instruction's whole text, "%name.12 = f32[...] ..."), without
    XLA's numeric suffixes."""
    m = _INST.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def busy_intervals(events, t0, t1) -> list:
    """Union of the op intervals inside [t0, t1], as sorted [a, b] pairs."""
    out = []
    for _, a, b in sorted(_clip(events, t0, t1), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_seconds(trace: dict) -> float:
    """Device busy time in the window, averaged over the devices."""
    t0, t1 = trace["window"]
    devs = trace["devices"].values()
    if not devs:
        return 0.0
    tot = sum(sum(b - a for a, b in busy_intervals(ev, t0, t1)) for ev in devs)
    return tot / len(devs) / 1e9


def window_seconds(trace: dict) -> float:
    t0, t1 = trace["window"]
    return (t1 - t0) / 1e9


def idle_share(trace: dict) -> float | None:
    if not trace["devices"]:
        return None
    return 1.0 - busy_seconds(trace) / window_seconds(trace)


def op_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """(seconds per device, events per device) of the ops whose name
    (``op_name``) matches ``pattern`` (a regular expression, searched), in
    the window."""
    t0, t1 = trace["window"]
    rx = re.compile(pattern)
    devs = list(trace["devices"].values())
    if not devs:
        return 0.0, 0
    secs = n = 0
    for ev in devs:
        for name, a, b in _clip(ev, t0, t1):
            if rx.search(op_name(name)):
                secs += b - a
                n += 1
    return secs / len(devs) / 1e9, n // len(devs)


def top_ops(trace: dict, k: int = 10) -> list:
    """[[op, seconds per device], ...] of the k ops that took most time."""
    t0, t1 = trace["window"]
    devs = list(trace["devices"].values())
    agg: dict = {}
    for ev in devs:
        for name, a, b in _clip(ev, t0, t1):
            key = op_name(name)
            agg[key] = agg.get(key, 0) + (b - a)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / max(len(devs), 1) / 1e9] for name, ns in top]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """[[label, seconds], ...]: the k longest stretches of the window with
    no op on the first device, each labelled by the innermost bench host
    span covering its midpoint ("none" where no span does)."""
    t0, t1 = trace["window"]
    devs = list(trace["devices"].values())
    if not devs:
        return []
    busy = busy_intervals(devs[0], t0, t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = trace["host"]
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        label = min(cover, key=lambda s: s[2])[0] if cover else "none"
        out.append([label, (b - a) / 1e9])
    return out


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": idle_gaps(trace)}


def span(name: str):
    """A host span on the profiler's clock (shown in ``idle_gaps``)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
