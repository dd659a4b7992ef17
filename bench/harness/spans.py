"""The program's own spans (``repro.obs.trace`` events, host clock)."""
from __future__ import annotations


def durations(events, name: str, **match) -> list:
    """Durations (s) of the completed spans called ``name`` whose begin
    event's args hold every ``match`` item."""
    open_, out = {}, []
    for ev in events or []:
        if ev.get("name") != name:
            continue
        key = ev.get("tid")
        if ev["ph"] == "B":
            open_[key] = ev
        elif ev["ph"] == "E" and key in open_:
            b = open_.pop(key)
            args = b.get("args", {})
            if all(args.get(k) == v for k, v in match.items()):
                out.append((ev["ts"] - b["ts"]) / 1e6)
    return out
