"""What ran how: the programs XLA compiles (not those loaded from the
persistent cache), from JAX's own monitoring events, and the program's ops
that did not resolve to compiled Pallas."""
from __future__ import annotations


def oracle_ops() -> int:
    """Ops the program's dispatch resolved to anything but compiled Pallas
    (a jnp oracle, or an interpreted kernel) since ``STATS`` was reset."""
    from repro.kernels import dispatch

    counts = dispatch.STATS.snapshot()
    return (sum(v for (op, b), v in counts.items() if b != "pallas")
            + sum(dispatch.STATS.interpreted.values()))


class Counter:
    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs
            self.names.append(str(kw.get("fun_name", "?")))
