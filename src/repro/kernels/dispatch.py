"""Kernel dispatch layer: one registry routing every hot-path op to its
Pallas kernel or its jnp oracle.

The FloatSD8 kernels (``floatsd_matmul``, ``lstm_cell``, ``floatsd_quantize``,
``qsigmoid``) each register a ``ref`` oracle and a ``pallas`` implementation.
Resolution per call site weighs three things:

  * **backend policy** — ``REPRO_KERNEL_BACKEND=ref|pallas|auto`` (env), a
    ``use_backend(...)`` context override, or an explicit ``backend=``
    argument; precedence: argument > context > env; default ``auto``.
  * **platform** — Pallas runs compiled on TPU and in ``interpret=True``
    validation mode everywhere else (``REPRO_KERNEL_INTERPRET=0|1``
    overrides off a TPU; ``=1`` is refused on one). ``auto`` therefore
    resolves to ``ref`` off-TPU — the interpreter is a correctness tool,
    not a fast path — and ``pallas`` on TPU. ``backend="pallas"`` forces
    the kernel path anywhere (interpreted off-TPU), which is how the
    parity suite exercises it.
  * **shape divisibility** — inputs the tiling doesn't divide are padded up
    to tile multiples (zero activations x zero-code weights contribute an
    exact 0.0). On a TPU the oracle runs only when asked for (``ref``):
    an op with no padded path raises there (:class:`OracleRefused`)
    rather than run the oracle in the kernel's place.

Every resolution is recorded in ``STATS``: per-``(op, backend)`` counters
plus the last ``Decision`` per op. Tests assert on these, so a tiling
regression cannot quietly turn every call into jnp. Jit caveat: inside a
jitted caller the resolver runs at trace time, so the counters count
(shape-distinct) traces, not executions — which is exactly the granularity
at which the backend choice is made.

``PackedTensor`` lives here (re-exported by ``serving.weight_store``) so the
nn layer can consume packed weights without depending on the serving stack.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import threading
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import floatsd, floatsd4
from ..obs import costmodel
from ..obs import telemetry as obs_telemetry
from .flash_attention import cost as fa_cost
from .flash_attention.kernel import flash_attention_pallas
from .flash_attention.ops import flash_attention_kernel, flash_tiles
from .flash_attention.ref import flash_attention_ref
from .floatsd_matmul import cost as fm_cost
from .floatsd_matmul.bwd import (
    matmul_dw_pallas,
    matmul_dw_ref,
    matmul_dx_pallas,
    matmul_dx_ref,
)
from .floatsd_matmul.kernel import floatsd_matmul_pallas
from .floatsd_matmul.ref import floatsd_matmul_ref
from .floatsd4_matmul import cost as fm4_cost
from .floatsd4_matmul.kernel import floatsd4_matmul_pallas
from .floatsd4_matmul.ref import floatsd4_matmul_ref
from .floatsd_quantize import cost as fq_cost
from .floatsd_quantize.kernel import quantize_pallas
from .lstm_cell import cost as lc_cost
from .lstm_cell.bwd import lstm_cell_bwd_pallas, lstm_cell_bwd_ref
from .lstm_cell.kernel import lstm_cell_pallas
from .lstm_cell.ref import lstm_cell_ref
from .qsigmoid import cost as qs_cost
from .qsigmoid.kernel import qsigmoid_pallas
from .qsigmoid.ref import qsigmoid_ref
from .rwkv_wkv import cost as wkv_cost
from .rwkv_wkv.kernel import wkv_pallas
from .rwkv_wkv.ops import wkv as wkv_op
from .rwkv_wkv.ref import wkv_ref

__all__ = [
    "BACKENDS", "PackedTensor", "PackedTensor4", "Decision",
    "DispatchStats", "STATS", "LEDGER", "record", "backend_policy",
    "use_backend", "interpret_mode", "matmul", "matmul4", "lstm_cell",
    "quantize", "qsigmoid", "packed_einsum", "hoist_packed", "matmul_tiles",
    "lstm_tiles", "row_tile", "matmul_dx", "matmul_dw", "lstm_cell_grad",
    "train_matmul", "lstm_cell_train", "pack_train", "hoist_train",
    "inference_only", "is_packed", "is_packed4", "pack4", "unpack4",
    "rwkv_wkv", "flash_attention", "OpSpec", "REGISTRY", "OracleRefused",
]

BACKENDS = ("ref", "pallas", "auto")

# uint8 code that decodes to exactly 0.0 at any bias: e=0, mantissa index of
# 0.0 in the symmetric 31-entry grid.
ZERO_CODE = int(np.searchsorted(floatsd.MANTISSA_VALUES, 0.0))


class PackedTensor(NamedTuple):
    """A FloatSD8-packed tensor: uint8 codes + scalar int32 exponent bias.

    NamedTuple => a pytree node, so packed trees pass through jit/tree_map
    transparently with codes/bias as leaves.
    """

    codes: jax.Array  # uint8, same shape as the dense tensor
    bias: jax.Array  # int32 scalar (per-tensor exponent bias)


def is_packed(x: Any) -> bool:
    return isinstance(x, PackedTensor)


# byte whose both nibbles are the FloatSD4 zero code: the tile-padding
# constant for nibble-packed code streams (decodes to exact 0.0 anywhere)
ZERO_BYTE4 = (floatsd4.ZERO_CODE << 4) | floatsd4.ZERO_CODE


@jax.tree_util.register_pytree_node_class
class PackedTensor4:
    """A FloatSD4-packed tensor: nibble-packed uint8 codes (2 codes/byte
    along axis 0) + int8 per-(GROUP x column) exponents + the true axis-0
    length ``k``.

    ``k`` rides as static pytree aux data, not a leaf: the unpack crop of
    an odd-K tensor needs it at trace time, and it is metadata, not data.
    Registered as a pytree node so packed trees pass through jit/tree_map
    with codes/exps as leaves, exactly like :class:`PackedTensor`.
    """

    __slots__ = ("codes", "exps", "k")

    def __init__(self, codes: jax.Array, exps: jax.Array, k: int):
        self.codes = codes  # uint8 [ceil(k/2), ...] nibble-packed
        self.exps = exps  # int8 [ceil(k/GROUP), ...]
        self.k = int(k)

    @property
    def shape(self) -> tuple:
        return (self.k,) + tuple(self.codes.shape[1:])

    def tree_flatten(self):
        return (self.codes, self.exps), self.k

    @classmethod
    def tree_unflatten(cls, k, children):
        return cls(children[0], children[1], k)

    def __repr__(self) -> str:
        return (
            f"PackedTensor4(codes={getattr(self.codes, 'shape', None)}, "
            f"exps={getattr(self.exps, 'shape', None)}, k={self.k})"
        )


def is_packed4(x: Any) -> bool:
    return isinstance(x, PackedTensor4)


def pack4(w) -> PackedTensor4:
    """FloatSD4-encode a weight (dense array or FloatSD8 PackedTensor —
    the serving conversion decodes the FloatSD8 master first)."""
    if is_packed(w):
        w = floatsd.decode(w.codes, w.bias, dtype=jnp.float32)
    codes, exps = floatsd4.encode(w)
    return PackedTensor4(floatsd4.pack_nibbles(codes), exps, w.shape[0])


def unpack4(w4: PackedTensor4, dtype=jnp.float32) -> jax.Array:
    """Decode a PackedTensor4 back to a dense tensor."""
    return floatsd4.decode_packed(w4.codes, w4.exps, w4.k, dtype=dtype)


# ---------------------------------------------------------------------------
# backend policy + decision record
# ---------------------------------------------------------------------------


class Decision(NamedTuple):
    op: str
    backend: str  # "ref" | "pallas"
    interpret: bool
    padded: bool
    reason: str
    # predicted cost of THIS call (costmodel.Cost) — attached by the
    # dispatched entry point once the resolved backend/tiling is known
    cost: Any = None


class DispatchStats:
    """Per-(op, backend) resolution counters, the last Decision per op,
    and the cost-ledger accumulators.

    Three sinks beyond the decision counters feed ``LEDGER``:

      * ``costs`` — predicted :class:`~repro.obs.costmodel.Cost` totals,
        accumulated from each recorded Decision (trace time);
      * ``touched`` — unique bytes of the ndarrays the dispatch actually
        handed to the backend plus its outputs, computed from array
        metadata (``size * itemsize`` — works on tracers). On ref this is
        the measurement the predicted bytes must match exactly;
      * ``wall`` — measured (timed_calls, seconds) per (op, backend), fed
        by ``bench_kernels.py --ledger`` via :meth:`add_time` — per-op
        wall attribution is only honest at microbenchmark granularity.

    Lock-guarded: resolutions happen at trace time on whatever thread is
    tracing (the serving pump worker, a test thread), while the /metrics
    scrape path reads ``snapshot()`` from the HTTP event loop — iterating
    the Counter during a concurrent ``record`` would be a data race."""

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()
        # per-op resolutions that run the Pallas interpreter (a chip run
        # must show none)
        self.interpreted: collections.Counter = collections.Counter()
        self.last: dict[str, Decision] = {}
        self.costs: dict[tuple[str, str], costmodel.Cost] = {}
        self.touched: collections.Counter = collections.Counter()
        self.wall: dict[tuple[str, str], list] = {}
        self._lock = threading.Lock()

    def record(self, d: Decision) -> None:
        with self._lock:
            key = (d.op, d.backend)
            self.counts[key] += 1
            if d.interpret:
                self.interpreted[d.op] += 1
            self.last[d.op] = d
            if d.cost is not None:
                self.costs[key] = costmodel.merge_costs(
                    self.costs.get(key, costmodel.ZERO_COST), d.cost
                )

    def add_touched(self, op: str, backend: str, nbytes: int) -> None:
        with self._lock:
            self.touched[(op, backend)] += int(nbytes)

    def add_time(self, op: str, backend: str, seconds: float,
                 calls: int = 1) -> None:
        """Attribute measured wall time to (op, backend) — the ledger's
        measured column. Callers time *executions*; the predicted side
        counts *traces*, so the ledger normalizes both per call."""
        with self._lock:
            entry = self.wall.setdefault((op, backend), [0, 0.0])
            entry[0] += int(calls)
            entry[1] += float(seconds)

    def count(self, op: str | None = None, backend: str | None = None) -> int:
        with self._lock:
            return sum(
                n for (o, b), n in self.counts.items()
                if (op is None or o == op) and (backend is None or b == backend)
            )

    def reset(self) -> None:
        with self._lock:
            self.counts.clear()
            self.interpreted.clear()
            self.last.clear()
            self.costs.clear()
            self.touched.clear()
            self.wall.clear()

    def snapshot(self) -> dict:
        """{(op, backend): resolutions} — what /metrics exports as
        ``repro_dispatch_decisions_total{op,backend}``."""
        with self._lock:
            return dict(self.counts)

    def cost_snapshot(self) -> dict:
        """{(op, backend): {cost, calls, touched_bytes, timed_calls,
        wall_s}} — the CostLedger's raw join input."""
        with self._lock:
            keys = (
                set(self.counts) | set(self.costs) | set(self.touched)
                | set(self.wall)
            )
            return {
                key: {
                    "cost": self.costs.get(key, costmodel.ZERO_COST),
                    "calls": self.counts.get(key, 0),
                    "touched_bytes": self.touched.get(key, 0),
                    "timed_calls": self.wall.get(key, (0, 0.0))[0],
                    "wall_s": self.wall.get(key, (0, 0.0))[1],
                }
                for key in keys
            }


STATS = DispatchStats()

#: Predicted-vs-measured cost ledger over STATS — the observatory's
#: joined view (trace counter tracks, /metrics export, --ledger artifacts).
LEDGER = costmodel.CostLedger(STATS)


def record(op: str, backend: str, *, interpret: bool = False,
           padded: bool = False, reason: str = "",
           cost: costmodel.Cost | None = None) -> Decision:
    d = Decision(op, backend, interpret, padded, reason, cost)
    STATS.record(d)
    return d


def _nbytes(*arrays) -> int:
    """Sum of ``size * itemsize`` over arrays/tracers/scalars — the
    unique-bytes-touched measurement the ref cost model must reproduce."""
    total = 0
    for a in arrays:
        if a is None:
            continue
        dt = getattr(a, "dtype", None)
        if dt is None:
            a = np.asarray(a)
            dt = a.dtype
        total += int(getattr(a, "size", 1)) * jnp.dtype(dt).itemsize
    return total


_OVERRIDE: list[str] = []  # use_backend() stack


def backend_policy(backend: str | None = None) -> str:
    """Effective policy: explicit argument > use_backend() > env > auto."""
    pol = backend or (_OVERRIDE[-1] if _OVERRIDE else None) or os.environ.get(
        "REPRO_KERNEL_BACKEND", "auto"
    ).lower()
    if pol not in BACKENDS:
        raise ValueError(f"REPRO_KERNEL_BACKEND must be one of {BACKENDS}, got {pol!r}")
    return pol


@contextlib.contextmanager
def use_backend(name: str):
    """Force a backend for all dispatch resolutions inside the context."""
    if name not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {name!r}")
    _OVERRIDE.append(name)
    try:
        yield
    finally:
        _OVERRIDE.pop()


class OracleRefused(RuntimeError):
    """A TPU run would have put the jnp oracle or the Pallas interpreter in
    a kernel's place without being asked to (``backend="ref"`` asks)."""


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas execution mode for this process: compiled on TPU, interpreted
    elsewhere. REPRO_KERNEL_INTERPRET=0 compiles anywhere (the compile
    rehearsal); =1 interprets, and is refused on a TPU, where an
    interpreted kernel would run in the compiled one's place."""
    env = os.environ.get("REPRO_KERNEL_INTERPRET")
    if env is not None and env != "0":
        if _on_tpu():
            raise OracleRefused(
                "REPRO_KERNEL_INTERPRET=1 on a TPU: the kernels run compiled "
                "there; unset it")
        return True
    if env is not None:
        return False
    return not _on_tpu()


def _decide(op: str, native: bool, waste: float, backend: str | None) -> Decision:
    """Pure resolution (no recording). ``native``: tiling divides as-is;
    ``waste``: padded/exact work ratio if padding were used."""
    pol = backend_policy(backend)
    interp = interpret_mode()
    if pol == "ref":
        return Decision(op, "ref", False, False, "policy:ref")
    if pol == "pallas":
        if native:
            return Decision(op, "pallas", interp, False, "policy:pallas")
        return Decision(
            op, "pallas", interp, True, f"policy:pallas, padded ({waste:.2f}x work)"
        )
    # auto
    if interp:
        return Decision(
            op, "ref", False, False, "auto:off-tpu (interpret is validation-only)"
        )
    if native:
        return Decision(op, "pallas", False, False, "auto:tpu, native tiles")
    return Decision(
        op, "pallas", False, True, f"auto:tpu, padded ({waste:.2f}x work)"
    )


def _choose(op: str, native: bool, waste: float, backend: str | None) -> Decision:
    d = _decide(op, native, waste, backend)
    STATS.record(d)
    return d


# ---------------------------------------------------------------------------
# tile planning (shared with the per-kernel ops wrappers)
# ---------------------------------------------------------------------------


def _ceil_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def matmul_tiles(m: int, n: int, k: int) -> tuple[int, int, int]:
    """Largest power-of-two-halved MXU-aligned blocks dividing (m, n, k)."""
    bm = max(8, min(256, m))
    bn = min(256, n)
    bk = min(512, k)
    while m % bm:
        bm //= 2
    while n % bn:
        bn //= 2
    while k % bk:
        bk //= 2
    return bm, bn, bk


def lstm_tiles(b: int, h: int) -> tuple[int, int]:
    bb = 8
    while b % bb == 0 and bb < 128:
        bb *= 2
    if b % bb:
        bb //= 2
    bh = 128
    while h % bh == 0 and bh < 512:
        bh *= 2
    if h % bh:
        bh //= 2
    return bb, bh


def row_tile(rows: int) -> int:
    """Largest block <= 256 that divides ``rows`` by repeated halving (the
    flattened-2D elementwise kernels: quantize, qsigmoid)."""
    bm = min(256, rows)
    while rows % bm:
        bm //= 2
    return max(bm, 1)


def _matmul_geometry(m: int, k: int, n: int):
    """(native, padded-work ratio, padded dims) for an [M,K]x[K,N] call —
    the single source of the alignment arithmetic, shared by ``matmul`` and
    ``hoist_packed`` so the hoist prediction can never diverge from the
    per-call decision."""
    mp, kp, np_ = _ceil_to(max(m, 1), 8), _ceil_to(k, 128), _ceil_to(n, 128)
    native = (mp, kp, np_) == (m, k, n)
    waste = (mp * kp * np_) / max(m * k * n, 1)
    return native, waste, (mp, kp, np_)


# ---------------------------------------------------------------------------
# dispatched ops
# ---------------------------------------------------------------------------


def matmul(x, codes, bias, *, out_dtype=jnp.float32, precise: bool = True,
           compute_dtype=None, backend: str | None = None):
    """x [..., K] @ decode(codes [K, N]) -> [..., N], backend-resolved.

    ``precise=True`` issues the kernel's MXU dot in f32 (parity with the
    oracle to ~1e-6 relative); ``precise=False`` uses the bf16 issue dtype
    (full MXU rate, the paper's accumulate-in-f32 datapath). An explicit
    ``compute_dtype`` (e.g. a bf16-compute policy's cdt) overrides both.
    """
    if compute_dtype is None:
        compute_dtype = jnp.float32 if precise else jnp.bfloat16
    k = x.shape[-1]
    k2, n = codes.shape
    assert k == k2, (x.shape, codes.shape)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    native, waste, (mp, kp, np_) = _matmul_geometry(m, k, n)
    dec = _decide("floatsd_matmul", native, waste, backend)
    x_bytes = jnp.dtype(x.dtype).itemsize
    o_bytes = jnp.dtype(out_dtype).itemsize
    if dec.backend == "ref":
        y = floatsd_matmul_ref(x2, codes, bias, out_dtype)
        cost = fm_cost.matmul_fwd_cost(
            m, k, n, backend="ref", x_bytes=x_bytes, out_bytes=o_bytes,
        )
        touched = _nbytes(x2, codes, bias, y)
        out = y
    else:
        xx, cc = x2, codes
        if dec.padded:
            xx = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
            cc = jnp.pad(codes, ((0, kp - k), (0, np_ - n)), constant_values=ZERO_CODE)
        bm, bn, bk = matmul_tiles(xx.shape[0], cc.shape[1], xx.shape[1])
        y = floatsd_matmul_pallas(
            xx, cc, bias, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
            compute_dtype=compute_dtype,
            interpret=dec.interpret,
        )
        cost = fm_cost.matmul_fwd_cost(
            m, k, n, backend="pallas", x_bytes=x_bytes, out_bytes=o_bytes,
            compute_bytes=jnp.dtype(compute_dtype).itemsize,
            padded=(mp, kp, np_), tiles=(bm, bn, bk),
        )
        touched = _nbytes(xx, cc, bias, y)
        out = y[:m, :n] if dec.padded else y
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("floatsd_matmul", dec.backend, touched)
    return out.reshape(*lead, n)


def matmul4(x, w4: PackedTensor4, *, out_dtype=jnp.float32,
            precise: bool = True, compute_dtype=None, transpose: bool = False,
            backend: str | None = None):
    """x [..., K] @ decode4(w4 [K, N]) -> [..., N], backend-resolved.

    The FloatSD4 sibling of :func:`matmul`: the weight operand is a
    :class:`PackedTensor4` (nibble-packed codes + group exponents), so the
    pallas path streams ~half the weight bytes of the FloatSD8 kernel.
    ``transpose``: w4 is stored [N, K] (packed along N, the tied logits
    head) and the product is x @ decode4(w4)^T; the kernel contracts the
    code tile's second axis, so the nibbles stream as stored.
    Padding uses the zero-code convention: code columns/rows pad with
    ``ZERO_BYTE4`` (both nibbles the zero code) and exponent rows with 0 —
    both decode to exact 0.0, so padded lanes contribute nothing.
    """
    if compute_dtype is None:
        compute_dtype = jnp.float32 if precise else jnp.bfloat16
    k = x.shape[-1]
    if transpose:
        assert w4.codes.shape[1] == k, (x.shape, w4.shape)
        n = w4.k
    else:
        assert w4.k == k, (x.shape, w4.shape)
        n = w4.codes.shape[1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    native, waste, (mp, kp, np_) = _matmul_geometry(m, k, n)
    dec = _decide("floatsd4_matmul", native, waste, backend)
    x_bytes = jnp.dtype(x.dtype).itemsize
    o_bytes = jnp.dtype(out_dtype).itemsize
    if dec.backend == "ref":
        if transpose:
            wq = floatsd4.decode_packed(w4.codes, w4.exps, w4.k)
            y = jnp.dot(x2.astype(jnp.float32), wq.T,
                        preferred_element_type=jnp.float32).astype(out_dtype)
        else:
            y = floatsd4_matmul_ref(x2, w4.codes, w4.exps, k, out_dtype)
        cost = fm4_cost.matmul4_fwd_cost(
            m, k, n, backend="ref", x_bytes=x_bytes, out_bytes=o_bytes,
        )
        touched = _nbytes(x2, w4.codes, w4.exps, y)
        out = y
    else:
        xx, cc, ee = x2, w4.codes, w4.exps
        if dec.padded:
            # (packed rows, columns) of the padded code / exponent arrays
            pr, pc = (np_, kp) if transpose else (kp, np_)
            xx = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
            cc = jnp.pad(
                cc, ((0, pr // 2 - cc.shape[0]), (0, pc - cc.shape[1])),
                constant_values=ZERO_BYTE4,
            )
            ee = jnp.pad(
                ee, ((0, pr // floatsd4.GROUP - ee.shape[0]),
                     (0, pc - ee.shape[1]))
            )
        bm, bn, bk = matmul_tiles(xx.shape[0], np_ if dec.padded else n,
                                  xx.shape[1])
        y = floatsd4_matmul_pallas(
            xx, cc, ee, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
            compute_dtype=compute_dtype, transpose_w=transpose,
            interpret=dec.interpret,
        )
        cost = fm4_cost.matmul4_fwd_cost(
            m, k, n, backend="pallas", x_bytes=x_bytes, out_bytes=o_bytes,
            compute_bytes=jnp.dtype(compute_dtype).itemsize,
            padded=(mp, kp, np_), tiles=(bm, bn, bk),
        )
        touched = _nbytes(xx, cc, ee, y)
        out = y[:m, :n] if dec.padded else y
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("floatsd4_matmul", dec.backend, touched)
    return out.reshape(*lead, n)


def lstm_cell(z, c_prev, *, quantized: bool = True, c_dtype=jnp.float16,
              backend: str | None = None):
    """Fused gates -> (h, c), backend-resolved. z: [B, 4H] (i|f|g|o)."""
    b, h4 = z.shape
    h = h4 // 4
    bp, hp = _ceil_to(max(b, 1), 8), _ceil_to(max(h, 1), 128)
    native = (bp, hp) == (b, h)
    waste = (bp * hp) / max(b * h, 1)
    dec = _decide("lstm_cell", native, waste, backend)
    dtypes = dict(
        z_bytes=jnp.dtype(z.dtype).itemsize,
        c_in_bytes=jnp.dtype(c_prev.dtype).itemsize,
    )
    if dec.backend == "ref":
        h_t, c_t = lstm_cell_ref(z, c_prev, quantized, c_dtype=c_dtype)
        cost = lc_cost.lstm_cell_cost(
            b, h, backend="ref",
            h_out_bytes=jnp.dtype(h_t.dtype).itemsize,
            c_out_bytes=jnp.dtype(c_t.dtype).itemsize, **dtypes,
        )
        touched = _nbytes(z, c_prev, h_t, c_t)
        STATS.record(dec._replace(cost=cost))
        STATS.add_touched("lstm_cell", "ref", touched)
        return h_t, c_t
    zz, cc = z, c_prev
    if dec.padded:
        zz = jnp.pad(
            z.reshape(b, 4, h), ((0, bp - b), (0, 0), (0, hp - h))
        ).reshape(bp, 4 * hp)
        cc = jnp.pad(c_prev, ((0, bp - b), (0, hp - h)))
    bb, bh = lstm_tiles(bp, hp)
    h_t, c_t = lstm_cell_pallas(
        zz, cc, bb=bb, bh=bh, quantized=quantized, c_dtype=c_dtype,
        interpret=dec.interpret,
    )
    cost = lc_cost.lstm_cell_cost(
        b, h, backend="pallas",
        h_out_bytes=jnp.dtype(h_t.dtype).itemsize,
        c_out_bytes=jnp.dtype(c_t.dtype).itemsize,
        padded=(bp, hp), tiles=(bb, bh), **dtypes,
    )
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("lstm_cell", "pallas", _nbytes(zz, cc, h_t, c_t))
    if dec.padded:
        h_t, c_t = h_t[:b, :h], c_t[:b, :h]
    return h_t, c_t


def quantize(x, bias=None, *, backend: str | None = None):
    """Any-shape tensor -> (uint8 FloatSD8 codes, int32 bias), resolved."""
    if bias is None:
        bias = floatsd.fit_bias(x)
    n = x.size
    # native = reshapes to [8k, 256] — rows a multiple of 8 so the layout is
    # TPU-tileable (f32 min tile is 8x128); anything else pads to that
    np_ = _ceil_to(max(n, 1), 8 * 256)
    native = n > 0 and n % (8 * 256) == 0
    waste = np_ / max(n, 1)
    dec = _decide("floatsd_quantize", native, waste, backend)
    x_bytes = jnp.dtype(x.dtype).itemsize
    if dec.backend == "ref":
        codes, _ = floatsd.encode(x, bias)
        cost = fq_cost.quantize_cost(n, backend="ref", x_bytes=x_bytes)
        STATS.record(dec._replace(cost=cost))
        STATS.add_touched("floatsd_quantize", "ref", _nbytes(x, bias, codes))
        return codes, bias
    flat = x.reshape(-1)
    if dec.padded:
        flat = jnp.pad(flat, (0, np_ - n))
    x2 = flat.reshape(-1, 256)
    tile_rows = row_tile(x2.shape[0])
    codes2 = quantize_pallas(
        x2, bias, bm=tile_rows, bn=256, interpret=dec.interpret
    )
    cost = fq_cost.quantize_cost(
        n, backend="pallas", x_bytes=x_bytes, padded_n=np_,
        tile_rows=tile_rows,
    )
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("floatsd_quantize", "pallas", _nbytes(x2, bias, codes2))
    return codes2.reshape(-1)[:n].reshape(x.shape), bias


def qsigmoid(x, *, backend: str | None = None):
    """Two-region FloatSD8 sigmoid for any-shape tensors, resolved."""
    n = x.size
    np_ = _ceil_to(max(n, 1), 8 * 256)
    native = n > 0 and n % (8 * 256) == 0
    waste = np_ / max(n, 1)
    dec = _decide("qsigmoid", native, waste, backend)
    x_bytes = jnp.dtype(x.dtype).itemsize
    if dec.backend == "ref":
        y = qsigmoid_ref(x)
        cost = qs_cost.qsigmoid_cost(
            n, backend="ref", x_bytes=x_bytes,
            y_bytes=jnp.dtype(y.dtype).itemsize,
        )
        STATS.record(dec._replace(cost=cost))
        STATS.add_touched("qsigmoid", "ref", _nbytes(x, y))
        return y
    flat = x.reshape(-1)
    if dec.padded:
        flat = jnp.pad(flat, (0, np_ - n))
    x2 = flat.reshape(-1, 256)
    tile_rows = row_tile(x2.shape[0])
    y2 = qsigmoid_pallas(x2, bm=tile_rows, bn=256, interpret=dec.interpret)
    cost = qs_cost.qsigmoid_cost(
        n, backend="pallas", x_bytes=x_bytes,
        y_bytes=jnp.dtype(y2.dtype).itemsize, padded_n=np_,
        tile_rows=tile_rows,
    )
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("qsigmoid", "pallas", _nbytes(x2, y2))
    return y2.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# backward ops (the training hot path: fused quantized BPTT)
# ---------------------------------------------------------------------------


def matmul_dx(g, codes, bias, *, backend: str | None = None):
    """Activation gradient of the FloatSD8 matmul, backend-resolved:
    g [..., N] x decode(codes [K, N])^T -> [..., K] in f32 (the precise
    datapath — FP8 act-grad quantization lives at the act_quant STE nodes,
    not here). Pallas path reuses the forward decode-in-VMEM kernel on the
    transposed 1-byte codes."""
    k, n = codes.shape
    lead = g.shape[:-1]
    g2 = g.reshape(-1, n)
    m = g2.shape[0]
    # output [m, k], contraction over n
    native, waste, (mp, np_, kp) = _matmul_geometry(m, n, k)
    dec = _decide("floatsd_matmul_dx", native, waste, backend)
    g_bytes = jnp.dtype(g.dtype).itemsize
    if dec.backend == "ref":
        dx = matmul_dx_ref(g2, codes, bias)
        cost = fm_cost.matmul_dx_cost(
            m, n, k, backend="ref", g_bytes=g_bytes,
            out_bytes=jnp.dtype(dx.dtype).itemsize,
        )
        touched = _nbytes(g2, codes, bias, dx)
        out = dx
    else:
        gg, cc = g2, codes
        if dec.padded:
            gg = jnp.pad(g2, ((0, mp - m), (0, np_ - n)))
            cc = jnp.pad(codes, ((0, kp - k), (0, np_ - n)), constant_values=ZERO_CODE)
        bm, bn, bk = matmul_tiles(mp, kp, np_)
        dx = matmul_dx_pallas(gg, cc, bias, bm=bm, bn=bn, bk=bk,
                              interpret=dec.interpret)
        cost = fm_cost.matmul_dx_cost(
            m, n, k, backend="pallas", g_bytes=g_bytes,
            out_bytes=jnp.dtype(dx.dtype).itemsize,
            padded=(mp, np_, kp), tiles=(bm, bn, bk),
        )
        touched = _nbytes(gg, cc, bias, dx)
        out = dx[:m, :k] if dec.padded else dx
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("floatsd_matmul_dx", dec.backend, touched)
    return out.reshape(*lead, k)


def _dw_flush_telemetry(dw, quant: bool):
    """Quantizer-health hook at the matmul_dw flush: when the telemetry
    sink is enabled (checked at trace time — see ``KernelStats``), count
    saturated (|dw| at the e5m2 clamp) and zero (true zeros + underflow,
    already collapsed by the in-kernel quantizer) elements of the flushed
    dW and report them host-side via ``jax.debug.callback``."""
    if not (quant and obs_telemetry.KERNEL_STATS.enabled):
        return dw
    sat = jnp.sum(jnp.abs(dw) >= obs_telemetry.FP8_SAT_THRESHOLD)
    zero = jnp.sum(dw == 0)
    jax.debug.callback(
        functools.partial(
            obs_telemetry.KERNEL_STATS.record, "floatsd_matmul_dw", dw.size
        ),
        sat,
        zero,
    )
    return dw


def matmul_dw(x, g, *, quant: bool = True, backend: str | None = None):
    """Weight gradient of the FloatSD8 matmul, backend-resolved:
    x [..., K]^T x g [..., N] -> [K, N], f32 accumulation, the paper's FP8
    weight-gradient quantizer applied at the accumulator flush *inside* the
    kernel (``quant=False`` gives the raw f32 dw for parity oracles)."""
    k = x.shape[-1]
    n = g.shape[-1]
    x2 = x.reshape(-1, k)
    g2 = g.reshape(-1, n)
    m = x2.shape[0]
    assert g2.shape[0] == m, (x.shape, g.shape)
    # output [k, n], contraction over m (rows pad to 8, lanes to 128)
    native, waste, (kp, mp, np_) = _matmul_geometry(k, m, n)
    dec = _decide("floatsd_matmul_dw", native, waste, backend)
    xg_bytes = dict(
        x_bytes=jnp.dtype(x.dtype).itemsize,
        g_bytes=jnp.dtype(g.dtype).itemsize,
    )
    if dec.backend == "ref":
        dw = matmul_dw_ref(x2, g2, quant=quant)
        cost = fm_cost.matmul_dw_cost(
            k, m, n, backend="ref", quant=quant,
            out_bytes=jnp.dtype(dw.dtype).itemsize, **xg_bytes,
        )
        STATS.record(dec._replace(cost=cost))
        STATS.add_touched("floatsd_matmul_dw", "ref", _nbytes(x2, g2, dw))
        return _dw_flush_telemetry(dw, quant)
    xx, gg = x2, g2
    if dec.padded:
        xx = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
        gg = jnp.pad(g2, ((0, mp - m), (0, np_ - n)))
    bm, bn, bk = matmul_tiles(kp, np_, mp)
    dw = matmul_dw_pallas(xx, gg, bm=bm, bn=bn, bk=bk, quant=quant,
                          interpret=dec.interpret)
    cost = fm_cost.matmul_dw_cost(
        k, m, n, backend="pallas", quant=quant,
        out_bytes=jnp.dtype(dw.dtype).itemsize,
        padded=(kp, mp, np_), tiles=(bm, bn, bk), **xg_bytes,
    )
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("floatsd_matmul_dw", "pallas", _nbytes(xx, gg, dw))
    if dec.padded:
        dw = dw[:k, :n]
    return _dw_flush_telemetry(dw, quant)


def lstm_cell_grad(z, c_prev, dh, dc, *, quantized: bool = True,
                   c_dtype=jnp.float16, backend: str | None = None):
    """Recompute-gates backward of the fused cell, backend-resolved.
    z: [B, 4H], c_prev/dh/dc: [B, H] -> (dz [B, 4H] f32, dc_prev [B, H]).
    The only residuals it needs are (z, c_prev) — see kernels README,
    'backward ops'."""
    b, h4 = z.shape
    h = h4 // 4
    bp, hp = _ceil_to(max(b, 1), 8), _ceil_to(max(h, 1), 128)
    native = (bp, hp) == (b, h)
    waste = (bp * hp) / max(b * h, 1)
    dec = _decide("lstm_cell_grad", native, waste, backend)
    in_bytes = dict(
        z_bytes=jnp.dtype(z.dtype).itemsize,
        c_in_bytes=jnp.dtype(c_prev.dtype).itemsize,
        dh_bytes=jnp.dtype(dh.dtype).itemsize,
        dc_bytes=jnp.dtype(dc.dtype).itemsize,
    )
    if dec.backend == "ref":
        dz, dcp = lstm_cell_bwd_ref(z, c_prev, dh, dc, quantized,
                                    c_dtype=c_dtype)
        cost = lc_cost.lstm_cell_grad_cost(
            b, h, backend="ref",
            dz_bytes=jnp.dtype(dz.dtype).itemsize,
            dcp_bytes=jnp.dtype(dcp.dtype).itemsize, **in_bytes,
        )
        STATS.record(dec._replace(cost=cost))
        STATS.add_touched("lstm_cell_grad", "ref",
                          _nbytes(z, c_prev, dh, dc, dz, dcp))
        return dz, dcp
    zz, cc, dhh, dcc = z, c_prev, dh, dc
    if dec.padded:
        zz = jnp.pad(
            z.reshape(b, 4, h), ((0, bp - b), (0, 0), (0, hp - h))
        ).reshape(bp, 4 * hp)
        cc = jnp.pad(c_prev, ((0, bp - b), (0, hp - h)))
        dhh = jnp.pad(dh, ((0, bp - b), (0, hp - h)))
        dcc = jnp.pad(dc, ((0, bp - b), (0, hp - h)))
    bb, bh = lstm_tiles(bp, hp)
    dz, dcp = lstm_cell_bwd_pallas(
        zz, cc, dhh, dcc, bb=bb, bh=bh, quantized=quantized, c_dtype=c_dtype,
        interpret=dec.interpret,
    )
    cost = lc_cost.lstm_cell_grad_cost(
        b, h, backend="pallas",
        dz_bytes=jnp.dtype(dz.dtype).itemsize,
        dcp_bytes=jnp.dtype(dcp.dtype).itemsize,
        padded=(bp, hp), tiles=(bb, bh), **in_bytes,
    )
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("lstm_cell_grad", "pallas",
                      _nbytes(zz, cc, dhh, dcc, dz, dcp))
    if dec.padded:
        dz = dz.reshape(bp, 4, hp)[:b, :, :h].reshape(b, 4 * h)
        dcp = dcp[:b, :h]
    return dz, dcp


# ---------------------------------------------------------------------------
# sequence-mixing ops (model-zoo hot paths): rwkv wkv + flash attention.
# These kernels have no padded path — indivisible shapes fall back to the
# oracle (recorded, never silent), matching their ops.py wrappers.
# ---------------------------------------------------------------------------


def _decide_fallback(op: str, native: bool, why: str,
                     backend: str | None) -> Decision:
    """Resolution for ops without a padding path: pallas only when the
    tiling divides natively, ref otherwise — with the fallback reason
    recorded so a shape regression shows up in STATS, not in silence.
    On a TPU that fallback raises :class:`OracleRefused` instead."""
    pol = backend_policy(backend)
    interp = interpret_mode()
    if pol == "ref":
        return Decision(op, "ref", False, False, "policy:ref")
    if pol == "pallas" and native:
        return Decision(op, "pallas", interp, False, "policy:pallas")
    if pol == "auto":
        if interp:
            return Decision(op, "ref", False, False,
                            "auto:off-tpu (interpret is validation-only)")
        if native:
            return Decision(op, "pallas", False, False, "auto:tpu, native tiles")
    if _on_tpu():
        raise OracleRefused(
            f"{op}: {why} and the kernel has no padded path; pass "
            f"backend='ref' to run the jnp oracle on the TPU")
    reason = f"policy:pallas, but {why}" if pol == "pallas" else f"auto:{why}"
    return Decision(op, "ref", False, False,
                    f"{reason} -> ref oracle (no padded path)")


def rwkv_wkv(r, k, v, w, u, *, chunk: int = 16, backend: str | None = None):
    """Chunked RWKV-6 wkv, backend-resolved: r/k/w [BH, S, K], v [BH, S, V],
    u [BH, K] -> [BH, S, V]. Pallas keeps the [K, V] state in VMEM across
    chunk steps; indivisible S falls back to the per-token oracle."""
    bh, s, dk = r.shape
    dv = v.shape[-1]
    native = s > 0 and s % chunk == 0
    dec = _decide_fallback("rwkv_wkv", native, f"S={s} % chunk={chunk}", backend)
    cost = wkv_cost.wkv_cost(
        bh, s, dk, dv, backend=dec.backend, chunk=chunk,
        elem_bytes=jnp.dtype(r.dtype).itemsize,
    )
    y = wkv_op(r, k, v, w, u, chunk=chunk,
               use_kernel=dec.backend == "pallas", interpret=dec.interpret)
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("rwkv_wkv", dec.backend, _nbytes(r, k, v, w, u, y))
    return y


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    backend: str | None = None):
    """Flash attention forward, backend-resolved: q [BH, Sq, D],
    k/v [BH, Skv, D] -> [BH, Sq, D]. Pallas streams KV tiles against
    VMEM-resident (m, l, acc) state; misaligned dims fall back to the
    materialized-scores oracle."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    native = sq > 0 and sq % 8 == 0 and skv % 128 == 0 and d % 8 == 0
    dec = _decide_fallback(
        "flash_attention", native, f"Sq={sq}/Skv={skv}/D={d} misaligned",
        backend,
    )
    bq, bk = flash_tiles(sq, skv) if native else (None, None)
    cost = fa_cost.flash_attention_cost(
        bh, sq, skv, d, backend=dec.backend, causal=causal, window=window,
        elem_bytes=jnp.dtype(q.dtype).itemsize, bq=bq, bk=bk,
    )
    o = flash_attention_kernel(
        q, k, v, causal=causal, window=window,
        use_kernel=dec.backend == "pallas", interpret=dec.interpret,
    )
    STATS.record(dec._replace(cost=cost))
    STATS.add_touched("flash_attention", dec.backend, _nbytes(q, k, v, o))
    return o


# ---------------------------------------------------------------------------
# custom-VJP training entry points: the whole train step resolves to
# registered kernels, forward AND backward
# ---------------------------------------------------------------------------


def pack_train(w) -> PackedTensor:
    """Encode a dense master weight to FloatSD8 codes for the fused training
    path (hoisted outside the time scan — encode is T-invariant). The codes
    carry the exact forward values: decode(encode(w)) == quantize(w).values
    bit-identically, so the fused path's loss trajectory matches the
    fake-quant STE path's. Gradients do not flow through the (integer)
    codes; ``train_matmul`` routes dw straight to the dense master (STE)."""
    codes, bias = floatsd.encode(jax.lax.stop_gradient(w))
    return PackedTensor(codes, bias)


def hoist_train(w, *, dtype=None, backend: str | None = None):
    """Scan-loop hoist for the fused TRAINING path — the gradient-side twin
    of ``hoist_packed``. When the resolved backend is ``ref``, the codes
    would be decoded per time step in BOTH scans (forward and backward), so
    quantize-at-use once outside the scan wins: returns the dense
    STE-fake-quantized weight (bit-identical values to decode(encode(w))).
    On the pallas path returns the ``PackedTensor`` — decode-in-VMEM per
    tile is the kernel's whole point, forward and backward alike."""
    pol = backend_policy(backend)
    ref = pol == "ref" or (pol == "auto" and interpret_mode())
    if ref:
        bias = jax.lax.stop_gradient(floatsd.fit_bias(w))
        wq = floatsd.quantize_ste(w, bias)
        return wq.astype(dtype or jnp.float32)
    return pack_train(w)


def _float0(x):
    return np.zeros(np.shape(x), jax.dtypes.float0)


@functools.lru_cache(maxsize=None)
def _make_train_matmul_packed(backend: str | None, w_dtype: str):
    """custom-VJP matmul over (x, w_master, codes, bias): forward is the
    dispatched decode+matmul on the codes; backward is the registered
    (floatsd_matmul_dx, floatsd_matmul_dw) op pair — dx f32, dw emitted
    through the FP8 gradient quantizer in-kernel and routed straight-through
    to the dense master weight."""

    @jax.custom_vjp
    def f(x, w, codes, bias):
        del w  # forward runs on the codes; w is the gradient target (STE)
        return matmul(x, codes, bias, out_dtype=jnp.float32, backend=backend)

    def fwd(x, w, codes, bias):
        del w
        y = matmul(x, codes, bias, out_dtype=jnp.float32, backend=backend)
        return y, (x, codes, bias)

    def bwd(res, g):
        x, codes, bias = res
        dx = matmul_dx(g, codes, bias, backend=backend).astype(x.dtype)
        dw = matmul_dw(x, g, backend=backend).astype(jnp.dtype(w_dtype))
        return dx, dw, _float0(codes), _float0(bias)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=None)
def _make_train_matmul_dense(backend: str | None):
    """Dense-hoisted variant (the ref backend): forward is a plain f32 dot
    on the pre-quantized weight (decode hoisted out of the scan by
    ``hoist_train``); backward keeps the fused-BPTT contract — dx in f32,
    dw through the FP8 gradient quantizer (the registered op's oracle),
    flowing to the master via the hoisted STE node."""

    @jax.custom_vjp
    def f(x, wq):
        return jnp.dot(x, wq, preferred_element_type=jnp.float32).astype(
            jnp.float32
        )

    def fwd(x, wq):
        return f(x, wq), (x, wq)

    def bwd(res, g):
        x, wq = res
        m = g.size // g.shape[-1]
        k2, n2 = wq.shape
        record(
            "floatsd_matmul_dx", "ref", reason="train:hoisted-dense",
            cost=fm_cost.matmul_like_cost(
                m, n2, k2, backend="ref", a_bytes=4,
                b_bytes=jnp.dtype(wq.dtype).itemsize, bias_bytes=0,
                decode=False, o_bytes=jnp.dtype(x.dtype).itemsize,
            ),
        )
        STATS.add_touched("floatsd_matmul_dx", "ref",
                          _nbytes(g, wq) + m * k2 * jnp.dtype(x.dtype).itemsize)
        dx = jnp.dot(g, wq.T, preferred_element_type=jnp.float32).astype(x.dtype)
        dw = matmul_dw(x, g, backend=backend).astype(wq.dtype)
        return dx, dw

    f.defvjp(fwd, bwd)
    return f


def train_matmul(x, w, wq, *, backend: str | None = None):
    """Training-path matmul: x [..., K] @ quantized(w) with the fused
    backward contract. ``w`` is the dense master weight the FP8 dw flows
    to; ``wq`` is its hoisted quantization from ``hoist_train`` — a
    ``PackedTensor`` on the pallas path (decode-in-VMEM, in-kernel FP8 dw)
    or the dense STE value on ref (plain dots, oracle FP8 dw; dw reaches
    ``w`` through the hoisted STE node, so ``w`` itself is unused here)."""
    pol = backend_policy(backend)
    if is_packed(wq):
        return _make_train_matmul_packed(pol, jnp.dtype(w.dtype).name)(
            x, w, wq.codes, wq.bias
        )
    k2, n2 = wq.shape
    m = x.size // max(k2, 1)
    record(
        "floatsd_matmul", "ref", reason="train:hoisted-dense",
        cost=fm_cost.matmul_like_cost(
            m, k2, n2, backend="ref",
            a_bytes=jnp.dtype(x.dtype).itemsize,
            b_bytes=jnp.dtype(wq.dtype).itemsize, bias_bytes=0,
            decode=False, o_bytes=4,
        ),
    )
    STATS.add_touched("floatsd_matmul", "ref", _nbytes(x, wq) + m * n2 * 4)
    return _make_train_matmul_dense(pol)(x, wq)




@functools.lru_cache(maxsize=None)
def _make_lstm_cell_train(quantized: bool, c_dtype, backend: str | None):
    @jax.custom_vjp
    def f(z, c_prev):
        return lstm_cell(z, c_prev, quantized=quantized, c_dtype=c_dtype,
                         backend=backend)

    def fwd(z, c_prev):
        # residual contract: ONLY (z, c_prev); gates are recomputed in bwd
        return f(z, c_prev), (z, c_prev)

    def bwd(res, ct):
        z, c_prev = res
        dh, dc = ct
        dz, dc_prev = lstm_cell_grad(
            z, c_prev, dh, dc, quantized=quantized, c_dtype=c_dtype,
            backend=backend,
        )
        return dz.astype(z.dtype), dc_prev

    f.defvjp(fwd, bwd)
    return f


def lstm_cell_train(z, c_prev, *, quantized: bool = True,
                    c_dtype=jnp.float16, backend: str | None = None):
    """The fused cell with the recompute-gates custom VJP — the training
    twin of ``lstm_cell``: forward values identical (same dispatched op),
    backward is the registered ``lstm_cell_grad`` op pair, saving only
    (z, c_prev) instead of autodiff's ~13 per-gate residuals."""
    pol = backend_policy(backend)
    return _make_lstm_cell_train(quantized, c_dtype, pol)(z, c_prev)


# ---------------------------------------------------------------------------
# packed weights are inference-only: gradients must fail loudly
# ---------------------------------------------------------------------------

_PACKED_GRAD_MSG = (
    "packed FloatSD8 weights are inference-only: jax.grad reached a "
    "PackedTensor weight site. The uint8 codes have no VJP — train on dense "
    "master weights (Policy.weight_quant='floatsd8' fake-quant, or the "
    "fused train_matmul path) and pack with WeightStore.pack for serving."
)


@jax.custom_vjp
def inference_only(y):
    """Identity whose backward raises: marks values computed from packed
    (FloatSD8-coded) weights, where a silent zero/missing gradient would
    otherwise be the failure mode."""
    return y


def _io_fwd(y):
    return y, None


def _io_bwd(_, g):
    raise TypeError(_PACKED_GRAD_MSG)


inference_only.defvjp(_io_fwd, _io_bwd)


# ---------------------------------------------------------------------------
# packed-weight entry points (the nn/serving hot paths)
# ---------------------------------------------------------------------------


def packed_einsum(eq: str, x, packed, *, out_dtype=jnp.float32,
                  cast_dtype=None, backend: str | None = None):
    """The weight-site einsums over a PackedTensor / PackedTensor4,
    backend-resolved.

    Supports the two-operand contractions used at every weight site:
    ``...d,df->...f`` / ``bd,dk->bk`` (contract w's first axis) and
    ``...d,vd->...v`` (contract w's second axis — tied logits head). The
    ref path decodes and einsums (bit-identical to the old unpack-then-
    einsum serving step); the pallas path feeds the codes to the fused
    decode+matmul kernel, transposing the (1-byte) codes when w is stored
    [free, contract]. FloatSD4 codes are nibble-packed along axis 0, so
    the FloatSD4 kernel takes that layout as stored (``transpose_w``).
    """
    ins, out = eq.replace(" ", "").split("->")
    xl, wl = ins.split(",")
    cl = xl[-1]  # contraction label: x's last axis
    if len(wl) != 2 or cl not in wl:
        raise NotImplementedError(f"packed_einsum does not support {eq!r}")
    transpose = wl[1] == cl  # w stored [free, contract], e.g. "vd"
    wf = wl[0] if transpose else wl[1]
    if out != xl[:-1] + wf:
        raise NotImplementedError(f"packed_einsum does not support {eq!r}")
    if is_packed4(packed):
        return _packed4_einsum(
            eq, x, packed, transpose, out_dtype=out_dtype,
            cast_dtype=cast_dtype, backend=backend,
        )
    dec_backend = backend_policy(backend)
    if dec_backend == "ref" or (dec_backend == "auto" and interpret_mode()):
        c = x.shape[-1]
        n_free = packed.codes.shape[0 if transpose else 1]
        record(
            "floatsd_matmul", "ref",
            reason=f"policy:{dec_backend} (packed einsum)",
            cost=fm_cost.matmul_fwd_cost(
                x.size // max(c, 1), c, n_free, backend="ref",
                x_bytes=jnp.dtype(x.dtype).itemsize,
                out_bytes=jnp.dtype(out_dtype).itemsize,
            ),
        )
        w = floatsd.decode(packed.codes, packed.bias, dtype=cast_dtype or jnp.float32)
        y = jnp.einsum(
            eq, x, w, preferred_element_type=jnp.float32
        ).astype(out_dtype)
        STATS.add_touched("floatsd_matmul", "ref",
                          _nbytes(x, packed.codes, packed.bias, y))
        return inference_only(y)
    codes = packed.codes.T if transpose else packed.codes
    # a non-f32 compute policy (e.g. floatsd8_tpu's bf16) keeps its issue
    # dtype on the kernel path too, matching the ref branch's decode cast
    cd = None if cast_dtype in (None, jnp.float32) else cast_dtype
    return inference_only(matmul(
        x, codes, packed.bias, out_dtype=out_dtype, compute_dtype=cd,
        backend=backend,
    ))


def _packed4_einsum(eq: str, x, packed: PackedTensor4, transpose: bool, *,
                    out_dtype, cast_dtype, backend):
    """FloatSD4 arm of :func:`packed_einsum` (eq already validated)."""
    dec_backend = backend_policy(backend)
    if dec_backend == "ref" or (dec_backend == "auto" and interpret_mode()):
        c = x.shape[-1]
        n_free = packed.shape[0 if transpose else 1]
        record(
            "floatsd4_matmul", "ref",
            reason=f"policy:{dec_backend} (packed4 einsum)",
            cost=fm4_cost.matmul4_fwd_cost(
                x.size // max(c, 1), c, n_free, backend="ref",
                x_bytes=jnp.dtype(x.dtype).itemsize,
                out_bytes=jnp.dtype(out_dtype).itemsize,
                wt_nbytes=_nbytes(packed.codes, packed.exps),
            ),
        )
        w = floatsd4.decode_packed(
            packed.codes, packed.exps, packed.k,
            dtype=cast_dtype or jnp.float32,
        )
        y = jnp.einsum(
            eq, x, w, preferred_element_type=jnp.float32
        ).astype(out_dtype)
        STATS.add_touched("floatsd4_matmul", "ref",
                          _nbytes(x, packed.codes, packed.exps, y))
        return inference_only(y)
    cd = None if cast_dtype in (None, jnp.float32) else cast_dtype
    return inference_only(matmul4(
        x, packed, out_dtype=out_dtype, compute_dtype=cd,
        transpose=transpose, backend=backend,
    ))


def hoist_packed(w, *, m: int | None = None, dtype=None,
                 backend: str | None = None):
    """Loop-hoist hint for packed weights used inside a time scan.

    When the per-call resolution will execute the matmuls on the ``ref``
    backend, decoding the codes once *outside* the scan beats decode-at-use
    every step; returns the dense decode then. On the pallas path the codes
    stay packed — decode-in-VMEM per tile is the kernel's whole point (2x
    less HBM weight traffic per step). Non-packed inputs pass through.

    ``m`` is the batch rows the scan-body matmuls will see; with it the
    prediction runs the SAME resolution as ``matmul``, so a call site that
    resolves to ref can never be left packed and pay a full decode per
    time step.
    """
    if not (is_packed(w) or is_packed4(w)):
        return w
    op = "floatsd4_matmul" if is_packed4(w) else "floatsd_matmul"
    if m is not None:
        k, n = w.shape if is_packed4(w) else w.codes.shape
        native, waste, _ = _matmul_geometry(m, k, n)
        d = _decide(op, native, waste, backend)
    else:  # coarse: platform/policy only
        pol = backend_policy(backend)
        ref = pol == "ref" or (pol == "auto" and interpret_mode())
        d = Decision(op, "ref" if ref else "pallas", False, False, "")
    if d.backend == "ref":
        # the decoded dense weight still came from inference-only codes: a
        # gradient reaching it must fail loudly, not silently vanish
        if is_packed4(w):
            return inference_only(unpack4(w, dtype=dtype or jnp.float32))
        return inference_only(
            floatsd.decode(w.codes, w.bias, dtype=dtype or jnp.float32)
        )
    return w


# ---------------------------------------------------------------------------
# op registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One dispatched op: its oracle, its Pallas kernel, the resolved
    public entry point (what the hot paths call), and its declarative
    cost model (the CostSpec contract — see kernels/README.md)."""

    name: str
    ref: Callable
    pallas: Callable
    dispatch: Callable
    cost: costmodel.CostSpec | None = None


REGISTRY: dict[str, OpSpec] = {}


def register(name: str, ref: Callable, pallas: Callable, dispatch: Callable,
             cost: costmodel.CostSpec | None = None) -> None:
    REGISTRY[name] = OpSpec(name, ref, pallas, dispatch, cost)


register(
    "floatsd_matmul", floatsd_matmul_ref, floatsd_matmul_pallas, matmul,
    cost=costmodel.CostSpec(
        "floatsd_matmul", fm_cost.matmul_fwd_cost,
        "decode-in-VMEM GEMM: codes 1 byte/weight; pallas refetches x per "
        "N-block and codes per M-block",
    ),
)
register(
    "floatsd4_matmul", floatsd4_matmul_ref, floatsd4_matmul_pallas, matmul4,
    cost=costmodel.CostSpec(
        "floatsd4_matmul", fm4_cost.matmul4_fwd_cost,
        "sub-byte decode-in-VMEM GEMM: 2 codes/byte along K + int8 group "
        "exponents (~0.53 byte/weight); pallas refetches x per N-block "
        "and the packed stream per M-block",
    ),
)
register(
    "lstm_cell", lstm_cell_ref, lstm_cell_pallas, lstm_cell,
    cost=costmodel.CostSpec(
        "lstm_cell", lc_cost.lstm_cell_cost,
        "elementwise single-pass; 3 MACs/elem (Table-7 Eq.5-6 lanes), "
        "c state in c_dtype (f16 blob)",
    ),
)
register(
    "floatsd_quantize",
    lambda x, bias=None: floatsd.encode(x, bias),
    quantize_pallas,
    quantize,
    cost=costmodel.CostSpec(
        "floatsd_quantize", fq_cost.quantize_cost,
        "elementwise encode f32 -> 1-byte codes, single pass",
    ),
)
register(
    "qsigmoid", qsigmoid_ref, qsigmoid_pallas, qsigmoid,
    cost=costmodel.CostSpec(
        "qsigmoid", qs_cost.qsigmoid_cost,
        "elementwise two-region LUT sigmoid, single pass",
    ),
)
# backward op pairs: the training path's VJPs resolve through these, so the
# whole BPTT step — not just inference — runs on registered kernels
register(
    "floatsd_matmul_dx", matmul_dx_ref, matmul_dx_pallas, matmul_dx,
    cost=costmodel.CostSpec(
        "floatsd_matmul_dx", fm_cost.matmul_dx_cost,
        "forward kernel on transposed codes; f32 compute",
    ),
)
register(
    "floatsd_matmul_dw", matmul_dw_ref, matmul_dw_pallas, matmul_dw,
    cost=costmodel.CostSpec(
        "floatsd_matmul_dw", fm_cost.matmul_dw_cost,
        "dense f32 GEMM, M innermost, FP8-e5m2 quantizer at the flush",
    ),
)
register(
    "lstm_cell_grad", lstm_cell_bwd_ref, lstm_cell_bwd_pallas, lstm_cell_grad,
    cost=costmodel.CostSpec(
        "lstm_cell_grad", lc_cost.lstm_cell_grad_cost,
        "recompute-gates backward; residuals are (z, c_prev) only",
    ),
)
# sequence mixers from the model zoo: dispatched + costed like the LSTM
# ops, but with oracle fallback (no padding path) on indivisible shapes
register(
    "rwkv_wkv", wkv_ref, wkv_pallas, rwkv_wkv,
    cost=costmodel.CostSpec(
        "rwkv_wkv", wkv_cost.wkv_cost,
        "chunked scan, [K, V] f32 state resident in VMEM; single-pass HBM",
    ),
)
register(
    "flash_attention", flash_attention_ref, flash_attention_pallas,
    flash_attention,
    cost=costmodel.CostSpec(
        "flash_attention", fa_cost.flash_attention_cost,
        "online softmax; KV refetched per Q-block; masked-out pairs "
        "charged to pad_waste_flops (kernel visits every tile)",
    ),
)
