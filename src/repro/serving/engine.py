"""ServeEngine: continuous-batching inference over packed FloatSD8 weights.

Lifecycle per request: queue -> (admission to a free lane) -> chunked
prefill -> decode -> retire. All B lanes advance in ONE jitted step per
iteration:

  * each active lane contributes a per-lane length k: a prefilling lane
    consumes ``min(remaining_prompt, chunk)`` tokens, a decoding lane
    exactly 1;
  * the token block is [B, S] with S in {1, chunk} (bucketed so jit
    compiles at most two shapes); positions >= k are padding and the
    lengths-masked LSTM scan freezes that lane's state there;
  * lanes freshly re-armed get their state slab zeroed by a masked reset
    fused into the same step;
  * the step consuming a lane's final prompt token doubles as its first
    generation step (the last valid logit predicts token 1) — a prompt of
    length L costs ceil(L/chunk) steps instead of the L steps the old
    one-token-per-step force-feed loop paid.

Weights are served from the packed uint8 store by default (decode-at-use
inside the jitted step); ``packed=False`` keeps the seed's dense
fake-quant-at-use path for A/B comparison.
"""
from __future__ import annotations

import inspect
import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..faults import (
    ENGINE_STEP_RAISE,
    ENGINE_STEP_SLOW,
    FAULTS,
    InjectedFault,
    NONFINITE_LOGITS,
    REPLICA_CRASH,
    ReplicaCrash,
)
from ..obs import profiler
from ..obs.trace import TRACER
from .metrics import ServeMetrics
from .scheduler import Request, Scheduler
from .state_pool import StatePool, masked_reset
from .weight_store import WEIGHT_FORMATS, WeightStore, unpack_tree

__all__ = ["ServeEngine", "Lane"]

# the serving stack's spans also land on the JAX profiler's clock
profiler.install()


class Lane:
    """Host-side bookkeeping for one decode lane."""

    __slots__ = ("req", "pos", "next_token")

    def __init__(self, req: Request):
        self.req = req
        self.pos = 0  # prompt tokens consumed so far
        self.next_token = 0  # token to feed when decoding

    @property
    def prefilling(self) -> bool:
        return self.pos < self.req.prompt_len


class ServeEngine:
    """Continuous-batching engine: one instance owns `lanes` decode lanes,
    a StatePool slab, and (packed) the uint8 WeightStore view of params.

    Admission → inject → prefill lifecycle (per request, the contract the
    frontend relies on): ``_arm_free_lanes`` binds the next scheduled
    request to a free lane; if a prefix cache is attached, admission does
    a trie ``lookup`` on the prompt first — on a hit the cached FP8 state
    is dequantized and **injected** into the lane's slab slice
    (``StatePool.inject``, replacing the masked reset) and prefill starts
    at the match point; a *full* hit replays the stored ``next_token`` at
    admission, so the request reaches first-token with zero device steps.
    Prefill then consumes ``min(remaining, chunk)`` prompt tokens per
    batched step (inserting block-boundary cache snapshots via
    ``wants_snapshot``), decode emits one token per step, and retire
    frees the lane and (``wants``) stores the final state keyed by
    prompt + generated[:-1].

    Cancellation (``cancel(rid)``) removes a request wherever it lives:
    scheduler removal while queued, masked lane release while bound (the
    free is folded into the step's existing reset mask — zero extra device
    steps). Preemption (``preempt=True``) lets ``step_once`` displace the
    longest-remaining decoding lane when the queue head owes much less
    work: the lane's (h, c) is snapshotted to host FP8 (the prefix cache's
    format and error bound), the victim requeued, and the snapshot
    restored on re-admission.

    Concurrency contract: the engine is **not thread-safe** — ``submit``
    / ``enqueue`` / ``step_once`` / ``run`` / ``cancel`` must be
    serialized by the caller (the Router calls them from its pump;
    AsyncRouter serializes pumps under its lock). ``step_once`` blocks the
    calling thread on one jitted device step; everything else is host-side
    bookkeeping. Load introspection (``free_lanes`` / ``load`` /
    ``has_work``) reads plain host state and is safe to call between
    steps.
    """

    def __init__(
        self,
        model,
        params,
        policy,
        lanes: int = 8,
        chunk: int = 8,
        admission: str = "fifo",
        packed: bool = True,
        cache_len: int | None = None,
        greedy: bool = True,
        prefix_cache=None,
        preempt: bool = False,
        preempt_margin: int = 8,
        preempt_max: int = 2,
        admit_pace: int | None = None,
        weight_format: str = "floatsd8",
    ):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if admit_pace is not None and admit_pace < 1:
            raise ValueError("admit_pace must be >= 1 (or None to disable)")
        if weight_format not in WEIGHT_FORMATS:
            raise ValueError(
                f"weight_format must be one of {WEIGHT_FORMATS}, "
                f"got {weight_format!r}"
            )
        del greedy  # argmax decoding only, for now
        self.model = model
        self.policy = policy
        self.lanes_n = lanes
        self.scheduler = Scheduler(admission)
        self.metrics = ServeMetrics(lanes)

        # Packed path: weights become uint8 codes; re-running the fake-quant
        # weight pass on already-decoded grid values would be redundant work
        # (decode(encode(w)) == quantize(w).values, see weight_store), so
        # the serving policy drops weight_quant.
        if packed and policy.weight_quant != "floatsd8":
            raise ValueError(
                f"packed=True serves FloatSD8-quantized weights, but policy "
                f"{policy.name!r} has weight_quant={policy.weight_quant!r} — "
                f"serving packed would silently change the model's outputs; "
                f"pass packed=False (CLI: --dense) for unquantized policies"
            )
        # weight_format="floatsd4" re-quantizes the FloatSD8 master to the
        # sub-byte format (2 codes/byte + group exponents): not
        # output-identical to the trained model — an explicit accuracy/
        # footprint trade, gated by the accuracy test in test_serving.py.
        self.weight_format = weight_format
        if packed:
            self.store: Optional[WeightStore] = WeightStore.pack(
                params, fmt=weight_format
            )
            self.serve_params = self.store.tree
            self.serve_policy = policy.replace(weight_quant="none")
        else:
            self.store = None
            self.serve_params = params
            self.serve_policy = policy

        # Models without lengths support (transformer KV decode) can only
        # advance lanes in lockstep -> force one-token steps.
        self._supports_lengths = (
            "lengths" in inspect.signature(model.decode_step).parameters
        )
        self.chunk = chunk if self._supports_lengths else 1

        self.pool = StatePool.for_model(model, lanes, policy, cache_len=cache_len)
        # Continuous batching (re-arming a used lane) requires every cache
        # leaf to be lane-major so masked_reset can actually clear it; a
        # cache with shared leaves (scalar positions, layer-major stacks)
        # would silently leak the previous request's state into the next.
        # Shape alone can't prove lane-majorness (a layer-major stack whose
        # group count happens to equal `lanes` would false-positive), so
        # require lengths support too: a model that freezes state per-lane
        # necessarily keeps its recurrent state lane-major.
        self._rearmable = self._supports_lengths and all(
            hasattr(l, "ndim") and l.ndim >= 1 and l.shape[0] == lanes
            for l in jax.tree_util.tree_leaves(self.pool.caches)
        )
        # Optional frontend prefix cache (duck-typed: lookup / insert /
        # wants / wants_snapshot — see serving/frontend/prefix_cache.py).
        # Injection overwrites a lane's whole state slice, which only makes
        # sense when every cache leaf is lane-major.
        if prefix_cache is not None and not self._rearmable:
            raise ValueError(
                "prefix caching requires a per-lane resettable (lane-major) "
                "state pool — an LSTM-family model with lengths support"
            )
        self.prefix_cache = prefix_cache
        # Lane preemption: snapshot a decoding lane's (h, c) to host FP8
        # (StatePool.snapshot_fp8 — the prefix cache's storage format and
        # error bound), requeue the request, hand the lane to shorter
        # queued work, and restore the snapshot when the request is
        # re-admitted. Same lane-major requirement as injection.
        if preempt and not self._rearmable:
            raise ValueError(
                "preempt=True requires a per-lane resettable (lane-major) "
                "state pool — an LSTM-family model with lengths support"
            )
        self.preempt = preempt
        self.preempt_margin = preempt_margin
        self.preempt_max = preempt_max
        self.admit_pace = admit_pace
        # rid -> (fp8 snapshot, dtypes, next_token, pos) for requests
        # preempted off a lane and waiting in the scheduler to resume
        self._preempted: dict[int, tuple] = {}
        self._lanes: list[Lane | None] = [None] * lanes
        self._lane_used = [False] * lanes
        self._reset = np.zeros((lanes,), np.int32)
        self._rid = 0
        # Replica identity (stamped by the Router) keys fault rules to a
        # specific engine; `_crashed` is the sticky replica_crash state —
        # once set, every step raises until the process restarts.
        self.replica: int | None = None
        self._crashed = False

        model_ = model
        pol = self.serve_policy
        supports_lengths = self._supports_lengths
        # Packed-aware models (supports_packed) consume PackedTensor leaves
        # at their weight sites through the kernel dispatch layer — the
        # engine hands them the 1-byte codes untouched and the dispatch
        # resolver picks decode-hoist (ref) or the fused decode-in-VMEM
        # Pallas matmul per site. Models without that flag get the legacy
        # whole-tree decode so arbitrary decode_steps keep working.
        unpack_in_step = packed and not getattr(model, "supports_packed", False)

        def _step(params, tokens, lengths, caches, reset_mask):
            caches = masked_reset(caches, reset_mask)
            if unpack_in_step:
                params = unpack_tree(params)
            if supports_lengths:
                logits, caches = model_.decode_step(
                    params, tokens, caches, pol, lengths=lengths
                )
                idx = jnp.clip(lengths - 1, 0, tokens.shape[1] - 1)
                last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0, :]
            else:
                logits, caches = model_.decode_step(params, tokens, caches, pol)
                last = logits[:, -1, :]
            nxt = jnp.argmax(last, -1).astype(jnp.int32)
            # Nonfinite guard: jnp.argmax over an all-NaN row silently
            # returns index 0 — a poisoned lane would emit token 0 forever
            # and its NaN state would bleed into the prefix cache. Flag
            # per-lane logit health here, where the logits still exist.
            ok = jnp.all(jnp.isfinite(last), axis=-1)
            return nxt, ok, caches

        # Donate the cache slab: the pre-step state is never read after the
        # call (pool.swap installs the result), so XLA can update the lane
        # state in place instead of keeping two copies live per step.
        self._step = jax.jit(_step, donate_argnums=(3,))

    # -- request intake --------------------------------------------------
    def submit(
        self,
        prompt,
        max_new: int = 32,
        tenant: str = "default",
        deadline: float | None = None,
    ) -> Request:
        req = Request(
            rid=self._rid,
            prompt=np.asarray(prompt),
            max_new=max_new,
            tenant=tenant,
            deadline=deadline,
        )
        self._rid += 1
        return self.enqueue(req)

    def enqueue(self, req: Request) -> Request:
        """Queue an externally constructed Request (the router path — the
        frontend owns rids/tenants/deadlines and load-balances across
        engine replicas)."""
        return self.scheduler.submit(req)

    def submit_all(self, prompts: Iterable, max_new: int = 32) -> list[Request]:
        return [self.submit(p, max_new) for p in prompts]

    # -- router-facing load introspection --------------------------------
    @property
    def free_lanes(self) -> int:
        return sum(l is None for l in self._lanes)

    @property
    def active_lanes(self) -> int:
        return self.lanes_n - self.free_lanes

    def has_work(self) -> bool:
        return self.active_lanes > 0 or bool(self.scheduler)

    @property
    def load(self) -> float:
        """Active lanes + backlog, per lane — the router's least-loaded
        balancing key."""
        return (self.active_lanes + len(self.scheduler)) / self.lanes_n

    # -- lane lifecycle --------------------------------------------------
    def _arm_free_lanes(self) -> int:
        """Bind queued requests to free lanes; returns the lanes armed."""
        armed = 0
        for i in range(self.lanes_n):
            # `while`, not `if`: a full prefix-cache hit with max_new == 1
            # retires at admission time without consuming a device step, so
            # the same slot can drain several queued requests in a row.
            while self._lanes[i] is None and self.scheduler:
                if self.admit_pace is not None and armed >= self.admit_pace:
                    # pacing: spread admissions over steps so a warm burst
                    # (cheap full hits arriving faster than lanes drain)
                    # cannot monopolize every freed lane in one round
                    return armed
                if self._lane_used[i] and not self._rearmable:
                    raise RuntimeError(
                        "cannot re-arm a used lane: this model's cache has "
                        "non-lane-major leaves that masked_reset cannot "
                        "clear per-lane; serve at most `lanes` requests per "
                        "engine (or use an LSTM-family model)"
                    )
                req = self.scheduler.pop()
                armed += 1
                # stamped per admission, not once per call: a slow cache
                # lookup for lane j would otherwise be billed to the queue
                # phase of every lane armed after it
                now = time.monotonic()
                if req.t_admit is None:
                    req.t_admit = now  # queue wait ends; prefill begins
                lane = Lane(req)
                self._lanes[i] = lane
                self._lane_used[i] = True
                stash = self._preempted.pop(req.rid, None)
                if stash is not None:
                    # resuming a preempted decode: restore the FP8 snapshot
                    # instead of reset-and-prefill; the request keeps its
                    # original t_admit/t_first so the preempted wait shows
                    # up in the decode phase it actually delayed
                    snap, dtypes, next_token, pos = stash
                    self.pool.inject_fp8(i, snap, dtypes)
                    self._reset[i] = 0
                    lane.pos = pos
                    lane.next_token = next_token
                    self.metrics.resumes += 1
                    if TRACER.enabled:
                        TRACER.instant(
                            "engine.resume", cat="engine", rid=req.rid,
                            lane=i, decoded=len(req.out),
                        )
                    break
                hit = None
                if self.prefix_cache is not None:
                    with TRACER.span("cache.lookup", cat="cache", rid=req.rid):
                        hit = self.prefix_cache.lookup(req.prompt)
                    self.metrics.on_cache_lookup(
                        hit=hit is not None,
                        full=hit is not None and hit.full,
                        saved=hit.match_len if hit is not None else 0,
                    )
                    if hit is not None:
                        req.cache_hit = True
                        req.cache_saved_tokens = hit.match_len
                        # whole prefill steps the injection replaced; the
                        # residual partial chunk merges into the suffix step
                        req.cache_saved_steps = hit.match_len // self.chunk
                if TRACER.enabled:
                    TRACER.instant(
                        "engine.admit", cat="engine", rid=req.rid, lane=i,
                        prompt_len=req.prompt_len,
                        cache=(
                            "full" if (hit is not None and hit.full)
                            else "hit" if hit is not None else "miss"
                        ),
                        saved_tokens=req.cache_saved_tokens,
                    )
                if hit is None:
                    self._reset[i] = 1  # zeroed inside the next jitted step
                    break
                # Inject the cached prefix state instead of resetting: the
                # snapshot overwrites every leaf of the lane slice, and a
                # masked reset afterwards would zero it again.
                self.pool.inject(i, hit.states)
                self._reset[i] = 0
                lane.pos = hit.match_len
                if hit.full:
                    # Whole prompt cached: the stored greedy continuation IS
                    # the first generated token — prefill is skipped
                    # entirely and TTFT costs zero device steps.
                    self._emit(lane, hit.next_token, now, first=True)
                    if req.done:
                        self._retire(i)
                        continue
                break
        return armed

    def _retire(self, i: int, status: str = "done", reason: str | None = None) -> None:
        """Unbind lane ``i`` terminally. ``status="done"`` is the normal
        completion path; ``status="cancelled"`` is the same bookkeeping
        with cancel-side accounting — one retire path keeps the
        metrics/tracer/prefix-cache invariants identical either way."""
        lane = self._lanes[i]
        req = lane.req
        now = time.monotonic()
        req.t_done = now  # decode phase ends; req.phases() is now total
        req.status = status
        if status != "done":
            req.cancel_reason = reason
        if (
            self.prefix_cache is not None
            and status != "numeric_error"  # never cache a poisoned state
            and len(req.out) >= 2
            and not lane.prefilling
        ):
            # The lane's final state summarizes prompt + out[:-1] (the last
            # generated token was emitted but never fed back); out[-1] is
            # its exact greedy continuation. Serves resubmissions that
            # extend this conversation — and salvages the prefill a
            # cancelled request already paid for.
            key = np.concatenate(
                [req.prompt, np.asarray(req.out[:-1], np.int32)]
            )
            if self.prefix_cache.wants(key, len(key)):
                self._cache_insert("retire", i, key, next_token=req.out[-1])
        if status == "done":
            self.metrics.on_retire(req, now)
        elif status == "numeric_error":
            self.metrics.on_numeric_error(req)
            self._reset[i] = 1  # wipe the poisoned state via the mask
        else:
            self.metrics.on_cancel(req, reason or "cancelled")
            # fold the lane release into the existing reset mask: the next
            # jitted step zeroes the dead state as part of work it was
            # doing anyway — cancellation costs zero extra device steps
            self._reset[i] = 1
        if TRACER.enabled:
            if status == "done":
                TRACER.instant(
                    "engine.retire", cat="engine", rid=req.rid, lane=i,
                    new_tokens=len(req.out),
                )
            elif status == "numeric_error":
                TRACER.instant(
                    "engine.numeric_error", cat="engine", rid=req.rid,
                    lane=i, new_tokens=len(req.out),
                    reason=reason or "nonfinite_logits",
                )
            else:
                TRACER.instant(
                    "engine.cancel", cat="engine", rid=req.rid, lane=i,
                    new_tokens=len(req.out), reason=reason or "cancelled",
                )
        self._lanes[i] = None

    def _cache_insert(self, kind: str, i: int, key, next_token=None) -> None:
        """Store lane ``i``'s state under ``key`` in the prefix cache. The
        ``cache.insert`` span covers the lane's extract (device to host)
        as well as the cache's own work; ``kind`` names the site:
        ``block`` (mid-prefill snapshot), ``prompt`` (prompt end) or
        ``retire``."""
        cache = self.prefix_cache
        if not TRACER.enabled:
            cache.insert(key, self.pool.extract(i), next_token=next_token)
            return
        with TRACER.span("cache.insert", cat="cache", kind=kind,
                         rid=self._lanes[i].req.rid) as sp:
            sp.note(bytes=cache.insert(
                key, self.pool.extract(i), next_token=next_token
            ))

    # -- cancellation ----------------------------------------------------
    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Terminally remove a request wherever it currently lives: still
        queued → scheduler removal; bound to a lane → masked lane release
        (the free rides the reset mask of the next step, costing zero
        extra device work). Idempotent: unknown / already-finished rids
        return False. Host-side only — safe between steps under the same
        serialization contract as ``step_once``."""
        req = self.scheduler.remove(rid)
        if req is not None:
            self._preempted.pop(rid, None)  # preempted-and-requeued state
            req.status = "cancelled"
            req.cancel_reason = reason
            req.t_done = time.monotonic()
            self.metrics.on_cancel(req, reason)
            if TRACER.enabled:
                TRACER.instant(
                    "engine.cancel", cat="engine", rid=rid,
                    new_tokens=len(req.out), reason=reason,
                )
            return True
        for i, lane in enumerate(self._lanes):
            if lane is not None and lane.req.rid == rid:
                self._retire(i, status="cancelled", reason=reason)
                return True
        return False

    # -- preemption ------------------------------------------------------
    def _maybe_preempt(self) -> None:
        """If every lane is busy and the queue head owes far less work
        than some decoding lane, snapshot that lane to host FP8 and hand
        it over (SJF with bounded regret: the victim resumes later from
        the snapshot). Only decoding lanes with at least one emitted token
        are candidates — their TTFT is already banked, so preemption can
        only improve the first-token tail, never worsen it."""
        if not self.preempt or self.free_lanes > 0 or not self.scheduler:
            return
        cand = self.scheduler.peek()
        if cand is None:
            return
        if cand.work_hint is None and self.prefix_cache is not None:
            # the router stamps work_hint at submission; engine-direct
            # submissions get the same probe here (non-mutating)
            cand.work_hint = self.prefix_cache.match_len(cand.prompt)
        cand_work = cand.remaining_work()
        victim, victim_rem = None, -1
        for i, lane in enumerate(self._lanes):
            if lane is None or lane.prefilling or not lane.req.out:
                continue
            if lane.req.preempt_count >= self.preempt_max:
                continue  # bounded thrash: a request is displaced at most preempt_max times
            rem = lane.req.max_new - len(lane.req.out)
            if rem > victim_rem:
                victim, victim_rem = i, rem
        if victim is None or victim_rem < cand_work + self.preempt_margin:
            return
        self._preempt_lane(victim)

    def _preempt_lane(self, i: int) -> None:
        lane = self._lanes[i]
        req = lane.req
        snap, dtypes = self.pool.snapshot_fp8(i)
        self._preempted[req.rid] = (snap, dtypes, lane.next_token, lane.pos)
        req.preempt_count += 1
        self.metrics.preemptions += 1
        if TRACER.enabled:
            TRACER.instant(
                "engine.preempt", cat="engine", rid=req.rid, lane=i,
                decoded=len(req.out),
                remaining=req.max_new - len(req.out),
            )
        self._lanes[i] = None
        self._reset[i] = 1  # freed state is wiped by the next step's mask
        self.scheduler.submit(req)  # t_submit preserved; resumes via stash

    # -- replica failure -------------------------------------------------
    def _check_faults(self) -> None:
        """Injection points at the top of ``step_once`` — the boundary the
        Router's per-replica health watches. Only reached when a plan is
        armed (``FAULTS.enabled`` gates the call)."""
        if FAULTS.fire(REPLICA_CRASH, key=self.replica) is not None:
            self._crashed = True  # sticky: dead until process restart
        if self._crashed:
            raise ReplicaCrash(f"replica {self.replica} crashed")
        f = FAULTS.fire(ENGINE_STEP_SLOW, key=self.replica)
        if f is not None:
            time.sleep(float(f.get("ms", 50)) / 1000.0)
        if FAULTS.fire(ENGINE_STEP_RAISE, key=self.replica) is not None:
            raise InjectedFault(
                f"injected step error on replica {self.replica}"
            )

    def evacuate(self) -> list[Request]:
        """Strip every live request off this replica so the Router can
        resubmit them elsewhere (ejection path). Requests are rewound to
        their pre-admission state — generated tokens cleared (greedy
        decode is deterministic, so a healthy replica regenerates the
        identical stream and the ticket's ``sent`` cursor deduplicates
        delivery) — but keep their original ``t_submit``/``t_first`` so
        latency accounting stays honest across the move."""
        out: list[Request] = []
        while self.scheduler:
            out.append(self.scheduler.pop())
        for i, lane in enumerate(self._lanes):
            if lane is not None:
                out.append(lane.req)
                self._lanes[i] = None
        self._preempted.clear()
        for req in out:
            req.out.clear()
            req.status = "active"
            req.preempt_count = 0
        return out

    # -- the batched step ------------------------------------------------
    def step_once(self) -> bool:
        """Advance every active lane one scheduling quantum. Returns False
        when there is nothing left to do. Raises :class:`ReplicaCrash` /
        :class:`InjectedFault` under an armed fault plan — the Router's
        health layer catches these and ejects or retries."""
        if self._crashed:
            raise ReplicaCrash(f"replica {self.replica} crashed")
        if FAULTS.enabled:
            self._check_faults()
        self._maybe_preempt()
        if TRACER.enabled and self.scheduler and None in self._lanes:
            with TRACER.span("engine.admit", cat="engine") as sp:
                sp.note(lanes=self._arm_free_lanes())
        else:
            self._arm_free_lanes()
        active = [i for i, l in enumerate(self._lanes) if l is not None]
        if not active:
            return False

        B, chunk = self.lanes_n, self.chunk
        ks = np.zeros((B,), np.int32)
        any_prefill = False
        for i in active:
            lane = self._lanes[i]
            if lane.prefilling:
                ks[i] = min(lane.req.prompt_len - lane.pos, chunk)
                if ks[i] > 1:
                    any_prefill = True
            else:
                ks[i] = 1
        # Bucket the block width to {1, chunk} so jit sees two shapes total.
        S = chunk if any_prefill else 1
        tokens = np.zeros((B, S), np.int32)
        for i in active:
            lane = self._lanes[i]
            k = int(ks[i])
            if lane.prefilling:
                tokens[i, :k] = lane.req.prompt[lane.pos : lane.pos + k]
            else:
                tokens[i, 0] = lane.next_token

        # Hand the device a buffer we will never touch again: jnp.asarray
        # can zero-copy ALIAS a numpy array on CPU, and jit dispatch is
        # async — mutating self._reset in place after the call would race
        # the computation reading it (observed: lost resets corrupting
        # re-armed lanes). A fresh zeros array per step sidesteps aliasing;
        # tokens/ks are likewise freshly allocated and never mutated.
        reset, self._reset = self._reset, np.zeros((B,), np.int32)
        # Per-lane attribution without per-lane cost: one span per batched
        # step (the engine's unit of device work) carrying the lane→rid map
        # and each lane's token count. Arg construction is guarded so the
        # disabled tracer costs one branch on this hot path.
        step_span = (
            TRACER.span(
                "engine.step", cat="engine",
                kind="prefill" if any_prefill else "decode",
                width=S, useful=int(ks.sum()),
                lanes={
                    str(i): {"rid": self._lanes[i].req.rid, "k": int(ks[i])}
                    for i in active
                },
            )
            if TRACER.enabled
            else TRACER.span("engine.step")
        )
        with step_span:
            nxt, ok, caches = self._step(
                self.serve_params,
                jnp.asarray(tokens),
                jnp.asarray(ks),
                self.pool.caches,
                jnp.asarray(reset),
            )
            # the host waits here for the device (the step was dispatched
            # asynchronously): the wait is device time, the rest host time
            with TRACER.span("engine.wait", cat="engine"):
                nxt = np.asarray(nxt)  # sync point: step outputs materialized
                ok = np.asarray(ok)
        self.pool.swap(caches)
        if FAULTS.enabled and FAULTS.fire(NONFINITE_LOGITS, key=self.replica):
            # Poison the host copy of one active lane's health flag: the
            # recovery path below is identical to a real device-side NaN
            # (tests inject actual NaN params to pin the jnp.isfinite leg).
            # np.asarray of a device array is a read-only zero-copy view,
            # so take a writable copy here (off the fault-free hot path).
            ok = ok.copy()
            ok[active[0]] = False

        self.metrics.on_step(
            width=S,
            active=len(active),
            useful=int(ks.sum()),
            any_prefill=any_prefill,
        )
        with TRACER.span("engine.post", cat="engine"):
            now = time.monotonic()
            cache = self.prefix_cache
            for i in active:
                lane = self._lanes[i]
                if not ok[i]:
                    # Nonfinite logits: never sample from NaN (the argmax
                    # result is garbage), never let the poisoned state reach
                    # the prefix cache or the next step — retire the request
                    # with a distinct status and fold the lane wipe into the
                    # reset mask, exactly like a cancel.
                    self._retire(i, status="numeric_error",
                                 reason="nonfinite_logits")
                    continue
                if lane.prefilling:
                    lane.pos += int(ks[i])
                    self.metrics.prompt_tokens += int(ks[i])
                    if not lane.prefilling:
                        # final prompt chunk consumed: this step's last valid
                        # logit is the first generated token
                        self._emit(lane, int(nxt[i]), now, first=True)
                        if cache is not None and cache.wants(
                            lane.req.prompt, lane.req.prompt_len
                        ):
                            # state after the whole prompt + its exact greedy
                            # continuation -> future identical prompts skip
                            # prefill entirely
                            self._cache_insert(
                                "prompt", i, lane.req.prompt,
                                next_token=int(nxt[i]),
                            )
                    elif cache is not None and cache.wants_snapshot(
                        lane.req.prompt, lane.pos
                    ):
                        # block-boundary snapshot mid-prefill: what makes
                        # *shared-prefix* (not just identical) prompts hit
                        self._cache_insert(
                            "block", i, lane.req.prompt[: lane.pos]
                        )
                else:
                    self._emit(lane, int(nxt[i]), now)
                if lane.req.done:
                    self._retire(i)
        return True

    def _emit(self, lane: Lane, tok: int, now: float, first: bool = False) -> None:
        if first and lane.req.t_first is None:
            lane.req.t_first = now
        lane.req.out.append(tok)
        lane.next_token = tok
        self.metrics.emitted += 1

    # -- drain -----------------------------------------------------------
    def run(self) -> ServeMetrics:
        """Serve until the queue and all lanes are drained."""
        # Fail fast instead of raising mid-run (discarding finished work):
        # a non-rearmable cache can serve at most `lanes` requests total.
        outstanding = len(self.scheduler) + sum(
            l is not None for l in self._lanes
        )
        if not self._rearmable and outstanding > self.lanes_n:
            raise ValueError(
                f"{outstanding} requests queued but this model's cache "
                f"cannot be reset per-lane (non-lane-major leaves); submit "
                f"at most lanes={self.lanes_n} requests per engine, or use "
                f"an LSTM-family model for continuous batching"
            )
        self.metrics.start()
        while self.step_once():
            pass
        self.metrics.stop()
        return self.metrics
