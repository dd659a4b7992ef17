#!/usr/bin/env python3
"""Smoke test on the chip: FloatSD8 LSTM serving and training at the
published widths of the paper's WikiText-2 LM (2 layers, emb = hidden =
1024, vocab 33278), through the normal entry points, with the Pallas
kernels compiled for the TPU.

    python3 chip_smoke.py              # one chip: serving + training
    python3 chip_smoke.py --chips 4    # four chips: data-parallel training
                                       # step against the one-chip step

Phases (one chip): device check; serving (``ServeEngine``, packed FloatSD8,
chunked prefill + decode); training (``make_train_step``). After each
phase every dispatched op must have resolved to compiled Pallas: an op
resolved to the jnp oracle or run interpreted fails the run. Each phase is
compared with the ``ref`` backend (the jnp oracles, f32 matmuls at
``highest`` precision) on the same chip, within the bounds below.
``--chips 4`` runs only the data-parallel training phase.

Prints per-phase wall and compile times ("one run, not a benchmark"); the
last line is ``{"ok": true, "device": {...}}``. Any failed check exits
non-zero without that line, as does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.policy import get_policy  # noqa: E402
from repro.data.pipeline import ShardedPipeline, to_global  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.train import make_mesh_for_host  # noqa: E402
from repro.models.lstm_models import WikiText2LM  # noqa: E402
from repro.models.task_zoo import make_task  # noqa: E402
from repro.optim.train_state import init_state, make_train_step  # noqa: E402
from repro.serving import ServeEngine  # noqa: E402

POLICY = "floatsd8_table6"
SERVE_OPS = ("floatsd_matmul", "lstm_cell")
TRAIN_OPS = SERVE_OPS + ("floatsd_matmul_dx", "floatsd_matmul_dw",
                         "lstm_cell_grad")
# Serving logits, Pallas vs ref: max |difference| over max |ref logit|.
# The repo's ref-vs-Pallas bound (EXPERIMENTS.md, fused BPTT: <= 1e-3
# relative); greedy tokens are compared only where the ref top-2 margin
# exceeds this bound in logit units.
LOGIT_TOL = 1e-3
# Training: step-1 loss, Pallas vs ref, relative (same source). At the
# untrained init the loss is ~ln(vocab) whatever the forward computes, so
# the backward is held separately: the applied gradient (SGD's f32
# momentum buffer after step 1, the quantity both runs update the f16
# masters with), per tensor, relative L2 <= UPDATE_TOL. Both runs round
# every gradient to FP8 (e5m2, steps of 2^-2 relative); they differ by
# f32 summation order, which moves an element across a rounding boundary
# only rarely, and by what such a move does downstream in BPTT.
# UPDATE_TOL sits between the readings (PERF.md) and the control, the ref
# step with FP8 gradient quantization off, which must miss it.
LOSS_TOL = 1e-3
UPDATE_TOL = 1e-2
# Four chips vs one on the same global batch, both through the same
# shard_map step: each device differentiates its share of the global mean,
# the f32 gradients are summed over the devices and snapped to FP8 once.
# The step-1 loss is the same forward split four ways: relative gap <=
# DP_LOSS_TOL. The applied gradient differs only by f32 summation order
# (~1e-6 relative), which flips the FP8 rounding of at most ~2e-6 / 2^-2
# ~ 1e-5 of the elements by one e5m2 step (<= 2^-2 of the value):
# <= 2^-2 * sqrt(1e-5) ~ 8e-4 relative L2 per tensor, hence DP_GRAD_TOL.
# Control: the one-chip step without a mesh, which rounds the tied head's
# dW to bf16 and the other non-gate gradients to the fp16 master dtype
# before their FP8 snap, must miss it.
DP_LOSS_TOL = 1e-5
DP_GRAD_TOL = 1e-3


class Compiles:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.seconds, self.hits, self.misses)

    def since(self, mark):
        s, h, m = mark
        return (f"compile {self.seconds - s:.1f}s, persistent cache "
                f"{self.hits - h} hits / {self.misses - m} misses")


def fail(msg: str):
    print(f"FAIL: {msg}", flush=True)
    raise SystemExit(1)


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


def check_dispatch(phase: str, required: tuple[str, ...]):
    """Every op resolved since the last STATS reset must be compiled
    Pallas, and every op in ``required`` must have been resolved."""
    counts = dispatch.STATS.snapshot()
    interpreted = dict(dispatch.STATS.interpreted)
    shown = ", ".join(f"{o}/{b}={n}" for (o, b), n in sorted(counts.items()))
    print(f"[{phase}] dispatch: {shown}; interpreted: {interpreted or 0}",
          flush=True)
    on_ref = sorted(o for (o, b), n in counts.items() if b != "pallas" and n)
    check(not on_ref, f"{phase}: ops resolved to the jnp oracle: {on_ref} "
          f"({[dispatch.STATS.last[o].reason for o in on_ref]})")
    check(not interpreted, f"{phase}: kernels ran interpreted: {interpreted}")
    missing = [o for o in required if not counts.get((o, "pallas"))]
    check(not missing, f"{phase}: main-path ops never dispatched: {missing}")


def ref_backend():
    """The oracle the compiled kernels are held to: the jnp reference ops,
    f32 matmuls at full precision."""
    import contextlib

    stack = contextlib.ExitStack()
    stack.enter_context(dispatch.use_backend("ref"))
    stack.enter_context(jax.default_matmul_precision("highest"))
    return stack


def serve_phase(model, policy, *, seed: int, lanes: int = 8, chunk: int = 8,
                max_new: int = 16, compiles: Compiles):
    """Serve ``lanes`` requests through the engine (packed FloatSD8, chunked
    prefill + decode) and hold its prefill logits and first greedy tokens
    to the ref backend."""
    t0, c0 = time.perf_counter(), compiles.mark()
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    # some prompts take one prefill chunk, some three
    lens = rng.integers(chunk // 2, 3 * chunk + 1, size=lanes)
    prompts = [rng.integers(0, model.vocab, int(n)).tolist() for n in lens]

    dispatch.STATS.reset()
    engine = ServeEngine(model, params, policy, lanes=lanes, chunk=chunk,
                         packed=True, weight_format="floatsd8")
    reqs = engine.submit_all(prompts, max_new=max_new)
    metrics = engine.run()
    print(f"[serve] {metrics.format()}", flush=True)
    check(all(r.done and len(r.out) == max_new for r in reqs),
          "serve: not every request finished with max_new tokens")

    # prefill logits of every prompt in one engine-step call, on the
    # engine's own packed weights and serving policy
    s = int(lens.max())
    tokens = np.zeros((lanes, s), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, : len(p)] = p
    lengths = jnp.asarray(lens, jnp.int32)
    caches = model.init_cache(lanes, policy)

    def logits():
        f = jax.jit(lambda p, t, c, n: model.decode_step(
            p, t, c, engine.serve_policy, lengths=n)[0])
        return np.asarray(f(engine.serve_params, jnp.asarray(tokens), caches,
                            lengths), np.float32)

    got = logits()
    check_dispatch("serve", SERVE_OPS)
    wall = time.perf_counter() - t0
    print(f"[serve] wall {wall:.1f}s ({compiles.since(c0)}); one run, not "
          f"a benchmark", flush=True)
    with ref_backend():
        want = logits()

    valid = np.arange(s)[None, :] < lens[:, None]
    check(np.isfinite(got[valid]).all(), "serve: nonfinite Pallas logits")
    scale = float(np.abs(want[valid]).max())
    err = float(np.abs(got - want)[valid].max())
    print(f"[serve] prefill logits [{lanes}x{s}x{got.shape[-1]}]: max |pallas"
          f" - ref| = {err:.3e}, max |ref| = {scale:.3e}, ratio "
          f"{err / scale:.3e} (bound {LOGIT_TOL})", flush=True)
    check(err <= LOGIT_TOL * scale, "serve: logits outside the bound")

    last = want[np.arange(lanes), lens - 1]
    top2 = np.sort(last, axis=-1)[:, -2:]
    decisive = (top2[:, 1] - top2[:, 0]) > LOGIT_TOL * scale
    first = np.array([r.out[0] for r in reqs])
    agree = first == last.argmax(-1)
    print(f"[serve] first greedy token vs ref argmax: {int(agree.sum())}/"
          f"{lanes} agree; {int(decisive.sum())} lanes decisive (top-2 "
          f"margin > bound), {int((agree & decisive).sum())} of them agree",
          flush=True)
    check(bool(agree[decisive].all()),
          "serve: a decisive first token differs from the ref argmax")


def _first_steps(model, batches, opt, lr, policy, mesh, *, seed, steps,
                 donate=True, step_mesh="same"):
    """``steps`` train steps from a fresh seeded init through the trainer's
    own pieces, on ``mesh`` (``step_mesh=None``: the step itself is built
    without one). Returns (losses, grads_finite, final state, the step-1
    optimizer state on the host)."""
    with shd.use_mesh(mesh):
        step = make_train_step(
            model.loss, opt, policy, lr=lr, donate=donate, telemetry=True,
            mesh=mesh if step_mesh == "same" else step_mesh)
        if not donate:
            step = jax.jit(step)
        state = jax.device_put(
            init_state(model.init(jax.random.PRNGKey(seed)), opt, policy),
            NamedSharding(mesh, P()))
        pipe = ShardedPipeline(iter(batches[:steps]), mesh)
        losses, finite, opt1 = [], [], None
        try:
            for _ in range(steps):
                state, m = step(state, next(pipe))
                losses.append(float(m["loss"]))
                finite.append(bool(m["grads_finite"]))
                if opt1 is None:
                    opt1 = jax.device_get(state.opt_state)
        finally:
            pipe.close()
    return losses, finite, state, opt1


def tensor_gaps(got, want) -> dict[str, float]:
    """Relative L2 gap per leaf of two pytrees of the same structure."""
    gaps = {}
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        gaps[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(g - w) / np.linalg.norm(w))
    return gaps


def show(gaps: dict[str, float]) -> str:
    return ", ".join(f"{k} {v:.3e}" for k, v in gaps.items())


def train_phase(model, data, opt, lr, policy, *, seed: int, steps: int = 3,
                compiles: Compiles):
    """``steps`` fused quantized-BPTT train steps on one chip; step 1's
    loss and applied gradient against the ref backend."""
    t0, c0 = time.perf_counter(), compiles.mark()
    batches = list(itertools.islice(data.batches, steps))
    mesh = make_mesh_for_host()
    dispatch.STATS.reset()
    losses, finite, _, opt1 = _first_steps(model, batches, opt, lr, policy,
                                           mesh, seed=seed, steps=steps)
    check_dispatch("train", TRAIN_OPS)
    wall = time.perf_counter() - t0
    print(f"[train] {steps} steps, batch {batches[0]['tokens'].shape}: loss "
          f"{losses}, grads finite {finite}", flush=True)
    print(f"[train] wall {wall:.1f}s ({compiles.since(c0)}); one run, not a "
          f"benchmark", flush=True)
    check(all(np.isfinite(losses)), "train: nonfinite loss")
    check(all(finite), "train: a step was skipped as nonfinite")
    with ref_backend():
        ref_losses, _, _, ref_opt1 = _first_steps(
            model, batches, opt, lr, policy, mesh, seed=seed, steps=1,
            donate=False)
        _, _, _, ctl_opt1 = _first_steps(
            model, batches, opt, lr,
            policy.replace(grad_quant="none", act_bwd="none"), mesh,
            seed=seed, steps=1, donate=False)
    rel = abs(losses[0] - ref_losses[0]) / abs(ref_losses[0])
    print(f"[train] step-1 loss pallas {losses[0]!r} vs ref "
          f"{ref_losses[0]!r}: relative gap {rel:.3e} (bound {LOSS_TOL})",
          flush=True)
    gaps = tensor_gaps(opt1, ref_opt1)
    ctl = tensor_gaps(opt1, ctl_opt1)
    print(f"[train] step-1 applied gradient, pallas vs ref, relative L2 per "
          f"tensor: {show(gaps)} (bound {UPDATE_TOL})", flush=True)
    print(f"[train] control, pallas vs ref without FP8 gradients: "
          f"{show(ctl)} (must exceed {UPDATE_TOL})", flush=True)
    check(rel <= LOSS_TOL, "train: step-1 loss outside the bound")
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= UPDATE_TOL,
          f"train: applied gradient of {worst} outside the bound")
    check(max(ctl.values()) > UPDATE_TOL,
          "train: the control stays inside the bound; it cannot tell")


def dp_phase(model, data, opt, lr, policy, *, seed: int, chips: int,
             compiles: Compiles):
    """One data-parallel train step over a (chips, 1) mesh against the same
    global batch on one chip: loss, update and the layout over devices."""
    t0, c0 = time.perf_counter(), compiles.mark()
    batches = [next(iter(data.batches))]
    mesh = make_mesh_for_host()
    check(mesh.shape["data"] == chips,
          f"dp: host mesh {dict(mesh.shape)} is not ({chips}, 1)")
    mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                              ("data", "model"))
    out = {}
    for name, m in (("1 chip", mesh1), (f"{chips} chips", mesh)):
        dispatch.STATS.reset()
        losses, finite, state, opt1 = _first_steps(
            model, batches, opt, lr, policy, m, seed=seed, steps=1)
        check_dispatch(f"dp {name}", TRAIN_OPS)
        out[name] = (losses[0], finite[0], state, opt1)
        print(f"[dp] {name}: loss {losses[0]!r}, grads finite {finite[0]}",
              flush=True)
    check(all(v[1] for v in out.values()), "dp: a step was skipped")

    devices = set(mesh.devices.flat)
    b = to_global(batches[0], mesh)  # the pipeline's placement
    state4 = out[f"{chips} chips"][2]
    for what, leaves in (("batch", jax.tree_util.tree_leaves(b)),
                         ("params", jax.tree_util.tree_leaves(state4.params))):
        spread = all(leaf.sharding.device_set == devices for leaf in leaves)
        shard_rows = sorted({s.data.shape[0] for leaf in leaves[:1]
                             for s in leaf.addressable_shards})
        print(f"[dp] {what}: {len(leaves)} arrays on "
              f"{sorted(d.id for d in leaves[0].sharding.device_set)}, "
              f"{leaves[0].sharding.spec}, rows per device {shard_rows}",
              flush=True)
        check(spread, f"dp: {what} not laid out over all {chips} devices")

    (l1, _, s1, o1), (l4, _, s4, o4) = out["1 chip"], out[f"{chips} chips"]
    rel_loss = abs(l4 - l1) / abs(l1)
    gaps = tensor_gaps(o4, o1)
    _, _, _, o_ctl = _first_steps(model, batches, opt, lr, policy, mesh1,
                                  seed=seed, steps=1, step_mesh=None)
    ctl = tensor_gaps(o_ctl, o1)
    p1, p4 = (np.concatenate([np.asarray(x, np.float32).ravel()
                              for x in jax.tree_util.tree_leaves(t)])
              for t in (s1.params, s4.params))
    print(f"[dp] updated f16 parameters: {int((p4 != p1).sum())} of "
          f"{p1.size} differ between {chips} chips and one", flush=True)
    print(f"[dp] {chips} chips vs 1: loss gap {rel_loss:.3e} (bound "
          f"{DP_LOSS_TOL}); applied gradient, relative L2 per tensor: "
          f"{show(gaps)} (bound {DP_GRAD_TOL})", flush=True)
    print(f"[dp] control, one chip without a mesh vs 1: {show(ctl)} (must "
          f"exceed {DP_GRAD_TOL})", flush=True)
    print(f"[dp] wall {time.perf_counter() - t0:.1f}s ({compiles.since(c0)});"
          f" one run, not a benchmark", flush=True)
    check(rel_loss <= DP_LOSS_TOL, "dp: loss outside the bound")
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= DP_GRAD_TOL,
          f"dp: applied gradient of {worst} outside the bound")
    check(max(ctl.values()) > DP_GRAD_TOL,
          "dp: the control stays inside the bound; it cannot tell")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel training phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, found {len(devs)}")
    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}", flush=True)
    compiles = Compiles()
    policy = get_policy(POLICY)

    if args.chips == 1:
        serve_phase(WikiText2LM(), policy, seed=args.seed, compiles=compiles)
    model, data, opt, lr, _ = make_task("wikitext2", full=True)
    if args.chips == 1:
        train_phase(model, data, opt, lr, policy, seed=args.seed,
                    compiles=compiles)
    else:
        dp_phase(model, data, opt, lr, policy, seed=args.seed,
                 chips=args.chips, compiles=compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
