"""Training cells: the program's train step as ``launch/train.py --full``
builds it (``make_train_step`` under a ("data", "model") mesh of the
cell's chips, donated state, telemetry on), on a job read from the mix.

Set-up builds the one step object and its state from the seed's weights,
and drives it through the first three steps on the window's own feed;
those steps compile the step and give the readings the reference is held
to (loss of each step, the first gradient as the optimizer holds it after
step 1, the change of the masters after step 3). The same object then
runs the window: whole steps, each ended by reading its loss on the host,
until the window's time is up. The window keeps about AHEAD_S seconds of
steps dispatched ahead of the one whose loss it reads, so that a stall of
the host does not leave the chip idle; once the time is up it sends no
more, waits for every step sent, and reads the clock after that wait.
Every op dispatched from the first step to the end of the window must
have run as compiled Pallas.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.harness import compiles, device, traffic
from bench.harness import trace as T

FIRST = 3          # steps the reference follows
TRACE_STEPS = 2    # steps in the traced part of a --trace 1 run
AHEAD_S = 6.0      # seconds of steps dispatched ahead of the loss read


def leaf_names(tree) -> list:
    import jax

    names = []
    for path, _ in jax.tree_util.tree_leaves_with_path(tree):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        names.append("/".join(str(k) for k in keys if k != "embed"))
    return names


def setup(r) -> dict:
    """The step object and its state from the seed, driven through the
    first steps; what they read, for the reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core.policy import get_policy
    from repro.distributed import sharding as shd
    from repro.kernels import dispatch
    from repro.optim import sgd
    from repro.optim.train_state import init_state, make_train_step

    tm = {"driver": time.monotonic()}
    cfg, job = r.cfg, r.mix
    chips = len(r.devices)
    models = r.module("models", cfg["model"])
    model = models.program_model(cfg)
    weights = models.make_weights(cfg, r.seed)
    params = jax.block_until_ready(models.program_params(model, weights))
    policy = get_policy(r.overrides.get("policy", cfg["policy"]))
    if job["optimizer"] != "sgd":
        raise ValueError(f"optimizer {job['optimizer']!r} not driven")
    opt, lr = sgd(job["momentum"]), job["lr"]
    rows, seq = job["batch_per_chip"] * chips, job["seq"]
    mesh = Mesh(np.asarray(r.devices).reshape(chips, 1), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))
    toks = traffic.zipf_markov_tokens(job, r.seed, job["distinct_batches"], rows,
                                      cfg["vocab"])

    @jax.jit
    def split(t):
        return [{"tokens": t[i, :, :-1], "labels": t[i, :, 1:]} for i in range(t.shape[0])]

    batches = jax.block_until_ready(
        jax.device_put(split(toks), NamedSharding(mesh, P("data", None))))
    tm["weights_data"] = time.monotonic()
    with shd.use_mesh(mesh):
        step = make_train_step(model.loss, opt, policy, lr=lr, donate=True,
                               telemetry=job["telemetry"], mesh=mesh)
        state = jax.device_put(init_state(params, opt, policy), NamedSharding(mesh, P()))

        @jax.jit
        def norms(tree):
            return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                    for x in jax.tree_util.tree_leaves(tree)]

        @jax.jit
        def change(masters, start):
            # both sides are stored masters: a cast pair f32 -> f16 -> f32
            # inside one program may be dropped by XLA on a TPU
            return [jnp.sqrt(jnp.sum(jnp.square(
                m.astype(jnp.float32) - s.astype(jnp.float32))))
                for m, s in zip(jax.tree_util.tree_leaves(masters),
                                jax.tree_util.tree_leaves(start))]

        start = jax.tree_util.tree_map(jnp.copy, state.params)
        tm["build"] = time.monotonic()

        dispatch.STATS.reset()
        names = leaf_names(state.params)
        losses, grad1, finite = [], None, []
        for t in range(FIRST):
            t_step = time.monotonic()
            state, m = step(state, batches[t])
            losses.append(float(m["loss"]))
            step_s = time.monotonic() - t_step   # the last is warm
            finite.append(bool(m["grads_finite"]))
            if t == 0:
                grad1 = [float(x) for x in norms(state.opt_state)]
        moved = [float(x) for x in change(state.params, start)]
        del start
    tm["first_steps"] = time.monotonic()
    parts, last = {"to_driver": tm["driver"] - r.t_start}, tm["driver"]
    for k in ("weights_data", "build", "first_steps"):
        parts[k], last = tm[k] - last, tm[k]
    host_batches = [(np.asarray(b["tokens"]), np.asarray(b["labels"]))
                    for b in batches[:FIRST]]
    return {"step": step, "state": state, "batches": batches, "mesh": mesh,
            "weights": weights, "rows": rows, "seq": seq, "chips": chips,
            "readings": (names, losses, grad1, moved), "finite": finite,
            "host_batches": host_batches, "setup_parts": parts,
            "ahead": max(1, min(8, round(AHEAD_S / step_s)))}


def window(st: dict, seconds: float, trace: bool = False) -> dict:
    """Whole steps of the same object until ``seconds`` have passed, the
    loss of each read on the host ``st["ahead"]`` steps after it was sent;
    the window ends on the last step's loss."""
    from repro.distributed import sharding as shd

    step, batches, ahead = st["step"], st["batches"], st["ahead"]
    counter = compiles.Counter()
    traced, losses, pending = {}, [], []
    k, traced_steps = 0, None
    with shd.use_mesh(st["mesh"]):
        t0 = time.monotonic()
        while True:
            if trace and traced_steps is None and time.monotonic() - t0 >= seconds / 3:
                traced_steps = TRACE_STEPS
                losses += [float(x) for x in pending]   # the traced steps alone
                pending = []
                with T.capture(traced):
                    for _ in range(TRACE_STEPS):
                        with T.span("bench.step"):
                            st["state"], m = step(st["state"], batches[(FIRST + k) % len(batches)])
                            losses.append(float(m["loss"]))
                        k += 1
            else:
                st["state"], m = step(st["state"], batches[(FIRST + k) % len(batches)])
                pending.append(m["loss"])
                k += 1
                if len(pending) > ahead:
                    losses.append(float(pending.pop(0)))
            if time.monotonic() - t0 >= seconds:
                break
        losses += [float(x) for x in pending]
        t1 = time.monotonic()
    return {"steps": k, "seconds": t1 - t0, "losses": losses, "compiles": counter.count,
            "trace": traced.get("trace"), "traced_steps": traced_steps}


def reference_readings(r, st: dict, **fault):
    """The reference's readings of the same first steps."""
    import jax.numpy as jnp

    ref = r.module("reference", r.cfg["reference"])
    return ref.train_readings(r.cfg, r.mix, st["weights"],
                              [tuple(map(jnp.asarray, b)) for b in st["host_batches"]],
                              FIRST, **fault)


def run(r) -> None:
    st = setup(r)
    setup_s = time.monotonic() - r.t_start
    w = window(st, r.seconds, trace=r.trace)
    on_oracle = compiles.oracle_ops()   # the first steps and the window
    # the first steps run the window's step: the process's peak is the step's
    mem = device.memory_peak_bytes(r.devices)
    rows, seq = st["rows"], st["seq"]
    rate = w["steps"] * rows * seq / w["seconds"]
    names, losses, grad1, moved = st["readings"]
    r.result.update(
        e2e={"train_tokens_per_s": rate, "setup_s": setup_s},
        attempted=w["steps"], failed=int(sum(not np.isfinite(x) for x in w["losses"])),
        memory_peak_bytes=mem, trace=w["trace"],
        extra={"setup_parts": st["setup_parts"], "window_steps": w["steps"], "window_s": w["seconds"],
               "steps_ahead": st["ahead"],
               "compiles_in_window": w["compiles"],
               "first_losses": losses, "first_steps_finite": st["finite"]},
    )
    r.layer = {"traced_steps": w["traced_steps"], "rows": rows, "seq": seq,
               "tokens_per_s": rate, "chips": st["chips"]}

    # -- correct: the first steps against the reference ---------------------
    for k in ("step", "state", "batches"):
        st.pop(k)
    gc.collect()
    ref_losses, ref_grad, ref_change = reference_readings(r, st)
    r.result["checks"], diag = compare(r.limits, names, losses, grad1, moved,
                                       ref_losses, ref_grad, ref_change)
    r.result["extra"].update(reference_losses=ref_losses, **diag)
    if not r.overrides.get("allow_cpu"):
        r.result["checks"]["oracle_ops"] = {"value": on_oracle, "limit": 0,
                                            "ok": on_oracle == 0}


def _leaf(name: str) -> str:
    return name.strip("[]'").replace("']['", "/")


def gap_of_norms(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """Worst leaf: |norm(program) - norm(reference)| over the larger of
    the reference leaf's norm and the median leaf's norm."""
    med = float(np.median([ref[k] for k in ref if k not in skip]))
    worst, at = 0.0, ""
    for k, v in ref.items():
        if k in skip:
            continue
        g = abs(prog[k] - v) / max(v, med)
        if g > worst or not np.isfinite(g):
            worst, at = g, k
    return worst, at


def compare(lim, names, losses, grad1, moved, ref_losses, ref_grad, ref_change):
    """The compared numbers, each with its limit, and what else was read:
    (checks, {"loss_gap", per-leaf norms program vs reference})."""
    rg = {_leaf(k): float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in ref_grad.items()}
    rc = {_leaf(k): float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in ref_change.items()}
    pg = dict(zip(names, grad1))
    pc = dict(zip(names, moved))
    med = float(np.median(list(rg.values())))
    # leaves the reference's gradient leaves at rounding do not count
    still = {k for k, v in rg.items() if v < 1e-3 * med}
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    grad_gap, _ = gap_of_norms(pg, rg)
    move_gap, _ = gap_of_norms(pc, rc, skip=still)
    diag = {"loss_gap": loss_gap,
            "leaves": {k: {"grad": [pg[k], rg[k]], "change": [pc[k], rc[k]]} for k in rg}}
    out = {}
    for name, v in (("grad_norm_gap", grad_gap), ("change_norm_gap", move_gap)):
        v = float(v) if np.isfinite(v) else float("inf")
        out[name] = {"value": v, "limit": lim[name], "ok": v <= lim[name]}
    return out, diag
