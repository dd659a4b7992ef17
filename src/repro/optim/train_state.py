"""TrainState: the paper's full update pipeline as one jittable step.

  loss*1024 -> backward (acts/act-grads FP8 inside the model) ->
  weight grads FP8 -> unscale f32, finite check ->
  optimizer update -> FP16 master add -> (re)quantize-at-use next step.

Two gradient paths, selected by ``make_train_step``:

  * **fused** (default when ``policy.grad_quant == 'fp8'``): the loss runs
    under ``grad_quant='fp8_kernel'`` — BPTT goes through the hand-written
    scan VJP and the LSTM gate matmuls emit their dW through the FP8
    quantizer *inside* the registered backward kernels
    (``kernels.dispatch.matmul_dw``). The ``grad_quant`` sweep below is an
    exact no-op on those leaves (fp8 is idempotent) and only provides the
    paper's §III-D coverage + overflow saturation for params no kernel
    emits (biases, embeddings, non-LSTM direct-use params) — it is a
    safety net, not the quantizer, on the hot leaves.
  * **autodiff baseline** (``fused=False`` or ``REPRO_FUSED_BPTT=0``): the
    pre-fusion behaviour — plain autodiff BPTT, with the same tree pass
    doing ALL the gradient quantization.

Given a mesh with a "data" axis (of any size, one device included), the
step runs data parallel under ``shard_map``: each device gets its slice of
the batch and a replica of the state (Pallas kernels are not partitioned
automatically) and differentiates its share of the global mean loss,
taken against f32 copies of the masters, so no partial gradient is
rounded (``Policy.explicit_grad_sum``: no weight site rounds its dW
either). The f32 gradients are summed over the axis, the sweep snaps the
sum to FP8, and only then is it cast to the master dtype (exact: FP8
values are representable at fp16). One, two or four devices thus compute
the same update up to f32 summation order. Without a mesh the gradients
take the masters' dtype as autodiff gives it, before the sweep.

Skip-on-nonfinite keeps dynamic loss scaling sound; with static scaling
(paper) a nonfinite step is skipped the same way (equivalent to PyTorch's
GradScaler semantics the baselines use). The finite check, skip select, and
scale adjustment are all part of the single jitted step; ``donate=True``
additionally donates the TrainState argument so the params/optimizer
buffers are updated in place instead of copied every step.
"""
from __future__ import annotations

import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import loss_scaling as ls
from ..core.fp8 import grad_quant
from ..core.policy import Policy
from ..obs import telemetry as obs_telemetry
from .optimizers import Optimizer

__all__ = ["TrainState", "make_train_step"]


class TrainState(NamedTuple):
    step: jax.Array
    params: Any  # master copy (policy.master_dtype)
    opt_state: Any
    scale: ls.LossScaleState


def init_state(params, opt: Optimizer, policy: Policy, dynamic_scale=False) -> TrainState:
    mdt = policy.mdt()
    master = jax.tree_util.tree_map(lambda p: p.astype(mdt), params)
    st = (
        ls.dynamic_init() if dynamic_scale else ls.static_init(policy.loss_scale)
    )
    return TrainState(jnp.zeros((), jnp.int32), master, opt.init(master), st)


def make_train_step(loss_fn, opt: Optimizer, policy: Policy, lr: float = 1e-3,
                    grad_clip: float | None = 1.0, fused: bool | None = None,
                    donate: bool = False, telemetry: bool = False,
                    mesh=None):
    """loss_fn(params, batch, policy) -> scalar. Returns a step fn.

    ``fused=None`` resolves to ``policy.grad_quant == 'fp8'`` unless
    ``REPRO_FUSED_BPTT=0`` (the killswitch restoring the tree-pass path).
    ``donate=True`` returns the step already jitted with the TrainState
    argument donated — callers must rebind ``state`` every step (every
    driver in this repo does).

    ``telemetry=True`` adds quantization-health stats (obs.telemetry) to
    the metrics dict under ``"tel"``: FP8 saturation/underflow/zero
    fractions measured on the loss-scaled grads at the §III-D sweep
    point, per-layer grad norms on the unscaled grads, and FloatSD
    carry/clamp fractions of the master-weight update. All computed
    inside the jitted step; feed the per-step dicts to a
    ``TelemetryLogger`` for aggregation + JSONL output.

    ``mesh`` with a "data" axis makes the step data parallel over it
    (module docstring); the batch must divide over the axis.
    """
    if fused is None:
        fused = (
            policy.grad_quant == "fp8"
            and os.environ.get("REPRO_FUSED_BPTT", "1") != "0"
        )
    run_policy = (
        policy.replace(grad_quant="fp8_kernel")
        if fused and policy.grad_quant == "fp8"
        else policy
    )

    dp = mesh is not None and "data" in mesh.shape
    n_dp = mesh.shape["data"] if dp else 1
    if dp:
        run_policy = run_policy.replace(explicit_grad_sum=True)

    def step(state: TrainState, batch):
        def scaled_loss(p):
            l = loss_fn(p, batch, run_policy)
            # data parallel: each device differentiates its share of the
            # global mean (local mean / n_dp), so every per-row gradient
            # has the one-chip scale, which the FP8 activation-gradient
            # quantizer (and its underflow) depends on; the shares sum
            g = l.astype(jnp.float32) / n_dp
            return ls.scale_loss(g, state.scale), l

        params = state.params
        if dp:
            params = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32), params)
        grads, raw_loss = jax.grad(scaled_loss, has_aux=True)(params)
        if dp:
            grads = jax.lax.psum(grads, "data")
            raw_loss = jax.lax.pmean(raw_loss, "data")
        # sweep-point telemetry: the loss-scaled values the FP8 quantizer
        # is about to see (saturation/underflow are scale-relative)
        tel = obs_telemetry.fp8_grad_stats(grads) if telemetry else None
        if run_policy.grad_quant in ("fp8", "fp8_kernel"):
            # paper §III-D: ALL gradients FP8. Idempotent (exact no-op) on
            # the leaves the fused backward kernels already emitted on the
            # fp8 grid; quantizes + saturates everything else.
            grads = grad_quant(grads)
        grads = jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), grads, state.params)
        grads, finite = ls.unscale_and_check(grads, state.scale)
        if telemetry:
            tel["grad_norm"] = obs_telemetry.layer_grad_norms(grads)
        if grad_clip is not None:
            gnorm = jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)
                )
            )
            coef = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-9))
            grads = jax.tree_util.tree_map(lambda g: g * coef.astype(g.dtype), grads)

        updates, new_opt = opt.update(grads, state.opt_state, state.params, lr)

        def apply(p, u):
            # FP16 master + update addition (f32 add, stored back at mdt)
            return (p.astype(jnp.float32) + u.astype(jnp.float32)).astype(p.dtype)

        new_params = jax.tree_util.tree_map(apply, state.params, updates)
        # skip-on-nonfinite: keep old state when grads overflowed
        new_params = jax.tree_util.tree_map(
            lambda n, o: jnp.where(finite, n, o), new_params, state.params
        )
        new_opt = jax.tree_util.tree_map(
            lambda n, o: jnp.where(finite, n, o) if isinstance(n, jax.Array) and n.shape == getattr(o, "shape", None) else n,
            new_opt, state.opt_state,
        )
        new_scale = ls.adjust(state.scale, finite)
        metrics = {
            "loss": raw_loss,
            "grads_finite": finite,
            "loss_scale": new_scale.scale,
        }
        if telemetry:
            # carry/clamp on the applied update (post skip-select, so a
            # skipped step honestly reports zero carries)
            tel.update(
                obs_telemetry.floatsd_update_stats(state.params, new_params)
            )
            metrics["tel"] = tel
        return TrainState(state.step + 1, new_params, new_opt, new_scale), metrics

    if dp:
        step = jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
            check_vma=False,
        )
    if donate:
        return jax.jit(step, donate_argnums=(0,))
    return step
