"""Precision policies (paper Tables II & VI).

A ``Policy`` says, for every quantization *site* in a model, what to do:

  w  - weights at matmul sites          (floatsd8 | none)
  g  - weight gradients                 (fp8 | none)
  a  - inter-layer activations fwd/bwd  (fp8 | fp16 | none)
  o  - last-layer output activations    (fp16 in Table VI; fp8 in Table II)
  f  - first-layer (embedding output)   (fp8; Table V ablation varies this)
  m  - master copy of weights           (fp32 | fp16)
  s  - sigmoid gates                    (floatsd8 two-region | none)

plus the compute dtype the matmuls run in and the loss scale. Policies are
hashable (usable as jit static args) and threaded through every layer.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

__all__ = ["Policy", "FP32", "BF16", "FLOATSD8_TABLE2", "FLOATSD8_TABLE6", "get_policy"]

# sentinel dtype names
_DTYPES = {
    "fp8": jnp.float8_e5m2,
    "fp8_e4m3": jnp.float8_e4m3fn,
    "fp16": jnp.float16,
    "bf16": jnp.bfloat16,
    "fp32": jnp.float32,
    "none": None,
}


def _dt(name: str | None):
    if name is None:
        return None
    return _DTYPES[name]


@dataclasses.dataclass(frozen=True)
class Policy:
    name: str = "fp32"
    weight_quant: str = "none"  # "floatsd8" | "none"
    grad_quant: str = "none"  # "fp8" | "none"   (weight grads, post-backward)
    act_fwd: str = "none"  # inter-layer activations, forward
    act_bwd: str = "none"  # inter-layer activation-gradients, backward
    first_layer_act: str = "none"  # embedding output (Table V col 1)
    last_layer_act: str = "none"  # logits/output layer (Table V col 2)
    master_dtype: str = "fp32"  # optimizer master copy (Table IV col 4)
    sigmoid_quant: bool = False  # two-region FloatSD8 sigmoid (Eq. 7-8)
    compute_dtype: str = "fp32"  # dtype matmuls execute in
    param_dtype: str = "fp32"  # dtype quantized weights are materialized in
    loss_scale: float = 1.0
    # the train step sums the per-device f32 gradients itself (shard_map
    # over a "data" mesh axis) and rounds the sum once, in its FP8 sweep:
    # weight sites then emit raw f32 dW, neither snapped to FP8 at the LSTM
    # kernels' flush nor narrowed to bf16 for an all-reduce the SPMD
    # partitioner would insert at the dot
    explicit_grad_sum: bool = False

    # -- dtype accessors -------------------------------------------------
    def cdt(self):
        return _dt(self.compute_dtype)

    def pdt(self):
        return _dt(self.param_dtype)

    def mdt(self):
        return _dt(self.master_dtype)

    def act_dtypes(self, site: str = "hidden"):
        """(fwd_dtype, bwd_dtype) for an activation site:
        'first' | 'hidden' | 'last'."""
        fwd = {"first": self.first_layer_act, "last": self.last_layer_act}.get(
            site, self.act_fwd
        )
        return _dt(fwd), _dt(self.act_bwd)

    def replace(self, **kw) -> "Policy":
        return dataclasses.replace(self, **kw)


FP32 = Policy(name="fp32")

BF16 = Policy(name="bf16", compute_dtype="bf16", param_dtype="bf16")

# Table II: the original proposed scheme — FP32 master, FP8 everywhere.
FLOATSD8_TABLE2 = Policy(
    name="floatsd8_table2",
    weight_quant="floatsd8",
    grad_quant="fp8",
    act_fwd="fp8",
    act_bwd="fp8",
    first_layer_act="fp8",
    last_layer_act="fp8",
    master_dtype="fp32",
    sigmoid_quant=True,
    loss_scale=1024.0,
)

# Table VI: the modified scheme — FP16 master, FP16 last-layer activations.
FLOATSD8_TABLE6 = FLOATSD8_TABLE2.replace(
    name="floatsd8_table6",
    last_layer_act="fp16",
    master_dtype="fp16",
)

# TPU-production variant: identical quantization sites, bf16 matmul issue
# dtype so the MXU runs at full rate (DESIGN.md §3.3).
FLOATSD8_TPU = FLOATSD8_TABLE6.replace(
    name="floatsd8_tpu", compute_dtype="bf16", param_dtype="bf16"
)

_REGISTRY = {
    p.name: p for p in (FP32, BF16, FLOATSD8_TABLE2, FLOATSD8_TABLE6, FLOATSD8_TPU)
}


def get_policy(name: str, **overrides: Any) -> Policy:
    p = _REGISTRY[name]
    return p.replace(**overrides) if overrides else p
