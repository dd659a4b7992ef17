"""Quantized dense / embedding layers — the paper's matmul site.

QuantDense implements the FloatSD8 x FP8 multiply of paper §III:
  * weights fake-quantized to FloatSD8 with straight-through gradients
    (master copy = the raw param; quantize-at-use == paper's re-quantize
    after update, since quantization is deterministic),
  * input activations quantized to the policy's (fwd, bwd) dtypes,
  * accumulation via ``preferred_element_type=float32`` (DESIGN.md §3.3).
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp

from ..core import floatsd, floatsd4
from ..core.fp8 import act_quant
from ..core.policy import Policy
from ..kernels import dispatch as kd
from . import module as M

__all__ = ["QuantDense", "QuantEmbedding", "quant_weight", "quant_einsum"]

# Perf A/B switch (EXPERIMENTS.md §Perf hillclimb #1/kimi): emit the
# weight-gradient dot in bf16 so the cross-shard gradient reduction (the
# all-reduce the SPMD partitioner inserts at that dot) moves half the bytes.
# Per-shard accumulation stays f32 inside the MXU; only the wire format
# narrows — the paper's FP8-gradient ethos applied at the reduction point.
# Active only for quantized policies (grad_quant != "none").
GRAD_REDUCE_BF16 = os.environ.get("REPRO_GRAD_REDUCE_BF16", "1") != "0"


def _precision(*ops):
    """f32 operands contract at f32 precision, as the kernels' f32 MXU dots
    do: a TPU's default for an f32 dot is one bf16 pass, which would round
    the FP16 last-layer activations and the f32 logit gradients to bf16.
    Narrower operands keep the default (and any enclosing context)."""
    if all(o.dtype == jnp.float32 for o in ops):
        return jax.lax.Precision.HIGHEST
    return None


@functools.lru_cache(maxsize=None)
def _make_einsum_gc(eq: str):
    """einsum with explicit-transpose VJP: dx keeps f32 accumulation; dw is
    emitted bf16 (the gradient-compression point). Supports the plain
    two-operand contractions used at every weight site (no repeated or
    diagonal labels)."""
    ins, out = eq.split("->")
    in1, in2 = ins.split(",")

    @jax.custom_vjp
    def f(x, w):
        return jnp.einsum(eq, x, w, preferred_element_type=jnp.float32,
                          precision=_precision(x, w))

    def fwd(x, w):
        return f(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        dx = jnp.einsum(
            f"{in2},{out}->{in1}", w, g, preferred_element_type=jnp.float32,
            precision=_precision(w, g),
        ).astype(x.dtype)
        dw = jnp.einsum(
            f"{in1},{out}->{in2}", x, g, preferred_element_type=jnp.bfloat16,
            precision=_precision(x, g),
        ).astype(w.dtype)
        return dx, dw

    f.defvjp(fwd, bwd)
    return f


def quant_weight(w: jax.Array, policy: Policy) -> jax.Array:
    """Apply the policy's weight quantizer (site: any matmul weight).

    PackedTensor weights (the serving deployment format) pass through: the
    codes ARE the quantized weights, and the matmul site dispatches them to
    the fused decode+matmul kernel (or decodes for the jnp oracle). Same
    for PackedTensor4 (the sub-byte serving format)."""
    if kd.is_packed(w) or kd.is_packed4(w):
        return w
    if policy.weight_quant == "floatsd8":
        bias = jax.lax.stop_gradient(floatsd.fit_bias(w))
        w = floatsd.quantize_ste(w, bias)
    return w.astype(policy.cdt() or w.dtype)


def quant_act(x: jax.Array, policy: Policy, site: str = "hidden") -> jax.Array:
    fwd, bwd = policy.act_dtypes(site)
    if fwd is None and bwd is None:
        return x
    return act_quant(x, fwd, bwd)


def policy_einsum(eq: str, x: jax.Array, w: jax.Array, policy: Policy):
    """The bare matmul primitive all weight sites share: f32 accumulation,
    bf16 dW emission when the policy quantizes gradients (GRAD_REDUCE_BF16)
    and leaves the gradient sum to the partitioner.
    Operands must already be quantized/cast. Packed weights (either
    format) route to the kernel dispatch layer (inference-only: no VJP
    through codes)."""
    if kd.is_packed(w) or kd.is_packed4(w):
        return kd.packed_einsum(eq, x, w, cast_dtype=policy.cdt())
    if (GRAD_REDUCE_BF16 and policy.grad_quant != "none"
            and not policy.explicit_grad_sum):
        return _make_einsum_gc(eq)(x, w)
    return jnp.einsum(eq, x, w, preferred_element_type=jnp.float32,
                      precision=_precision(x, w))


def quant_einsum(eq: str, x: jax.Array, w: jax.Array, policy: Policy, site: str = "hidden"):
    """einsum with both operands quantized per policy; f32 accumulation."""
    xq = quant_act(x, policy, site)
    cdt = policy.cdt() or x.dtype
    if kd.is_packed(w) or kd.is_packed4(w):
        y = kd.packed_einsum(eq, xq.astype(cdt), w, cast_dtype=policy.cdt())
    else:
        wq = quant_weight(w, policy)
        y = policy_einsum(eq, xq.astype(cdt), wq.astype(cdt), policy)
    return y.astype(cdt)


@dataclasses.dataclass(frozen=True)
class QuantDense:
    in_dim: int
    out_dim: int
    use_bias: bool = True
    in_axis: str = "embed"
    out_axis: str = "mlp"
    name: str = "dense"

    def init(self, key):
        kw, _ = jax.random.split(key)
        p = {"w": M.truncated_normal_init(kw, (self.in_dim, self.out_dim))}
        if self.use_bias:
            p["b"] = jnp.zeros((self.out_dim,), jnp.float32)
        return p

    def specs(self):
        s = {"w": (self.in_axis, self.out_axis)}
        if self.use_bias:
            s["b"] = (self.out_axis,)
        return s

    def apply(self, p, x, policy: Policy, site: str = "hidden"):
        y = quant_einsum("...d,df->...f", x, p["w"], policy, site)
        if self.use_bias:
            y = y + p["b"].astype(y.dtype)
        return y


@dataclasses.dataclass(frozen=True)
class QuantEmbedding:
    vocab: int
    dim: int
    name: str = "embed"

    def init(self, key):
        return {"table": M.truncated_normal_init(key, (self.vocab, self.dim), 0.02)}

    def specs(self):
        return {"table": ("vocab", "embed")}

    def apply(self, p, tokens, policy: Policy):
        """tokens int32 -> embeddings. The embedding *output* is the paper's
        'first layer activation' site (Table V). A packed table gathers the
        1-byte codes first, then decodes only the gathered rows — same
        values as decode-then-gather (decode is element-wise), ~4x less
        gather traffic."""
        if kd.is_packed4(p["table"]):
            y = kd.inference_only(floatsd4.gather_decode(
                p["table"].codes, p["table"].exps, tokens,
                dtype=policy.cdt() or jnp.float32,
            ))
        elif kd.is_packed(p["table"]):
            codes = jnp.take(p["table"].codes, tokens, axis=0)
            y = kd.inference_only(floatsd.decode(
                codes, p["table"].bias, dtype=policy.cdt() or jnp.float32
            ))
        else:
            t = quant_weight(p["table"], policy)
            y = jnp.take(t, tokens, axis=0)
        return quant_act(y, policy, site="first")

    def attend(self, p, x, policy: Policy):
        """Tied-weight logits head: x @ table^T. This is the 'last layer'
        site — Table VI keeps it FP16."""
        y = quant_einsum("...d,vd->...v", x, p["table"], policy, site="last")
        return y
