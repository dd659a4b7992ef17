"""Plain reference of the paper's WikiText-2 LSTM language model.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the paper (arXiv:2001.08450, sections III-A to III-D, Table
III, Table VI) and the configuration file, importing nothing of the
program: embedding (tied to the output head) -> ``n_layers`` LSTM layers
-> logits. Quantization sites of the Table VI scheme:

* every weight matrix and the embedding table: FloatSD8 (or, for a
  FloatSD4 deployment, FloatSD4 of the FloatSD8 value);
* the embedding output and every LSTM layer input, and h before the
  recurrent matmul: FP8 e5m2 forward, FP8 e5m2 gradient backward;
* gates i, f, o: the two-region FloatSD8 sigmoid; g and tanh(c): FP8;
  the cell state c is stored in float16;
* the head input: float16 forward, FP8 gradient backward;
* weight gradients: FP8 after the backward pass (loss scale 1024), the
  optimizer on a float16 master copy, the update added in float32.

Gradients pass the quantizers straight through: the derivative of a
quantized sigmoid is the derivative of the sigmoid, of a quantized tanh
that of the tanh, of the float16 rounding of c the identity.

``dtype="bfloat16"`` computes every matmul with bfloat16 operands and a
bfloat16 result: the control, one precision below the configuration's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .formats import floatsd4, floatsd8, floatsd8_sigmoid, fp8, fp16

F32 = jnp.float32


# -- quantizer nodes with their gradient rules -----------------------------

@jax.custom_vjp
def _ste_value(w, q):
    """Forward: q (the quantized w); backward: the gradient goes to w."""
    return q


_ste_value.defvjp(lambda w, q: (q, None),
                  lambda _, g: (g, jnp.zeros_like(g)))


def _act(fwd, bwd):
    @jax.custom_vjp
    def f(x):
        return fwd(x)

    f.defvjp(lambda x: (fwd(x), None), lambda _, g: (bwd(g),))
    return f


act_fp8 = _act(fp8, fp8)            # FP8 forward and backward
act_head = _act(fp16, fp8)          # float16 forward, FP8 backward
round_c = _act(fp16, lambda g: g)   # float16 storage of c, identity backward


def _smooth(value_fn, smooth_fn):
    """value_fn forward, derivative of smooth_fn backward."""
    def f(z):
        s = smooth_fn(z)
        return s + jax.lax.stop_gradient(value_fn(z) - s)
    return f


q_sigmoid = _smooth(floatsd8_sigmoid, jax.nn.sigmoid)
q_tanh = _smooth(lambda z: fp8(jnp.tanh(z)), jnp.tanh)


# -- model -----------------------------------------------------------------

def quantize_weights(cfg: dict, w: dict) -> dict:
    """FloatSD8 (or FloatSD4-of-FloatSD8) value of every weight matrix,
    straight-through to the float32 weight."""
    def q(x):
        v = floatsd8(x)
        if cfg["weight_format"] == "floatsd4":
            v = floatsd4(v)
        return _ste_value(x, v)

    out = {"table": q(w["table"])}
    for i in range(cfg["n_layers"]):
        p = w[f"lstm{i}"]
        out[f"lstm{i}"] = {"wx": q(p["wx"]), "wh": q(p["wh"]), "b": p["b"]}
    return out


def _dot(a, b, dtype):
    if dtype == "bfloat16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.bfloat16).astype(F32)
    return jnp.dot(a, b, preferred_element_type=F32,
                   precision=jax.lax.Precision.HIGHEST)


def _layer(p, xs, dtype):
    """xs [S, B, D] (already FP8) -> hs [S, B, H]."""
    b, h = xs.shape[1], p["wh"].shape[0]

    def step(carry, x):
        h_prev, c_prev = carry
        z = _dot(x, p["wx"], dtype) + _dot(act_fp8(h_prev), p["wh"], dtype) + p["b"]
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        i, f, o = q_sigmoid(zi), q_sigmoid(zf), q_sigmoid(zo)
        c = round_c(f * c_prev + i * q_tanh(zg))
        h_new = o * q_tanh(c)
        return (h_new, c), h_new

    zeros = jnp.zeros((b, h), F32)
    _, hs = jax.lax.scan(step, (zeros, zeros), xs)
    return hs


def hidden(cfg: dict, qw: dict, tokens, dtype="float32"):
    """tokens [B, S] -> the head's input [S, B, D] (float16 values)."""
    x = act_fp8(jnp.take(qw["table"], tokens.T, axis=0))   # embedding output
    for i in range(cfg["n_layers"]):
        x = _layer(qw[f"lstm{i}"], act_fp8(x), dtype)
    return act_head(x)


def logits(qw: dict, rows, dtype="float32"):
    """rows [N, D] -> logits [N, V] through the tied head."""
    return _dot(rows, qw["table"].T, dtype)


# -- serving: how far below the reference's best each served token lies ----

@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _serve_hidden(cfg_items, w, tokens, dtype):
    cfg = dict(cfg_items)
    qw = quantize_weights(cfg, w)
    return hidden(cfg, qw, tokens, dtype), qw


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head_block(qw, rows, served, ref_rows, dtype):
    """For each row: reference best logit, reference logit of the served
    token, and (with ``ref_rows``) the reference logit of the token this
    row's own computation puts first."""
    lg = logits(qw, ref_rows, "float32")
    best = jnp.max(lg, axis=-1)
    at_served = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    own_first = jnp.argmax(logits(qw, rows, dtype), axis=-1)
    at_own = jnp.take_along_axis(lg, own_first[:, None], axis=-1)[:, 0]
    return best, at_served, at_own


def serve_gaps(cfg: dict, weights: dict, requests, dtype="float32",
               block: int = 512):
    """Teacher-forced reference over each (prompt, served tokens) pair.

    Returns (gap_served, gap_own): per served token, the reference's best
    logit minus the reference logit of the served token, and minus the
    reference logit of the token that a ``dtype`` computation of the same
    positions puts first (the control's reading; 0 where it agrees).
    """
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, str))))
    lens = [len(p) + len(o) - 1 for p, o in requests]
    s = max(lens)
    toks = np.zeros((len(requests), s), np.int32)
    for i, (p, o) in enumerate(requests):
        seq = list(p) + list(o[:-1])
        toks[i, : len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        hs_ref, qw = _serve_hidden(cfg_items, weights, jnp.asarray(toks), "float32")
        hs_own = hs_ref if dtype == "float32" else _serve_hidden(
            cfg_items, weights, jnp.asarray(toks), dtype)[0]
        # rows of the positions that produced a served token
        lane, pos, served = [], [], []
        for i, (p, o) in enumerate(requests):
            for j, t in enumerate(o):
                lane.append(i)
                pos.append(len(p) - 1 + j)
                served.append(t)
        lane, pos = np.asarray(lane), np.asarray(pos)
        served = np.asarray(served, np.int32)
        n = len(served)
        pad = -n % block
        gs, go = [], []
        for a in range(0, n + pad, block):
            idx = np.arange(a, a + block) % n
            r_ref = hs_ref[pos[idx], lane[idx]]
            r_own = hs_own[pos[idx], lane[idx]]
            best, at_s, at_o = _head_block(qw, r_own, jnp.asarray(served[idx]),
                                           r_ref, dtype)
            gs.append(np.asarray(best - at_s))
            go.append(np.asarray(best - at_o))
    gap_served = np.concatenate(gs)[:n]
    gap_own = np.concatenate(go)[:n]
    # a served id outside the vocabulary is wrong by any measure
    gap_served = np.where(served >= cfg["vocab"], np.inf, gap_served)
    return gap_served, gap_own


# -- training: the first steps of the job ----------------------------------

def _loss(cfg, w, tokens, labels, dtype):
    qw = quantize_weights(cfg, w)
    x = hidden(cfg, qw, tokens, dtype)                     # [S, B, D]
    s, b, d = x.shape
    lg = logits(qw, x.reshape(s * b, d), dtype)            # [S*B, V]
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels.T.reshape(-1, 1), axis=-1)[:, 0]
    return jnp.mean(lse - ll)


def make_train_step(cfg: dict, job: dict, dtype="float32"):
    """One step of the job's optimizer on float16 masters; returns
    (new masters, new momentum, loss, the momentum after this step)."""
    scale = float(cfg["loss_scale"])
    lr, mom, clip = float(job["lr"]), float(job["momentum"]), float(job["grad_clip"])
    if job["optimizer"] != "sgd":
        raise ValueError(f"reference optimizer {job['optimizer']!r} not written")

    def step(masters, buf, tokens, labels, rows_used):
        w32 = jax.tree_util.tree_map(lambda m: m.astype(F32), masters)

        def scaled(w):
            l = _loss(cfg, w, tokens[:rows_used], labels[:rows_used], dtype)
            return l * scale, l

        g, loss = jax.grad(scaled, has_aux=True)(w32)
        g = jax.tree_util.tree_map(fp8, g)                     # FP8 gradients
        g = jax.tree_util.tree_map(lambda x: fp16(x * (1.0 / scale)), g)
        leaves = jax.tree_util.tree_leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
        coef = fp16(jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)))
        g = jax.tree_util.tree_map(lambda x: fp16(x * coef), g)
        buf = jax.tree_util.tree_map(lambda b_, x: mom * b_ + x, buf, g)
        new = jax.tree_util.tree_map(
            lambda m, b_: fp16(m.astype(F32) - lr * b_).astype(jnp.float16),
            masters, buf)
        return new, buf, loss

    return jax.jit(step, static_argnames=("rows_used",))


def train_readings(cfg: dict, job: dict, weights: dict, batches, steps: int = 3,
                   dtype="float32", rows_used: int | None = None,
                   freeze: bool = False):
    """Run ``steps`` reference steps from ``weights`` (float32, reference
    layout) on ``batches`` (list of (tokens, labels) [B, S]). Returns
    (losses, first-step momentum per leaf, change of the masters per leaf),
    the per-leaf values as flat dicts of float32 arrays.

    ``rows_used`` < B and ``freeze`` plant faults in the reference put in
    the program's place: half of the batch left out, and a step that
    returns its state unchanged."""
    step = make_train_step(cfg, job, dtype)
    masters = jax.tree_util.tree_map(lambda x: fp16(x).astype(jnp.float16), weights)
    start = masters
    buf = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), weights)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for t, (tokens, labels) in enumerate(batches[:steps]):
            n = rows_used or tokens.shape[0]
            new, new_buf, loss = step(masters, buf, tokens, labels, n)
            if not freeze:
                masters, buf = new, new_buf
            losses.append(float(loss))
            if t == 0:
                first = new_buf
    change = jax.tree_util.tree_map(
        lambda a, b_: a.astype(F32) - b_.astype(F32), masters, start)
    return losses, flat(first), flat(change)


def flat(tree) -> dict:
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}
