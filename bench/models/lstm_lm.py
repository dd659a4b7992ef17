"""Weights of the LSTM language model, made by the benchmark from the seed.

One jitted call on the device makes every weight in float32 in the
reference's layout (embedding table [vocab, emb]; per layer wx [in, 4H],
wh [H, 4H], b [4H], gate order i, f, g, o). The program receives the same
values in its own layout (its embedding table padded with zero rows); the
reference receives them as made. Initialisation follows the usual LSTM LM
recipe: table ~ N(0, 0.02) cut at 2 sigma, LSTM weights and biases
~ U(-1/sqrt(H), 1/sqrt(H)), forget-gate bias + 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.harness.traffic import seed_key


@functools.partial(jax.jit, static_argnames=("vocab", "emb", "hidden", "n_layers"))
def _make(key, *, vocab, emb, hidden, n_layers):
    ks = jax.random.split(key, 1 + 3 * n_layers)
    w = {"table": 0.02 * jax.random.truncated_normal(ks[0], -2.0, 2.0, (vocab, emb))}
    lim = 1.0 / hidden ** 0.5
    for i in range(n_layers):
        d = emb if i == 0 else hidden
        kx, kh, kb = ks[1 + 3 * i: 4 + 3 * i]
        b = jax.random.uniform(kb, (4 * hidden,), minval=-lim, maxval=lim)
        w[f"lstm{i}"] = {
            "wx": jax.random.uniform(kx, (d, 4 * hidden), minval=-lim, maxval=lim),
            "wh": jax.random.uniform(kh, (hidden, 4 * hidden), minval=-lim, maxval=lim),
            "b": b.at[hidden: 2 * hidden].add(1.0),
        }
    return w


def make_weights(cfg: dict, seed: int) -> dict:
    return _make(seed_key(seed), vocab=cfg["vocab"], emb=cfg["emb"],
                 hidden=cfg["hidden"], n_layers=cfg["n_layers"])


def program_model(cfg: dict):
    from repro.models.lstm_models import WikiText2LM

    return WikiText2LM(vocab=cfg["vocab"], emb=cfg["emb"], hidden=cfg["hidden"],
                       n_layers=cfg["n_layers"])


def program_params(model, weights: dict) -> dict:
    """The benchmark's weights in the program's layout (zero-padded table)."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rows = shapes["embed"]["table"].shape[0]
    tree = {k: v for k, v in weights.items() if k != "table"}

    @jax.jit
    def place(t, rest):
        out = dict(rest)
        out["embed"] = {"table": jnp.pad(t, ((0, rows - t.shape[0]), (0, 0)))}
        return out

    params = place(weights["table"], tree)
    got = jax.tree_util.tree_structure(params)
    want = jax.tree_util.tree_structure(shapes)
    if got != want:
        raise ValueError(f"weights do not match the program's layout: {got} vs {want}")
    return params
