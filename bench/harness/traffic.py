"""The one generator of traffic: it reads a mix's parameters (a data file
under ``bench/traffic/``) and makes the inputs of a run from the seed.

The sizes and the arrival gaps, in their order, are drawn once from the
mix's ``base_seed``; ``--seed`` draws the token ids (and, elsewhere, the
weights). So every seed offers the same work at the same times. The order
is fixed too: near the knee of a serving cell a reordering of the same
bursts and lengths moves the queueing, and with it the latencies, far more
than the run-to-run noise of one order (PERF.md).
"""
from __future__ import annotations

import math

import numpy as np


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(spec: dict, rate: float, n: int, rng) -> np.ndarray:
    if spec["process"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    shape = 1.0 / spec["cv"] ** 2
    g = rng.gamma(shape, 1.0 / (rate * shape), n)
    return g * (n / rate) / g.sum()   # exactly the mean rate over the n gaps


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """Open-loop schedule for ``seconds``: due times (s from the window's
    start; request i is due after the first i gaps), the gaps, prompts
    (int32 arrays) and output lengths."""
    rate = float(mix["rate_rps"])
    n = max(1, int(math.ceil(rate * seconds)))
    base = np.random.default_rng(mix["base_seed"])
    plen = _lengths(mix["prompt_len"], n, base)
    olen = _lengths(mix["output_len"], n, base)
    gaps = _gaps(mix["arrivals"], rate, n, base)
    rng = np.random.default_rng(seed)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = [rng.integers(0, vocab, int(k), dtype=np.int32) for k in plen]
    return {"due": due, "gaps": gaps, "prompts": prompts,
            "max_new": olen.astype(int).tolist()}


def warmup_requests(seed: int, vocab: int, lanes: int, chunk: int):
    """One request per lane, each with two prefill chunks and a decode step:
    every step width and per-lane path the window uses runs once."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, 2 * chunk + 1, dtype=np.int32) for _ in range(lanes)]


def zipf_markov_tokens(job: dict, seed: int, n_batches: int, rows: int, vocab: int):
    """[n_batches, rows, seq + 1] int32 token ids on the device, made in one
    jitted call: a second-order Markov chain whose successor of each
    context is drawn Zipf-weighted from a fixed table of candidates (the
    shape of a WikiText-2 stream), starts drawn from the seed."""
    import jax
    import jax.numpy as jnp

    data = job["data"]
    seq, branch, ctxs = job["seq"], data["branch"], data["contexts"]
    p = 1.0 / np.arange(1, branch + 1) ** data["zipf_a"]
    logp = jnp.asarray(np.log(p / p.sum()), jnp.float32)

    @jax.jit
    def make(key):
        kt, ks, kc = jax.random.split(key, 3)
        table = jax.random.randint(jax.random.PRNGKey(data["table_seed"]),
                                   (ctxs, branch), 0, vocab)
        m = n_batches * rows
        start = jax.random.randint(ks, (2, m), 0, vocab)
        choice = jax.random.categorical(kc, logp, shape=(seq - 1, m))

        def step(carry, ch):
            a, b = carry
            nxt = table[(a * 31 + b) % ctxs, ch]
            return (b, nxt), nxt

        _, rest = jax.lax.scan(step, (start[0], start[1]), choice)
        toks = jnp.concatenate([start, rest], axis=0).T      # [m, seq + 1]
        del kt
        return toks.reshape(n_batches, rows, seq + 1).astype(jnp.int32)

    return make(seed_key(seed))


def seed_key(seed: int):
    """A JAX PRNG key for any whole number up to 2**64."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
