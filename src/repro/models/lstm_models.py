"""The paper's four LSTM models (§IV-A), faithful to the described layouts.

  UDPOS     : embed -> 2-layer BiLSTM -> FC tagger            (ADAM, ls 1024)
  SNLI      : embed -> FC proj -> 1-layer BiLSTM -> 4xFC      (ADAM, ls 1024)
  Multi30K  : enc(embed+LSTM) -> dec(embed+LSTM+FC)           (ADAM, ls 1024)
  WikiText-2: embed -> 2-layer LSTM -> tied FC decoder        (SGD,  ls 1024)

All are built on the quantized LSTM/Dense sites, so swapping
Policy FP32 <-> FLOATSD8_TABLE2/6 reproduces Table IV's comparisons.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..core.policy import Policy
from ..nn.linear import QuantDense, QuantEmbedding
from ..nn.lstm import BiLSTM, LSTMLayer, LSTMState
from .lm import cross_entropy

__all__ = ["UDPOSTagger", "SNLIClassifier", "Multi30KSeq2Seq", "WikiText2LM"]


@dataclasses.dataclass(frozen=True)
class UDPOSTagger:
    vocab: int = 8000
    n_tags: int = 18
    emb: int = 100
    hidden: int = 128

    def init(self, key):
        ks = jax.random.split(key, 4)
        return {
            "embed": QuantEmbedding(self.vocab, self.emb).init(ks[0]),
            "bilstm1": BiLSTM(self.emb, self.hidden).init(ks[1]),
            "bilstm2": BiLSTM(2 * self.hidden, self.hidden).init(ks[2]),
            "out": QuantDense(2 * self.hidden, self.n_tags).init(ks[3]),
        }

    def specs(self):
        return {
            "embed": QuantEmbedding(self.vocab, self.emb).specs(),
            "bilstm1": BiLSTM(self.emb, self.hidden).specs(),
            "bilstm2": BiLSTM(2 * self.hidden, self.hidden).specs(),
            "out": QuantDense(2 * self.hidden, self.n_tags).specs(),
        }

    def logits(self, p, tokens, policy: Policy):
        x = QuantEmbedding(self.vocab, self.emb).apply(p["embed"], tokens, policy)
        x = BiLSTM(self.emb, self.hidden).apply(p["bilstm1"], x, policy)
        x = BiLSTM(2 * self.hidden, self.hidden).apply(p["bilstm2"], x, policy)
        return QuantDense(2 * self.hidden, self.n_tags).apply(p["out"], x, policy, site="last")

    def loss(self, p, batch, policy: Policy):
        lg = self.logits(p, batch["tokens"], policy)
        return cross_entropy(lg, batch["labels"], batch.get("mask"))

    def accuracy(self, p, batch, policy: Policy):
        lg = self.logits(p, batch["tokens"], policy)
        pred = jnp.argmax(lg, -1)
        m = batch.get("mask", jnp.ones_like(batch["labels"]))
        return jnp.sum((pred == batch["labels"]) * m) / jnp.maximum(jnp.sum(m), 1)


@dataclasses.dataclass(frozen=True)
class SNLIClassifier:
    vocab: int = 20000
    emb: int = 300
    proj: int = 200
    hidden: int = 300
    n_cls: int = 3

    def _mods(self):
        return (
            QuantEmbedding(self.vocab, self.emb),
            QuantDense(self.emb, self.proj),
            BiLSTM(self.proj, self.hidden),
            QuantDense(8 * self.hidden, 512),
            QuantDense(512, 512),
            QuantDense(512, 512),
            QuantDense(512, self.n_cls),
        )

    def init(self, key):
        emb, proj, lstm, f1, f2, f3, f4 = self._mods()
        ks = jax.random.split(key, 7)
        return {
            "embed": emb.init(ks[0]), "proj": proj.init(ks[1]),
            "bilstm": lstm.init(ks[2]),
            "fc1": f1.init(ks[3]), "fc2": f2.init(ks[4]),
            "fc3": f3.init(ks[5]), "fc4": f4.init(ks[6]),
        }

    def specs(self):
        emb, proj, lstm, f1, f2, f3, f4 = self._mods()
        return {
            "embed": emb.specs(), "proj": proj.specs(), "bilstm": lstm.specs(),
            "fc1": f1.specs(), "fc2": f2.specs(), "fc3": f3.specs(), "fc4": f4.specs(),
        }

    def _encode(self, p, tokens, policy):
        emb, proj, lstm, *_ = self._mods()
        x = emb.apply(p["embed"], tokens, policy)
        x = jax.nn.relu(proj.apply(p["proj"], x, policy))
        h = lstm.apply(p["bilstm"], x, policy)
        return jnp.max(h, axis=1)  # max-pool over time

    def logits(self, p, batch, policy: Policy):
        *_, f1, f2, f3, f4 = self._mods()
        u = self._encode(p, batch["premise"], policy)
        v = self._encode(p, batch["hypothesis"], policy)
        feat = jnp.concatenate([u, v, jnp.abs(u - v), u * v], axis=-1)
        h = jax.nn.relu(f1.apply(p["fc1"], feat, policy))
        h = jax.nn.relu(f2.apply(p["fc2"], h, policy))
        h = jax.nn.relu(f3.apply(p["fc3"], h, policy))
        return f4.apply(p["fc4"], h, policy, site="last")

    def loss(self, p, batch, policy: Policy):
        return cross_entropy(self.logits(p, batch, policy)[:, None, :], batch["label"][:, None])

    def accuracy(self, p, batch, policy: Policy):
        return jnp.mean(jnp.argmax(self.logits(p, batch, policy), -1) == batch["label"])


@dataclasses.dataclass(frozen=True)
class Multi30KSeq2Seq:
    src_vocab: int = 8000
    tgt_vocab: int = 8000
    emb: int = 256
    hidden: int = 512

    def _mods(self):
        return (
            QuantEmbedding(self.src_vocab, self.emb),
            LSTMLayer(self.emb, self.hidden),
            QuantEmbedding(self.tgt_vocab, self.emb),
            LSTMLayer(self.emb, self.hidden),
            QuantDense(self.hidden, self.tgt_vocab),
        )

    def init(self, key):
        se, sl, te, tl, out = self._mods()
        ks = jax.random.split(key, 5)
        return {
            "src_embed": se.init(ks[0]), "enc": sl.init(ks[1]),
            "tgt_embed": te.init(ks[2]), "dec": tl.init(ks[3]),
            "out": out.init(ks[4]),
        }

    def specs(self):
        se, sl, te, tl, out = self._mods()
        return {
            "src_embed": se.specs(), "enc": sl.specs(),
            "tgt_embed": te.specs(), "dec": tl.specs(), "out": out.specs(),
        }

    def logits(self, p, batch, policy: Policy):
        se, sl, te, tl, out = self._mods()
        xs = se.apply(p["src_embed"], batch["src"], policy)
        _, enc_state = sl.apply(p["enc"], xs, policy)
        xt = te.apply(p["tgt_embed"], batch["tgt_in"], policy)
        h, _ = tl.apply(p["dec"], xt, policy, state=enc_state)
        return out.apply(p["out"], h, policy, site="last")

    def loss(self, p, batch, policy: Policy):
        return cross_entropy(
            self.logits(p, batch, policy), batch["tgt_out"], batch.get("mask")
        )

    def perplexity(self, p, batch, policy: Policy):
        return jnp.exp(self.loss(p, batch, policy))


@dataclasses.dataclass(frozen=True)
class WikiText2LM:
    """Paper Table III: vocab 33278, 2-layer LSTM hidden=1024, emb 1024.
    The decoder is tied to the embedding: 33278*1024 + 2 * 4*(2*1024+1)*1024
    ~= 50.9M params (Table III's 84.98M counts an untied decoder).

    The embedding table is padded to a multiple of 256 so the vocab dim
    shards over the model axis (perf hillclimb #2b: the raw 33278 is not
    divisible by 16, which forces replicated logits + a [B,S,V] f32
    all-gather at 256-chip scale — measured in EXPERIMENTS.md §Perf).
    Padded logit columns are masked to -inf in the loss.
    REPRO_LSTM_PAD_VOCAB=0 restores the unpadded baseline.
    """

    vocab: int = 33278
    emb: int = 1024
    hidden: int = 1024
    n_layers: int = 2

    # every weight site (embedding gather/attend, LSTM gate matmuls, proj)
    # consumes PackedTensor leaves natively via the kernel dispatch layer,
    # so ServeEngine hands this model the packed tree as-is.
    supports_packed = True

    def _vp(self) -> int:
        import os

        if os.environ.get("REPRO_LSTM_PAD_VOCAB", "1") == "0":
            return self.vocab
        return -(-self.vocab // 256) * 256

    def _mods(self):
        proj = (
            QuantDense(self.hidden, self.emb, use_bias=False)
            if self.hidden != self.emb
            else None
        )
        return (
            QuantEmbedding(self._vp(), self.emb),
            [
                LSTMLayer(self.emb if i == 0 else self.hidden, self.hidden)
                for i in range(self.n_layers)
            ],
            proj,
        )

    def init(self, key):
        emb, layers, proj = self._mods()
        ks = jax.random.split(key, 2 + len(layers))
        p = {
            "embed": emb.init(ks[0]),
            **{f"lstm{i}": l.init(ks[1 + i]) for i, l in enumerate(layers)},
        }
        if proj is not None:
            p["proj"] = proj.init(ks[-1])
        return p

    def specs(self):
        emb, layers, proj = self._mods()
        s = {
            "embed": emb.specs(),
            **{f"lstm{i}": l.specs() for i, l in enumerate(layers)},
        }
        if proj is not None:
            s["proj"] = proj.specs()
        return s

    def logits(self, p, tokens, policy: Policy, states=None, lengths=None,
               inference=False):
        emb, layers, proj = self._mods()
        x = emb.apply(p["embed"], tokens, policy)
        new_states = []
        for i, l in enumerate(layers):
            x, st = l.apply(
                p[f"lstm{i}"], x, policy,
                None if states is None else states[i], lengths=lengths,
                inference=inference,
            )
            new_states.append(st)
        if proj is not None:
            x = proj.apply(p["proj"], x, policy)
        return emb.attend(p["embed"], x, policy), new_states

    def loss(self, p, batch, policy: Policy):
        from .lm import mask_padded_vocab

        lg, _ = self.logits(p, batch["tokens"], policy)
        lg = mask_padded_vocab(lg, self.vocab)
        return cross_entropy(lg, batch["labels"], batch.get("mask"))

    def perplexity(self, p, batch, policy: Policy):
        return jnp.exp(self.loss(p, batch, policy))

    def prefill(self, p, batch, policy: Policy):
        lg, _ = self.logits(p, batch["tokens"], policy)
        return lg

    # serve path: one token at a time with recurrent state as the "cache"
    def init_cache(self, batch, policy: Policy | None = None):
        emb, layers, _ = self._mods()
        hdt = (policy.cdt() if policy else None) or jnp.float32
        cdt = jnp.float16 if (policy and policy.master_dtype == "fp16") else jnp.float32
        return [
            LSTMState(
                jnp.zeros((batch, l.hidden), hdt),
                jnp.zeros((batch, l.hidden), cdt),
            )
            for l in layers
        ]

    def decode_step(self, p, tokens, states, policy: Policy, lengths=None):
        """One batched serving step over a [B, S] token block.

        ``p`` may be a dense param tree or a packed FloatSD8 weight-store
        tree (``kernels.dispatch.PackedTensor`` leaves, 1 byte/weight).
        Packed leaves are consumed at the weight sites themselves through
        the kernel dispatch layer: the embedding gathers codes and decodes
        only the gathered rows, and the gate matmuls either hoist one
        decode out of the time scan (ref backend) or feed the codes to the
        fused decode-in-VMEM Pallas matmul (pallas backend) — the paper
        PE's datapath. ``lengths`` ([B] int32) marks how many of the S
        positions are valid per lane (chunked prefill); the recurrent state
        freezes past each lane's length.
        """
        lg, new_states = self.logits(
            p, tokens, policy, states, lengths=lengths, inference=True
        )
        return lg, new_states
