"""The plain reference for a training cell: the first steps of AdamW on a
dense decoder, in float32 at ``highest`` precision, one batch row and one
layer at a time, in ``jax.numpy`` alone.

It imports nothing of the system under test and takes nothing it made: the
sizes come from the configuration file, the optimizer's settings from the
job file, the float32 weights are drawn again from the seed
(``weights.leaf``), and the batches are the token arrays the program was
fed.  The layer equations are those of ``reference.py``; the loss is the
mean token cross-entropy over the batch's masked positions with the tied
or untied head; each layer's gradient comes from ``jax.vjp`` of that layer
alone, so only one layer's activations of one row live at a time.  AdamW
is written out: clipping by the global norm, bias-corrected moments,
decoupled weight decay on every leaf, and the cosine learning rate with no
warm-up.

``state="bfloat16"`` is the control: the master weights and both AdamW
moments are kept in bfloat16, the nearest precision below the float32
state the cell states, as a memory-saving change would keep them.
``rows`` below the batch is a planted fault: the loss is the mean over the
first ``rows`` rows only.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import reference, weights

F32 = jnp.float32


def lr_at(job: dict, step: int) -> float:
    """The job's cosine schedule with no warm-up, at ``step``."""
    s = job["schedule"]
    prog = min(max(step / max(s["total_steps"], 1), 0.0), 1.0)
    peak, fin = job["peak_lr"], s["final_frac"]
    return fin * peak + (1 - fin) * peak * 0.5 * (1 + math.cos(math.pi * prog))


@lru_cache(maxsize=None)
def _fns(h, hk, hd, theta, fraction, eps, vocab):
    fwd = reference._layer_fn(h, hk, hd, theta, fraction, eps)

    @jax.jit
    def layer_vjp(x, w, dy):
        with jax.default_matmul_precision("highest"):
            _, pull = jax.vjp(fwd, x, w)
            return pull(dy)

    def head_loss(x, g, table, labels, mask):
        logits = reference._rmsnorm(x, g, eps) @ table[:vocab].T
        logz = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        return jnp.sum((logz - ll) * mask)

    @jax.jit
    def head(x, g, table, labels, mask):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(head_loss, argnums=(0, 1, 2))(
                x, g, table, labels, mask)

    return fwd, layer_vjp, head


@lru_cache(maxsize=None)
def _adam_fn(b1, b2, eps, wd, state):
    @partial(jax.jit, donate_argnums=(0, 2, 3))
    def upd(p, g, m, n, scale, b1c, b2c, lr):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        step = (m / b1c) / (jnp.sqrt(n / b2c) + eps) + wd * p
        return tuple(_kept(x, state) for x in (p - lr * step, m, n))

    return upd


def _kept(x, state):
    """``x`` as a state of dtype ``state`` holds it (float32 values)."""
    return x if state is None else x.astype(state).astype(F32)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
_embed_add = jax.jit(lambda t, rows, dx: t.at[rows].add(dx),
                     donate_argnums=0)
_sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
_sq_dist = jax.jit(lambda x, y: jnp.sum(jnp.square(x - y)))


def _leaves(p: dict):
    """(program leaf path, array, layer or None) of a reference tree."""
    for path in ("embed", "final_norm/scale", "lm_head"):
        if path.split("/")[0] in p:
            yield path, p[path.split("/")[0]], None
    for i, w in enumerate(p["layers"]):
        for n, x in w.items():
            yield reference.LAYER + reference.SUBPATH[n], x, i


def leaf_norms(tree: dict) -> dict:
    """Per program leaf path, the norm over all its layers."""
    out = {}
    for path, x, _ in _leaves(tree):
        out[path] = out.get(path, 0.0) + float(_sq(x))
    return {k: math.sqrt(v) for k, v in out.items()}


class Reference:
    """The model's float32 weights and AdamW state, stepped on batches."""

    def __init__(self, model: dict, job: dict, seed: int, state=None,
                 rows=None):
        self.m, self.job, self.seed, self.rows = model, job, seed, rows
        self.state = state
        d, vp = model["hidden_size"], reference.padded_vocab(model)
        self.p = {"embed": weights.leaf(seed, "embed", (vp, d), dtype=F32),
                  "final_norm": weights.leaf(seed, "final_norm/scale", (d,),
                                             dtype=F32)}
        if not model["tie_word_embeddings"]:
            self.p["lm_head"] = weights.leaf(seed, "lm_head", (d, vp),
                                             dtype=F32)
        shapes = reference.layer_shapes(model)
        self.p["layers"] = [
            {n: weights.leaf(seed, reference.LAYER + reference.SUBPATH[n],
                             s, i, dtype=F32) for n, s in shapes.items()}
            for i in range(model["num_hidden_layers"])]
        self.p = jax.tree.map(lambda x: _kept(x, state), self.p)
        self.mu = jax.tree.map(jnp.zeros_like, self.p)
        self.nu = jax.tree.map(jnp.zeros_like, self.p)
        self.count = 0
        self.fwd, self.layer_vjp, self.head = _fns(
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], float(model["rope_theta"]),
            float(model.get("rope_fraction", 1.0)),
            float(model["rms_norm_eps"]), model["vocab_size"])

    def loss_and_grads(self, batch: dict):
        """Mean cross-entropy over the batch's rows, its gradient's sum over
        the masked positions, and their count."""
        tokens, labels, mask = (np.asarray(batch[k]) for k in
                                ("tokens", "labels", "mask"))
        rows = self.rows or tokens.shape[0]
        p, tied = self.p, self.m["tie_word_embeddings"]
        g = jax.tree.map(jnp.zeros_like, p)
        table = p["embed"] if tied else p["lm_head"].T
        total, count = 0.0, float(mask[:rows].sum())
        for b in range(rows):
            ids = jnp.asarray(tokens[b])
            x, xs = p["embed"][ids], []
            for w in p["layers"]:
                xs.append(x)
                x = self.fwd(x, w)
            loss, (dx, dg, dtable) = self.head(
                x, p["final_norm"], table, jnp.asarray(labels[b]),
                jnp.asarray(mask[b], F32))
            total += float(loss)
            g["final_norm"] = g["final_norm"] + dg
            if tied:
                g["embed"] = _add(g["embed"], dtable)
            else:
                g["lm_head"] = _add(g["lm_head"], dtable.T)
            del dtable
            for i in reversed(range(len(xs))):
                dx, dw = self.layer_vjp(xs[i], p["layers"][i], dx)
                g["layers"][i] = _add(g["layers"][i], dw)
            g["embed"] = _embed_add(g["embed"], ids, dx)
        return total / count, g, count

    def step(self, batch: dict, step: int) -> dict:
        """One AdamW step → the loss, and per leaf path the norms of the raw
        gradient and of the clipped one the optimizer took."""
        loss, g, count = self.loss_and_grads(batch)
        raw = {k: v / count for k, v in leaf_norms(g).items()}
        o = self.job["adamw"]
        gnorm = math.sqrt(sum(v * v for v in raw.values()))
        scale = 1.0 if o["clip_norm"] is None else min(
            1.0, o["clip_norm"] / max(gnorm, 1e-9))
        self.count += 1
        upd = _adam_fn(o["b1"], o["b2"], o["eps"], o["weight_decay"],
                       self.state)
        consts = (scale / count, 1 - o["b1"] ** self.count,
                  1 - o["b2"] ** self.count, lr_at(self.job, step))
        flat, tree = jax.tree.flatten(self.p)
        out = [upd(p, gi, m, n, *consts) for p, gi, m, n in zip(
            flat, tree.flatten_up_to(g), tree.flatten_up_to(self.mu),
            tree.flatten_up_to(self.nu))]
        del g
        self.p, self.mu, self.nu = (tree.unflatten([o_[i] for o_ in out])
                                    for i in range(3))
        return {"loss": loss, "grad_raw": raw,
                "grad": {k: v * scale for k, v in raw.items()}}

    def change(self) -> dict:
        """Per program leaf path, the norm of how far the weights moved."""
        out = {}
        for path, x, layer in _leaves(self.p):
            y = weights.leaf(self.seed, path, x.shape, layer, dtype=F32)
            out[path] = out.get(path, 0.0) + float(_sq_dist(x, y))
        return {k: math.sqrt(v) for k, v in out.items()}


def run(model: dict, job: dict, seed: int, batches, state=None,
        rows=None) -> dict:
    """The checked steps on ``batches`` → each step's loss, the first
    step's raw and clipped gradient norms per leaf, and each leaf's change
    after the last."""
    ref = Reference(model, job, seed, state, rows)
    steps = [ref.step(b, i) for i, b in enumerate(batches)]
    return {"loss": [s["loss"] for s in steps], "grad": steps[0]["grad"],
            "grad_raw": steps[0]["grad_raw"], "change": ref.change()}
