"""The weights from the seed, and the plain float32 reference train step.

Nothing here imports the system under test.  ``init_params`` makes the
weights both sides start from (the program is handed them; the
reference makes them again after the window); ``Reference`` runs the
configuration's train step - a Llama-style decoder (RMSNorm, RoPE with
rotate-half, grouped-query causal attention, SwiGLU), mean token
cross-entropy over the mask, AdamW with global-norm clipping - in
straightforward ``jax.numpy``, float32, at ``highest`` matmul precision.

Departures from the published models, all shared with the trainer being
compared: RMSNorm keeps its scale as ``1 + w`` with ``w`` starting at 0,
and weight decay applies to every leaf.  RMSNorm's epsilon is the
configuration file's ``rms_norm_eps``, which states the trainer's fixed
value (the file's ``differs_from_source`` gives the published one).  Parameters are stored in the
configuration's ``torch_dtype`` after each update and the Adam moments in
its optimizer's ``state_dtype``, as the configuration states.

The reference fits beside nothing else on a 16 GiB chip at the cells'
widths, so it runs once the program's state is freed, and computes each
batch's gradient in blocks of rows, summing the loss numerators.

``rounding="fp8"`` is the control: every matmul operand is rounded to
float8 (e4m3) on the way forward, with the backward pass straight
through, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# embedding and head tables are padded to this many rows (the trainer's
# tile boundary); padded rows are never a label and never win a softmax
TABLE_ALIGN = 128


def table_rows(c: Dict[str, Any]) -> int:
    return -(-c["vocab_size"] // TABLE_ALIGN) * TABLE_ALIGN


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    too)."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        key = jax.random.fold_in(key, np.uint32(word & 0xFFFFFFFF))
    return key


def init_params(c: Dict[str, Any], key) -> Dict[str, Any]:
    """Random weights in the trainer's tree layout, in the stated dtype:
    dense kernels N(0, 1/fan_in), embeddings N(0, 0.02^2), norm scales 0."""
    d, n_l = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, f, v = d // h, c["intermediate_size"], table_rows(c)
    dt = jnp.dtype(c["torch_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dt)

    def dense(shape):
        return normal(shape, 1.0 / math.sqrt(shape[-2]))

    return {
        "embed": normal((v, d), 0.02),
        "lm_head": dense((d, v)),
        "final_norm": jnp.zeros((d,), dt),
        "layers": {
            "ln1": jnp.zeros((n_l, d), dt),
            "ln2": jnp.zeros((n_l, d), dt),
            "attn": {"wq": dense((n_l, d, h * hd)),
                     "wk": dense((n_l, d, kv * hd)),
                     "wv": dense((n_l, d, kv * hd)),
                     "wo": dense((n_l, h * hd, d))},
            "ffn": {"w1": dense((n_l, d, f)), "w3": dense((n_l, d, f)),
                    "w2": dense((n_l, f, d))},
        },
    }


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf L2 norm (float32), keyed by the leaf's path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = _norms(tuple(x for _, x in flat))
    return {jax.tree_util.keystr(k): float(n)
            for (k, _), n in zip(flat, jax.device_get(norms))}


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves]


class Weights:
    """The seed's initial weights of one configuration, made on the device
    in one jitted call, and the per-leaf norm of a change from them."""

    def __init__(self, c: Dict[str, Any]):
        self.init = jax.jit(lambda key: init_params(c, key))

        def change(key, leaves):
            init = jax.tree.leaves(init_params(c, key))
            return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                                - y.astype(jnp.float32))))
                    for x, y in zip(leaves, init)]

        self._change = jax.jit(change)

    def change_norms(self, key, params) -> Dict[str, float]:
        """Per-leaf norm of ``params`` minus the seed's initial weights."""
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        norms = self._change(key, [x for _, x in flat])
        return {jax.tree_util.keystr(k): float(n)
                for (k, _), n in zip(flat, jax.device_get(norms))}


# -- the model ------------------------------------------------------------


def _fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


ROUNDINGS = {"f32": lambda x: x, "fp8": _fp8}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x (B, S, H, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def nll_sum(c: Dict[str, Any], rnd, p, tokens, labels, mask):
    """Sum over masked positions of -log p(label); ``p`` float32."""
    d, n_l = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd, eps = d // h, c["rms_norm_eps"]
    mm = lambda a, b: rnd(a) @ rnd(b)
    x = p["embed"][tokens]
    b, s, _ = x.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(n_l):
        lp = jax.tree.map(lambda a: a[i], p["layers"])
        a = _rms_norm(x, lp["ln1"], eps)
        q = _rope(mm(a, lp["attn"]["wq"]).reshape(b, s, h, hd),
                  c["rope_theta"])
        k = _rope(mm(a, lp["attn"]["wk"]).reshape(b, s, kv, hd),
                  c["rope_theta"])
        v = mm(a, lp["attn"]["wv"]).reshape(b, s, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k)) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", rnd(pr), rnd(v))
        x = x + mm(o.reshape(b, s, h * hd), lp["attn"]["wo"])
        a = _rms_norm(x, lp["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(a, lp["ffn"]["w1"]))
                   * mm(a, lp["ffn"]["w3"]), lp["ffn"]["w2"])
    x = _rms_norm(x, p["final_norm"], eps)
    logits = mm(x, p["lm_head"][:, :c["vocab_size"]])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return jnp.sum(nll * mask)


class Reference:
    """Three (or more) train steps of the configuration from the seed.

    ``run(key, batches)`` returns the readings the comparison uses: the
    loss of each step, the per-leaf norm of the first moment after step 1
    (``(1 - b1)`` times the clipped gradient the optimizer got), and the
    per-leaf norm of the parameters' change over all the steps."""

    def __init__(self, c: Dict[str, Any], rounding: str = "f32",
                 half_batch: bool = False):
        self.c = c
        self.rnd = ROUNDINGS[rounding]
        # a planted fault: the step sees only the first half of the rows
        self.half_batch = half_batch
        o = c["optimizer"]
        self.pdt = jnp.dtype(c["torch_dtype"])
        self.sdt = jnp.dtype(o["state_dtype"])

        def acc_fn(acc, p, tokens, labels, mask):
            f = lambda pf: nll_sum(c, self.rnd, pf, tokens, labels, mask)
            val, g = jax.value_and_grad(f)(p)
            return jax.tree.map(jnp.add, acc, g), val

        def adamw(p, acc, m, v, t, count):
            g = jax.tree.map(lambda x: x / count, acc)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, o["clip_norm"]
                                / jnp.maximum(gnorm, 1e-9))

            def leaf(p, g, m, v):
                g = g * scale
                m = o["b1"] * m.astype(jnp.float32) + (1 - o["b1"]) * g
                v = o["b2"] * v.astype(jnp.float32) + (1 - o["b2"]) * g * g
                mhat = m / (1 - o["b1"] ** t)
                vhat = v / (1 - o["b2"] ** t)
                p = p - o["lr"] * (mhat / (jnp.sqrt(vhat) + o["eps"])
                                   + o["weight_decay"] * p)
                # stored as the configuration states, computed in float32
                return (p.astype(self.pdt).astype(jnp.float32),
                        m.astype(self.sdt), v.astype(self.sdt))

            out = jax.tree.map(leaf, p, g, m, v)
            pick = lambda i: jax.tree.map(
                lambda t_: t_[i], out, is_leaf=lambda x: isinstance(x, tuple))
            return pick(0), pick(1), pick(2)

        self._acc = jax.jit(acc_fn, donate_argnums=0)
        self._adamw = jax.jit(adamw, donate_argnums=(0, 1, 2, 3))
        self.weights = Weights(c)
        self._f32 = jax.jit(lambda key: jax.tree.map(
            lambda x: x.astype(jnp.float32), init_params(c, key)))

    def run(self, key, batches: List[Dict[str, np.ndarray]]
            ) -> Dict[str, Any]:
        rows = self.c["reference_rows_per_block"]
        with jax.default_matmul_precision("highest"):
            p = self._f32(key)
            m = jax.tree.map(lambda x: jnp.zeros(x.shape, self.sdt), p)
            v = jax.tree.map(lambda x: jnp.zeros(x.shape, self.sdt), p)
            losses, first_moment = [], None
            for t, batch in enumerate(batches, 1):
                n = batch["tokens"].shape[0]
                if self.half_batch:
                    n //= 2
                acc = jax.tree.map(jnp.zeros_like, p)
                total = 0.0
                for r in range(0, n, rows):
                    blk = [jnp.asarray(batch[k][r:min(r + rows, n)])
                           for k in ("tokens", "labels", "mask")]
                    acc, val = self._acc(acc, p, *blk)
                    total += float(val)
                count = max(float(batch["mask"][:n].sum()), 1.0)
                losses.append(total / count)
                p, m, v = self._adamw(p, acc, m, v, jnp.float32(t),
                                      jnp.float32(count))
                if t == 1:
                    first_moment = leaf_norms(m)
            del m, v
            change = self.weights.change_norms(key, p)
        return {"loss": losses, "first_moment": first_moment,
                "change": change}
