"""Plain reference: a dense decoder LM trained by local SGD, in float32.

Written from the published descriptions, not from the program, and
importing nothing of it:

- the model (the LLaMA-architecture decoder, as SmolLM2 uses it): token
  embedding; per layer a pre-norm residual block of RMSNorm, causal
  grouped-query attention with rotary position embeddings (the
  "rotate half" form over the whole head) and a pre-norm SwiGLU feed
  forward; a final RMSNorm; logits from the tied embedding (or a head);
  the loss is the mean next-token cross-entropy over every position;
- the optimizer (the paper's local SGD, arXiv:1808.07217, Alg. 1 with
  local momentum, App. B.4.1): the gradient is clipped by its global
  norm, weight decay is added to every matrix (not to norm scales),
  Nesterov momentum, a constant learning rate of ``base_lr`` times the
  global batch over ``base_batch``;
- the sync: after every ``local_steps`` steps each worker's parameters
  become the mean over workers; momentum stays local.

Initial weights are made from the seed by the initialisation rule the
configuration's model states, so the reference starts where the
program does without taking anything the program made: the seed's
``jax.random.PRNGKey`` is split into one key per weight, in the order
of ``init_order``; matrices are normal with standard deviation
1/sqrt(fan-in) (the fan-in is the second-to-last dimension), the
embedding normal with standard deviation 0.02, and norm scales are
ones. Where the configuration's ``stacked_layers`` is true each layer
weight is drawn for all layers in one call; where it is false, layer
by layer, each with a key of its own. Either way the reference holds
layer weights stacked over layers along a leading axis.

Matrix products run at ``Precision.HIGHEST`` in float32. With
``dtype=bfloat16`` everything (weights, activations, gradients and the
optimizer state) is held in bfloat16 at default precision: that is the
control, which the comparison has to refuse.

Work is cut so that it fits one chip: gradients are accumulated over
blocks of rows, every layer and every block of queries is recomputed in
the backward pass, and attention visits only the keys a block of
queries can see.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.traffic import worker_rows

LAYER_KEYS = ("wd", "wg", "wu", "ln1", "ln2", "wk", "wo", "wq", "wv")
NORM_KEYS = ("final_norm", "ln1", "ln2")


def init_order(cfg: dict) -> tuple[tuple[str, int | None], ...]:
    """(weight, layer) in the order the seed's keys are dealt: layer None
    for a weight outside the layers or stacked over them."""
    head = () if cfg["tie_word_embeddings"] else ("head",)
    top = tuple((k, None) for k in ("embed", "final_norm") + head)
    if cfg["stacked_layers"]:
        return top + tuple((k, None) for k in LAYER_KEYS)
    return top + tuple((k, i) for i in range(cfg["num_hidden_layers"])
                       for k in LAYER_KEYS)


def shapes(cfg: dict) -> dict:
    L, E = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KH, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    return {"embed": (V, E), "final_norm": (E,), "head": (E, V),
            "wq": (L, E, H * D), "wk": (L, E, KH * D), "wv": (L, E, KH * D),
            "wo": (L, H * D, E), "wg": (L, E, F), "wu": (L, E, F),
            "wd": (L, F, E), "ln1": (L, E), "ln2": (L, E)}


def make_init(cfg: dict):
    """(PRNGKey) -> float32 initial weights (see the module docstring).

    Each weight is drawn and scaled by its own operations, not fused
    into one program, so the draws round as the rule's plain steps do."""
    shp = shapes(cfg)
    order = init_order(cfg)

    def draw(k, shape, key):
        if k in NORM_KEYS:
            return jnp.ones(shape, jnp.float32)
        if k == "embed":
            return jax.random.normal(key, shape, jnp.float32) * 0.02
        std = 1.0 / math.sqrt(shape[-2])
        return jax.random.normal(key, shape, jnp.float32) * std

    def make(key):
        keys = jax.random.split(key, len(order))
        parts: dict = {}
        for (k, layer), kk in zip(order, keys):
            shape = shp[k] if layer is None else shp[k][1:]
            parts.setdefault(k, []).append(draw(k, shape, kk))
        return {k: v[0] if len(v) == 1 else jnp.stack(v)
                for k, v in parts.items()}
    return make


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """Rotary embedding over the whole head, "rotate half" form."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_loss(cfg: dict, *, dtype, q_block: int = 1024):
    """sum of next-token cross-entropies of a (rows, seq) block."""
    H, KH, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    mm = partial(jnp.einsum, precision=prec)

    def attend(q, k, v, q0):
        # q: (b, qb, H, D) at positions q0.. ; k, v: (b, kn, H, D)
        s = mm("bqhd,bkhd->bhqk", q, k) / jnp.asarray(math.sqrt(D), q.dtype)
        qpos = q0 + jnp.arange(q.shape[1])
        kpos = jnp.arange(k.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s,
                      jnp.asarray(-jnp.inf, s.dtype))
        a = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", a, v)

    def layer(x, w):
        b, S, E = x.shape
        pos = jnp.arange(S)
        h = _rms_norm(x, w["ln1"], eps)
        q = _rope(mm("bse,ef->bsf", h, w["wq"]).reshape(b, S, H, D), pos,
                  theta)
        k = _rope(mm("bse,ef->bsf", h, w["wk"]).reshape(b, S, KH, D), pos,
                  theta)
        v = mm("bse,ef->bsf", h, w["wv"]).reshape(b, S, KH, D)
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
        qb = min(q_block, S)
        outs = []
        for i in range(0, S, qb):
            end = i + qb
            outs.append(jax.checkpoint(attend, static_argnums=(3,))(
                q[:, i:end], k[:, :end], v[:, :end], i))
        o = jnp.concatenate(outs, axis=1).reshape(b, S, H * D)
        x = x + mm("bsf,fe->bse", o, w["wo"])
        h = _rms_norm(x, w["ln2"], eps)
        g = mm("bse,ef->bsf", h, w["wg"])
        u = mm("bse,ef->bsf", h, w["wu"])
        x = x + mm("bsf,fe->bse", jax.nn.silu(g) * u, w["wd"])
        return x, None

    def loss(p, tokens, labels):
        x = p["embed"][tokens]
        layers = {k: p[k] for k in LAYER_KEYS}
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, layers)
        x = _rms_norm(x, p["final_norm"], eps)
        head = p["embed"].T if cfg["tie_word_embeddings"] else p["head"]
        logits = mm("bse,ev->bsv", x, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold).astype(jnp.float32))

    return loss


def make_grad(cfg: dict, *, dtype, rows_per_block: int):
    """jit (p, tokens (B, S), labels) -> (mean loss, gradient of it)."""
    loss = make_loss(cfg, dtype=dtype)

    @jax.jit
    def grad(p, tokens, labels):
        B, S = tokens.shape
        rb = math.gcd(B, rows_per_block)
        n = B * S
        if rb == B:
            lsum, g = jax.value_and_grad(loss)(p, tokens, labels)
        else:
            def body(acc, xs):
                lsum, g = jax.value_and_grad(loss)(p, *xs)
                return (acc[0] + lsum,
                        jax.tree.map(lambda a, b: a + b.astype(a.dtype),
                                     acc[1], g)), None

            zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, p))
            (lsum, g), _ = jax.lax.scan(
                body, zero, (tokens.reshape(B // rb, rb, S),
                             labels.reshape(B // rb, rb, S)))
        return lsum / n, jax.tree.map(lambda a: a / jnp.asarray(n, a.dtype), g)
    return grad


def make_sgd(optim: dict, lr: float, *, no_decay=NORM_KEYS):
    """jit (p, u, g) -> (p, u): one step; weights named in ``no_decay``
    are not decayed."""
    m, wd, clip = optim["momentum"], optim["weight_decay"], optim["grad_clip"]
    nesterov = optim["nesterov"]

    @jax.jit
    def sgd(p, u, g):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                          for x in jax.tree.leaves(g)))
        scale = (jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)) if clip
                 else jnp.float32(1.0))
        p2, u2 = {}, {}
        for k in p:
            gf = g[k] * scale.astype(g[k].dtype)
            if wd and k not in no_decay:
                gf = gf + jnp.asarray(wd, p[k].dtype) * p[k]
            u2[k] = jnp.asarray(m, u[k].dtype) * u[k] + gf
            step = (jnp.asarray(m, u[k].dtype) * u2[k] + gf if nesterov
                    else u2[k])
            p2[k] = p[k] - jnp.asarray(lr, p[k].dtype) * step
        return p2, u2
    return sgd


def norm(x, keep: int):
    """L2 norm over all but the first ``keep`` axes, in float32."""
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(keep, x.ndim))))


@jax.jit
def _norms(t, b):
    return {k: norm(x if b is None
                    else x.astype(jnp.float32) - b[k].astype(jnp.float32),
                    1 if k in LAYER_KEYS else 0) for k, x in t.items()}


def leaf_norms(tree: dict, base: dict | None = None) -> dict:
    """L2 norm of every weight, per layer for layer weights: name ->
    float or (layers,) array.  With ``base``, of ``tree - base``."""
    return {k: np.asarray(v) for k, v in _norms(tree, base).items()}


class Reference:
    """The reference for one configuration and traffic mix, its programs
    built once and reused for every seed."""

    FAULTS = (None, "half_batch", "no_sync")

    def __init__(self, cfg: dict, traffic: dict, devices, *,
                 dtype=jnp.float32, fault: str | None = None):
        """``fault`` plants one of the faults the comparison must catch,
        for reading its numbers: ``half_batch`` learns from half of each
        worker's batch (the first half of its rows, or of its positions
        where a worker has one row), ``no_sync`` leaves out the mean."""
        if fault not in self.FAULTS:
            raise ValueError(f"fault {fault!r}")
        self.cfg, self.traffic, self.dtype = cfg, traffic, dtype
        self.fault = fault
        W, B, S = (traffic["workers"], traffic["local_batch"],
                   traffic["seq_len"])
        optim = traffic["optim"]
        self.lr = optim["base_lr"] * (W * B) / optim.get("base_batch", W * B)
        self.init = make_init(cfg)
        self.grad = make_grad(cfg, dtype=dtype,
                              rows_per_block=max(1, 4096 // S))
        self.sgd = make_sgd(optim, self.lr)
        self.devs = [devices[w % len(devices)] for w in range(W)]

    def train(self, tokens: np.ndarray, *, seed: int, steps: int) -> dict:
        """Follow the first ``steps`` steps of every worker.

        Returns per-step losses (mean over workers), each worker's
        momentum norms after step 1 and parameter-change norms after
        ``steps``.  Worker ``w`` runs on ``devices[w % len(devices)]``.
        """
        W, B = self.traffic["workers"], self.traffic["local_batch"]
        H = self.traffic["local_steps"]
        devs, dtype = self.devs, self.dtype
        rows = worker_rows(len(tokens), W, B, steps, seed=seed)
        key = jax.random.PRNGKey(seed)
        cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)
        ps = [jax.device_put(cast(self.init(key)), d) for d in devs]
        us = [jax.tree.map(jnp.zeros_like, p) for p in ps]
        losses, u1 = [], None
        for t in range(steps):
            outs = []
            for w in range(W):
                r = rows[t, w]
                tok, lab = tokens[r, :-1], tokens[r, 1:]
                if self.fault == "half_batch":
                    if B > 1:
                        tok, lab = tok[:B // 2], lab[:B // 2]
                    else:
                        half = tok.shape[1] // 2
                        tok, lab = tok[:, :half], lab[:, :half]
                tok = jax.device_put(tok, devs[w])
                lab = jax.device_put(lab, devs[w])
                outs.append(self.grad(ps[w], tok, lab))
            for w, (_, g) in enumerate(outs):
                ps[w], us[w] = self.sgd(ps[w], us[w], g)
            losses.append(float(np.mean([float(l) for l, _ in outs])))
            del outs
            if t == 0:
                u1 = [leaf_norms(u) for u in us]
            if (t + 1) % H == 0 and self.fault != "no_sync":
                home = devs[0]
                acc = jax.tree.map(lambda x: x.astype(jnp.float32), ps[0])
                for w in range(1, W):
                    acc = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), acc,
                        jax.device_put(ps[w], home))
                mean = cast(jax.tree.map(lambda a: a / W, acc))
                del acc
                ps = [jax.device_put(mean, d) for d in devs]
        del us
        p0 = self.init(key)      # made again, not held through training
        change = [leaf_norms(p, jax.device_put(p0, d))
                  for p, d in zip(ps, devs)]
        return {"losses": losses,
                "grad_norms": {k: np.stack([n[k] for n in u1])
                               for k in u1[0]},
                "change_norms": {k: np.stack([n[k] for n in change])
                                 for k in change[0]}}
