"""Plain float32 reference of the training step: Mistral/Llama decoder,
next-token cross entropy, AdamW.

Straightforward `jax.numpy` at `highest` matmul precision: no kernels, no
bf16, no program code, no program arrays. It makes its own weights from
the seed (`benchmarks/weights.py`) and follows the cell's first steps on
the same token batches. So that it fits beside nothing else on the chip
it walks the model layer by layer (a `jax.vjp` per layer) over blocks of
rows, and applies AdamW to a layer as soon as its gradient is whole.

`mode="int8"` and `mode="fp8"` are controls: the same mathematics with
both operands of every weight matmul rounded to int8 or to float8 e4m3
(absmax scale per row of the activations and per output column of the
weights, straight-through gradient), the precisions below the bf16 that
the configuration states.

`fault` plants a wrong step for the harness's own tests and readings:
"half_batch" (the second half of the rows left out, the mean taken over
the rest), "no_exchange" (shard i of every gradient comes from rows of
shard i alone, as if the chips never exchanged gradients).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks import flops, weights

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def _fake_int8(x, axis):
    """Round to 127 levels of the absmax along `axis`; gradient passes
    straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x, axis):
    """Round to float8 e4m3 (3 bits of mantissa) with the absmax along
    `axis` scaled to the format's largest number; gradient passes
    straight through."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_mm(mode: str):
    if mode == "f32":
        return lambda a, w: jnp.matmul(a, w, precision=HIGHEST)
    if mode == "int8":
        return lambda a, w: jnp.matmul(
            _fake_int8(a, -1), _fake_int8(w, 0), precision=HIGHEST)
    if mode == "fp8":
        return lambda a, w: jnp.matmul(
            _fake_fp8(a, -1), _fake_fp8(w, 0), precision=HIGHEST)
    raise ValueError(f"unknown reference mode {mode!r} (f32, int8, fp8)")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """Rotary embedding over [rows, heads, t, head_dim], half-split form
    (HF `rotate_half`)."""
    t, hd = x.shape[-2], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v, window: Optional[int], q_block: int = 2048):
    """Causal (sliding-window) softmax attention with grouped KV heads.
    q [r, nq, t, hd], k/v [r, nkv, t, hd]. One KV group and one block of
    queries at a time, recomputed in the backward pass, so that the
    [t, t] scores of all heads never exist together."""
    r, nq, t, hd = q.shape
    nkv = k.shape[1]
    rep = nq // nkv
    qb = q_block if t % q_block == 0 else t
    nb = t // qb
    starts = jnp.arange(nb, dtype=jnp.int32) * qb

    def group(qg, kg, vg):  # [r, rep, t, hd], [r, t, hd], [r, t, hd]
        @jax.checkpoint
        def block(qblk, start):  # [r, rep, qb, hd]
            i = start + jnp.arange(qb, dtype=jnp.int32)[:, None]
            j = jnp.arange(t, dtype=jnp.int32)[None, :]
            mask = j <= i
            if window is not None:
                mask &= j > i - window
            s = jnp.einsum("rgqd,rkd->rgqk", qblk, kg, precision=HIGHEST) / np.sqrt(hd)
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return jnp.einsum("rgqk,rkd->rgqd", p, vg, precision=HIGHEST)

        blocks = qg.reshape(r, rep, nb, qb, hd).transpose(2, 0, 1, 3, 4)
        out = jax.lax.map(lambda a: block(*a), (blocks, starts))
        return out.transpose(1, 2, 0, 3, 4).reshape(r, rep, t, hd)

    qg = q.reshape(r, nkv, rep, t, hd).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: group(*a),
                      (qg, k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3)))
    return out.transpose(1, 0, 2, 3, 4).reshape(r, nq, t, hd)


def layer_fwd(x, p, cfg: Dict, mm):
    """One decoder layer over a block of rows, x [r, t, d]."""
    r, t, _ = x.shape
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], flops.head_dim(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = rms_norm(x, p["attn_norm"], eps)
    q = mm(h, p["wq"]).reshape(r, t, nq, hd).transpose(0, 2, 1, 3)
    k = mm(h, p["wk"]).reshape(r, t, nkv, hd).transpose(0, 2, 1, 3)
    v = mm(h, p["wv"]).reshape(r, t, nkv, hd).transpose(0, 2, 1, 3)
    a = attention(rope(q, theta), rope(k, theta), v, cfg.get("sliding_window"))
    x = x + mm(a.transpose(0, 2, 1, 3).reshape(r, t, nq * hd), p["wo"])
    h = rms_norm(x, p["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(h, p["w1"])) * mm(h, p["w3"]), p["w2"])


def head_nll(x, final_norm, head, targets, cfg: Dict, mm):
    """Summed next-token negative log likelihood of a block of rows."""
    logits = mm(rms_norm(x, final_norm, cfg["rms_norm_eps"]), head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def adamw_update(p, grads: List, opt: Dict):
    """The parameter after step t = len(grads), from the parameter before
    it and the gradients of steps 1..t (moments rebuilt from them)."""
    t = len(grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = sum((1 - b1) * b1 ** (t - 1 - k) * g for k, g in enumerate(grads))
    nu = sum((1 - b2) * b2 ** (t - 1 - k) * g * g for k, g in enumerate(grads))
    mu_hat = mu / (1 - b1 ** t)
    nu_hat = nu / (1 - b2 ** t)
    return p - opt["learning_rate"] * (
        mu_hat / (jnp.sqrt(nu_hat) + opt["eps"]) + opt["weight_decay"] * p)


# ---------------------------------------------------------------------------
# the walk: layer by layer, block of rows by block of rows
# ---------------------------------------------------------------------------


class Reference:
    """Follows a cell's first steps from the seed. `run(batches, n)` takes
    n full steps (loss, gradient, AdamW) on the first n batches and only
    the loss of the rest, and returns every loss, the per-leaf norms of
    the first gradient, and the per-leaf norms of the parameters' change
    over the n steps."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int, devices,
                 mode: str = "f32", fault: Optional[str] = None):
        self.cfg, self.cell, self.seed, self.fault = cfg, cell, seed, fault
        self.opt = cell["optimizer"]
        self.mm = make_mm(mode)
        self.n_dev = len(devices)
        self.mesh = Mesh(np.array(list(devices)), ("rows",))
        self.block = int(cell["reference"]["row_block"])
        # under "no_exchange" the gradient is cut into as many shards as
        # the cell has chips, each from its own rows
        self.n_shards = int(cell["chips"])
        self.shardings = jax.tree_util.tree_map(
            self._leaf_sharding, weights.leaf_shapes(cfg), is_leaf=weights.is_shape)
        self.make_weights = weights.maker(cfg, self.shardings)
        self.params = self._start_params()
        # gradients of earlier steps, for AdamW's moments: on the host
        # where parameters and a copy of the gradients would crowd a chip
        self.history_on_host = 2 * 4 * flops.total_params(cfg) / self.n_dev > 5e9
        self.history: List[Dict] = []
        self._jits()

    def _leaf_sharding(self, shape):
        if len(shape) == 2 and shape[0] % self.n_dev == 0:
            return NamedSharding(self.mesh, P("rows"))
        return NamedSharding(self.mesh, P())

    def _start_params(self):
        bf16 = self.make_weights(self.seed)
        return jax.jit(lambda t: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), t),
            out_shardings=self.shardings)(bf16)

    def _put_rows(self, arr):
        spec = P("rows") if arr.shape[0] % self.n_dev == 0 else P()
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _jits(self):
        cfg, mm, opt = self.cfg, self.mm, self.opt
        sh = self.shardings
        tmap = jax.tree_util.tree_map

        self._embed = jax.jit(lambda table, ids: table[ids])
        self._layer = jax.jit(lambda x, p: layer_fwd(x, p, cfg, mm))

        def layer_back(x, p, dy):
            _, vjp = jax.vjp(lambda x_, p_: layer_fwd(x_, p_, cfg, mm), x, p)
            return vjp(dy)

        self._layer_back = jax.jit(
            layer_back, out_shardings=(None, sh["layers"][0]))

        def head_back(x, fn, head, targets):
            return jax.value_and_grad(
                lambda x_, f_, h_: head_nll(x_, f_, h_, targets, cfg, mm),
                argnums=(0, 1, 2))(x, fn, head)

        self._head_back = jax.jit(head_back, out_shardings=(
            None, (None, sh["final_norm"], sh["lm_head"])))
        self._head = jax.jit(
            lambda x, fn, head, targets: head_nll(x, fn, head, targets, cfg, mm))
        self._embed_back = jax.jit(
            lambda ids, dx, like: jnp.zeros_like(like).at[ids].add(dx),
            out_shardings=sh["embed"])
        self._add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0,))

        def shard_of(g, b, scale):
            # rows of block b give shard b (along the first axis) of every
            # leaf and nothing else
            def one(x):
                n = x.shape[0]
                idx = jnp.arange(n) * self.n_shards // n
                keep = (idx == b).reshape((n,) + (1,) * (x.ndim - 1))
                return jnp.where(keep, x * scale, 0.0)
            return tmap(one, g)

        self._shard_of = jax.jit(shard_of, static_argnums=(1, 2))
        self._scale = jax.jit(lambda a, s: tmap(lambda g: g * s, a),
                              donate_argnums=(0,))
        self._sq = jax.jit(lambda t: tmap(lambda g: jnp.sum(jnp.square(g)), t))
        self._adam = jax.jit(
            lambda p, grads: tmap(
                lambda p_, *g: adamw_update(p_, list(g), opt), p, *grads),
            donate_argnums=(0,))
        self._diff_sq = jax.jit(lambda a, b: tmap(
            lambda x, y: jnp.sum(jnp.square(x - y.astype(jnp.float32))), a, b))

    # -- one step -----------------------------------------------------------

    def _blocks(self, tokens: np.ndarray):
        rows = tokens.shape[0]
        blk = self.block
        if self.fault == "half_batch":
            rows = max(rows // 2, 1)
        if self.fault == "no_exchange":
            blk = rows // self.n_shards
        blk = min(blk, rows)
        return [(lo, min(lo + blk, rows)) for lo in range(0, rows, blk)], rows

    def _forward(self, tokens: np.ndarray, keep: bool):
        """Per block of rows: token ids, every layer's input (kept only
        for a full step) and the final activations."""
        blocks, rows = self._blocks(tokens)
        acts = []
        for lo, hi in blocks:
            ids = self._put_rows(tokens[lo:hi, :-1])
            x = self._embed(self.params["embed"], ids)
            inputs = []
            for p in self.params["layers"]:
                if keep:
                    inputs.append(x)
                x = self._layer(x, p)
            acts.append([ids, inputs, x])
        return blocks, rows, acts

    def loss_only(self, tokens: np.ndarray) -> float:
        blocks, rows, acts = self._forward(tokens, keep=False)
        nll = 0.0
        for (lo, hi), (_, _, x) in zip(blocks, acts):
            nll += float(self._head(
                x, self.params["final_norm"], self.params["lm_head"],
                self._put_rows(tokens[lo:hi, 1:])))
        return nll / (rows * (tokens.shape[1] - 1))

    def _acc(self, acc, g, b: int):
        if self.fault == "no_exchange":
            g = self._shard_of(g, b, float(self.n_shards))
        return g if acc is None else self._add(acc, g)

    def _settle(self, grads_now: Dict, grad_sq: Dict, name: str, g, inv, index=None):
        """A leaf group's gradient is whole: norm it, apply AdamW, keep it
        for the next step's moments."""
        g = self._scale(g, inv)
        sq = self._sq(g)
        where = self.params if index is None else self.params["layers"]
        key = name if index is None else index
        past = [h[name] if index is None else h["layers"][index]
                for h in self.history]
        if self.history_on_host:
            sharding = self.shardings[name] if index is None else self.shardings["layers"][0]
            past = [jax.device_put(h, sharding) for h in past]
        where[key] = self._adam(where[key], past + [g])
        kept = jax.device_get(g) if self.history_on_host else g
        if index is None:
            grads_now[name], grad_sq[name] = kept, sq
        else:
            grads_now["layers"][index], grad_sq["layers"][index] = kept, sq

    def full_step(self, tokens: np.ndarray) -> Dict:
        """Loss and gradient of one batch, then AdamW on every leaf. A
        layer is updated as soon as its gradient is whole: the layers
        below it no longer need its parameters."""
        blocks, rows, acts = self._forward(tokens, keep=True)
        n_layers = len(self.params["layers"])
        inv = np.float32(1.0 / (rows * (tokens.shape[1] - 1)))
        grads_now = {"layers": [None] * n_layers}
        grad_sq = {"layers": [None] * n_layers}

        nll, dxs, g_fn, g_head = 0.0, [], None, None
        for b, ((lo, hi), act) in enumerate(zip(blocks, acts)):
            n, (dx, dfn, dhead) = self._head_back(
                act[2], self.params["final_norm"], self.params["lm_head"],
                self._put_rows(tokens[lo:hi, 1:]))
            act[2] = None
            nll += float(n)
            dxs.append(dx)
            g_fn, g_head = self._acc(g_fn, dfn, b), self._acc(g_head, dhead, b)
        self._settle(grads_now, grad_sq, "final_norm", g_fn, inv)
        self._settle(grads_now, grad_sq, "lm_head", g_head, inv)
        del g_fn, g_head

        for i in reversed(range(n_layers)):
            g_layer = None
            for b, act in enumerate(acts):
                dxs[b], g = self._layer_back(
                    act[1][i], self.params["layers"][i], dxs[b])
                act[1][i] = None
                g_layer = self._acc(g_layer, g, b)
            self._settle(grads_now, grad_sq, "layers", g_layer, inv, index=i)
            del g_layer

        g_embed = None
        for b, act in enumerate(acts):
            g_embed = self._acc(g_embed, self._embed_back(
                act[0], dxs[b], self.params["embed"]), b)
        self._settle(grads_now, grad_sq, "embed", g_embed, inv)
        self.history.append(grads_now)
        return {"loss": nll * float(inv), "grad_sq": jax.device_get(grad_sq)}

    # -- the readings -------------------------------------------------------

    def run(self, batches: List[np.ndarray], full_steps: int) -> Dict:
        root = lambda t: jax.tree_util.tree_map(lambda s: float(np.sqrt(s)), t)
        out = {"loss": [], "grad_norm": None, "change_norm": None}
        for k, tokens in enumerate(batches):
            if k >= full_steps:
                out["loss"].append(self.loss_only(tokens))
                continue
            r = self.full_step(tokens)
            out["loss"].append(r["loss"])
            if k == 0:
                out["grad_norm"] = root(r["grad_sq"])
            if k == full_steps - 1:
                self.history = []
                start = self.make_weights(self.seed)
                out["change_norm"] = root(jax.device_get(
                    self._diff_sq(self.params, start)))
        return out
