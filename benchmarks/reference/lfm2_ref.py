"""Plain float32 reference of the training step of a model whose layers
are gated short convolutions and grouped-query attention, with a dense
FFN first and routed expert FFNs after it (LFM2-MoE's layer equations,
PERF.md section 4): next-token cross entropy over a tied head, AdamW.

Straightforward `jax.numpy` at `highest` matmul precision: no kernels, no
bf16, no program code, no program arrays. Its own weights from the seed
(`benchmarks/weights_hybrid.py`), the cell's first steps on the same
token batches, layer by layer (a `jax.vjp` per layer) over blocks of
rows, AdamW on a layer as soon as its gradient is whole: the walk of
`llama_ref.py`, whose norm, RoPE, attention, AdamW and control
arithmetic it shares. One device.

The equations, `u` the normed input of a mixer or an FFN:

  layer       h = x + mixer(norm_op(x));  y = h + ffn(norm_ffn(h))
  convolution [B, C, z] = split3(u @ W_in); g = B * z;
              c[t] = sum_j w[:, j] * g[t - (K-1) + j], g zero before the
              sequence; mixer = (C * c) @ W_out
  attention   q, k, v projections; RMSNorm over each head of q and of k;
              RoPE (half-split); causal softmax attention, key/value
              heads shared by groups of query heads; output projection
  dense FFN   (silu(u @ W1) * (u @ W3)) @ W2
  expert FFN  s = sigmoid(u @ W_r) over all router outputs;
              sel = top-k(s + bias); g_i = s_i / (sum_{j in sel} s_j +
              1e-6) * routed_scaling_factor for i in sel;
              ffn = sum_{i in sel and held here} g_i * expert_i(u).
              The bias enters the selection alone and takes no gradient;
              the sum in the denominator runs over all k choices, held or
              not; what the absent experts would add is left out.
  model       embedding lookup, the layers, a final RMSNorm, the
              embedding's transpose as the head. No auxiliary loss.

`mode="int8"` and `mode="fp8"` are `llama_ref`'s controls: both operands
of every bf16 weight matmul rounded; the router, which the configuration
states in float32, keeps its precision. `fault="half_batch"` plants a
wrong step.

Each full step also counts, in every expert layer, the (token, choice)
pairs whose choice changes when the router's input alone is rounded to
bfloat16 (`route_flip_share`): an estimate from below of how often the
program's top-k, taken on bfloat16 activations, differs from this one.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops, weights_hybrid
from benchmarks.reference.llama_ref import (HIGHEST, adamw_update, attention,
                                            make_mm, rms_norm, rope)

ROUTER_NORM_EPS = 1e-6
ROUTE_LEAVES = ("router", "router_bias")  # what `route` reads of a layer


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def short_conv(u, p, mm):
    """Gated short convolution over u [r, t, d]."""
    t = u.shape[1]
    b_, c_, z = jnp.split(mm(u, p["conv_in"]), 3, axis=-1)
    g = b_ * z
    taps = p["conv_w"].shape[1]
    gp = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(gp[:, j:j + t] * p["conv_w"][:, j] for j in range(taps))
    return mm(c_ * c, p["conv_out"])


def gqa(u, p, cfg: Dict, mm):
    r, t, _ = u.shape
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], flops.head_dim(cfg)
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    heads = lambda x, n: x.reshape(r, t, n, hd).transpose(0, 2, 1, 3)
    q = rms_norm(heads(mm(u, p["wq"]), nq), p["q_norm"], eps)
    k = rms_norm(heads(mm(u, p["wk"]), nkv), p["k_norm"], eps)
    v = heads(mm(u, p["wv"]), nkv)
    a = attention(rope(q, theta), rope(k, theta), v, None)
    return mm(a.transpose(0, 2, 1, 3).reshape(r, t, nq * hd), p["wo"])


def route(u, router, bias, k: int):
    """0/1 plane [.., outputs] of the k outputs chosen for each token and
    every output's score."""
    s = jax.nn.sigmoid(jnp.matmul(u, router, precision=HIGHEST))
    _, sel = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    return jnp.sum(jax.nn.one_hot(sel, s.shape[-1], dtype=s.dtype), axis=-2), s


def expert_ffn(u, p, cfg: Dict, mm):
    """(the held experts' part of the routed FFN, choices that flip under
    a bfloat16 input)."""
    k, first = cfg["num_experts_per_tok"], cfg.get("first_expert", 0)
    chosen, s = route(u, p["router"], p["router_bias"], k)
    picked = s * chosen
    g = picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    g = g * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(u)
    for i in range(p["w1"].shape[0]):
        out = mm(jax.nn.silu(mm(u, p["w1"][i])) * mm(u, p["w3"][i]), p["w2"][i])
        y = y + g[..., first + i, None] * out
    rounded = u.astype(jnp.bfloat16).astype(jnp.float32)
    chosen_bf16, _ = route(rounded, p["router"], p["router_bias"], k)
    flips = jnp.sum(chosen * (1.0 - chosen_bf16))
    return y, jax.lax.stop_gradient(flips)


def layer_fwd(x, p, cfg: Dict, mm):
    """One layer over a block of rows, x [r, t, d]: (y, flipped choices)."""
    eps = cfg["norm_eps"]
    if "conv_in" in p:
        x = x + short_conv(rms_norm(x, p["conv_norm"], eps), p, mm)
    else:
        x = x + gqa(rms_norm(x, p["attn_norm"], eps), p, cfg, mm)
    u = rms_norm(x, p["mlp_norm"], eps)
    if "moe" in p:
        y, flips = expert_ffn(u, p["moe"], cfg, mm)
        return x + y, flips
    y = mm(jax.nn.silu(mm(u, p["w1"])) * mm(u, p["w3"]), p["w2"])
    return x + y, jnp.zeros((), jnp.float32)


def head_nll(x, final_norm, embed, targets, cfg: Dict, mm):
    """Summed next-token negative log likelihood of a block of rows, the
    head the embedding's transpose."""
    logits = mm(rms_norm(x, final_norm, cfg["norm_eps"]), embed.T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(params, tokens, cfg: Dict, mm=None):
    """The whole model's mean next-token loss in one piece, for sizes at
    which everything fits at once (the tests): what the walk below
    computes block by block."""
    mm = mm or make_mm("f32")
    x = params["embed"][tokens[:, :-1]]
    for p in params["layers"]:
        x, _ = layer_fwd(x, p, cfg, mm)
    nll = head_nll(x, params["final_norm"], params["embed"], tokens[:, 1:], cfg, mm)
    return nll / tokens[:, 1:].size


# ---------------------------------------------------------------------------
# the walk: layer by layer, block of rows by block of rows
# ---------------------------------------------------------------------------


class Reference:
    """Follows a cell's first steps from the seed, as
    `llama_ref.Reference` does: `run(batches, n)` returns every loss, the
    per-leaf norms of the first gradient and of the parameters' change
    over the n steps, `route_flip_share`, and
    `selection_leaves`: the leaves `route` reads, whose value decides a
    top-k choice and whose gradient jumps where a choice flips."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int, devices,
                 mode: str = "f32", fault: Optional[str] = None):
        if not cfg.get("tie_word_embeddings"):
            raise ValueError("this reference ties the head to the embedding")
        if fault not in (None, "half_batch"):
            raise ValueError(f"fault {fault!r} is not planted here (half_batch)")
        self.cfg, self.cell, self.seed, self.fault = cfg, cell, seed, fault
        self.opt = cell["optimizer"]
        self.mm = make_mm(mode)
        self.device = list(devices)[0]
        self.block = int(cell["reference"]["row_block"])
        self.make_weights = weights_hybrid.maker(cfg)
        with jax.default_device(self.device):
            self.params = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t))(self.make_weights(seed))
        # gradients of earlier steps, for AdamW's moments: on the host
        # where parameters and a copy of the gradients would crowd a chip
        self.history_on_host = 2 * 4 * sum(
            int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(self.params)) > 5e9
        self.history: List[Dict] = []
        self.flips = self.pairs = 0.0
        self._jits()

    def _jits(self):
        cfg, mm, opt = self.cfg, self.mm, self.opt
        tmap = jax.tree_util.tree_map
        self._embed = jax.jit(lambda table, ids: table[ids])
        self._layer = jax.jit(lambda x, p: layer_fwd(x, p, cfg, mm))

        def layer_back(x, p, dy):
            _, vjp, _ = jax.vjp(
                lambda x_, p_: layer_fwd(x_, p_, cfg, mm), x, p, has_aux=True)
            return vjp(dy)

        self._layer_back = jax.jit(layer_back)
        self._head_back = jax.jit(lambda x, fn, emb, targets: jax.value_and_grad(
            lambda x_, f_, e_: head_nll(x_, f_, e_, targets, cfg, mm),
            argnums=(0, 1, 2))(x, fn, emb))
        self._head = jax.jit(
            lambda x, fn, emb, targets: head_nll(x, fn, emb, targets, cfg, mm))
        self._embed_back = jax.jit(
            lambda ids, dx, like: jnp.zeros_like(like).at[ids].add(dx))
        self._add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0,))
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

    def _put(self, arr):
        return jax.device_put(arr, self.device)

    def _blocks(self, tokens: np.ndarray):
        rows = tokens.shape[0]
        if self.fault == "half_batch":
            rows = max(rows // 2, 1)
        blk = min(self.block, rows)
        return [(lo, min(lo + blk, rows)) for lo in range(0, rows, blk)], rows

    def _forward(self, tokens: np.ndarray, keep: bool):
        """Per block of rows: token ids, every layer's input (kept only
        for a full step) and the final activations."""
        blocks, rows = self._blocks(tokens)
        acts = []
        for lo, hi in blocks:
            ids = self._put(tokens[lo:hi, :-1])
            x = self._embed(self.params["embed"], ids)
            inputs = []
            for p in self.params["layers"]:
                if keep:
                    inputs.append(x)
                x, flips = self._layer(x, p)
                if keep and "moe" in p:
                    self.flips += float(flips)
                    self.pairs += float(ids.size * self.cfg["num_experts_per_tok"])
            acts.append([ids, inputs, x])
        return blocks, rows, acts

    def loss_only(self, tokens: np.ndarray) -> float:
        blocks, rows, acts = self._forward(tokens, keep=False)
        nll = 0.0
        for (lo, hi), (_, _, x) in zip(blocks, acts):
            nll += float(self._head(
                x, self.params["final_norm"], self.params["embed"],
                self._put(tokens[lo:hi, 1:])))
        return nll / (rows * (tokens.shape[1] - 1))

    def _acc(self, acc, g):
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
            past = [self._put(h) for h in past]
        where[key] = self._adam(where[key], past + [g])
        kept = jax.device_get(g) if self.history_on_host else g
        if index is None:
            grads_now[name], grad_sq[name] = kept, sq
        else:
            grads_now["layers"][index], grad_sq["layers"][index] = kept, sq

    def full_step(self, tokens: np.ndarray) -> Dict:
        """Loss and gradient of one batch, then AdamW on every leaf. A
        layer is updated as soon as its gradient is whole; the embedding,
        which is also the head, last."""
        blocks, rows, acts = self._forward(tokens, keep=True)
        n_layers = len(self.params["layers"])
        inv = np.float32(1.0 / (rows * (tokens.shape[1] - 1)))
        grads_now = {"layers": [None] * n_layers}
        grad_sq = {"layers": [None] * n_layers}

        nll, dxs, g_fn, g_embed = 0.0, [], None, None
        for (lo, hi), act in zip(blocks, acts):
            n, (dx, dfn, demb) = self._head_back(
                act[2], self.params["final_norm"], self.params["embed"],
                self._put(tokens[lo:hi, 1:]))
            act[2] = None
            nll += float(n)
            dxs.append(dx)
            g_fn, g_embed = self._acc(g_fn, dfn), self._acc(g_embed, demb)
        self._settle(grads_now, grad_sq, "final_norm", g_fn, inv)
        del g_fn

        for i in reversed(range(n_layers)):
            g_layer = None
            for b, act in enumerate(acts):
                dxs[b], g = self._layer_back(
                    act[1][i], self.params["layers"][i], dxs[b])
                act[1][i] = None
                g_layer = self._acc(g_layer, g)
            self._settle(grads_now, grad_sq, "layers", g_layer, inv, index=i)
            del g_layer

        for b, act in enumerate(acts):
            g_embed = self._add(g_embed, self._embed_back(
                act[0], dxs[b], self.params["embed"]))
        self._settle(grads_now, grad_sq, "embed", g_embed, inv)
        self.history.append(grads_now)
        return {"loss": nll * float(inv), "grad_sq": jax.device_get(grad_sq)}

    # -- the readings -------------------------------------------------------

    def run(self, batches: List[np.ndarray], full_steps: int) -> Dict:
        root = lambda t: jax.tree_util.tree_map(lambda s: float(np.sqrt(s)), t)
        out = {"loss": [], "grad_norm": None, "change_norm": None}
        with jax.default_device(self.device):
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
        out["route_flip_share"] = self.flips / self.pairs if self.pairs else 0.0
        out["selection_leaves"] = sorted(
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(self.params)[0]
            if getattr(path[-1], "key", None) in ROUTE_LEAVES)
        return out
