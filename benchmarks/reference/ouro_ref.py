"""Plain float32 reference of the training step of a looped decoder
(Ouro / LoopLM, arXiv:2510.25741): a stack of N layers applied T times
over the same weights, a head and an exit gate after every pass, the
expected loss over the gate's exit distribution, AdamW.

Straightforward `jax.numpy` at `highest` matmul precision: no kernels, no
bf16, no program code, no program arrays. Its own weights from the seed
(`benchmarks/weights_looped.py`), the cell's first steps on the same
token batches. So that it fits at the cell's size it walks one block of
rows (one sequence) at a time through every pass, keeps each layer
application's input, computes one pass's logits at a time (twice: once
for the losses the objective mixes, once for their gradient), and takes
the backward pass application by application (a `jax.vjp` each), summing
a weight's gradient over its T uses. Norm, RoPE, attention, AdamW and the
control arithmetic are `llama_ref.py`'s. One device.

The equations (S tokens, d hidden, N layers, T passes):

  layer l     a = x + RMSNorm_2l(Attn_l(RMSNorm_1l(x)))
              y = a + RMSNorm_4l(SwiGLU_l(RMSNorm_3l(a)))
              Attn: q, k, v = h W_q, h W_k, h W_v in heads of head_dim,
              RoPE (half-split) on q and k, causal
              softmax(q k^T / sqrt(head_dim)) v, then W_o.
              SwiGLU(h) = (silu(h W_1) * h W_3) W_2
  model       h_0 = E[tokens]; for t = 1..T:
              u_t = layer_N(... layer_1(h_{t-1})), h_t = RMSNorm_f(u_t);
              logits_t = h_t W_head; lambda_t = sigmoid(h_t w_g + b_g)
  exit        p_1 = lambda_1; p_t = lambda_t prod_{j<t}(1 - lambda_j) for
              1 < t < T; p_T = prod_{j<T}(1 - lambda_j); sum_t p_t = 1
  loss        mean over tokens of [sum_t p_t CE_t - beta H(p)], CE_t the
              next-token cross entropy of logits_t, H(p) = -sum_t p_t log p_t

What config.json does not give and this file assumes (the configuration
file's `assumed` has the same list, with where each comes from): four
norms a layer (the family's input_layernorm, input_layernorm_2,
post_attention_layernorm, post_attention_layernorm_2: here attn_norm,
post_attn_norm, mlp_norm, post_mlp_norm); the final norm inside the
loop, one set of weights, its output feeding the next pass, the head and
the gate; the gate a Linear(d, 1) with bias; the paper's stage-I
objective with a uniform prior over exit steps and beta = 0.05; the last
pass's gate decides nothing. Each is a departure to note, not a thing to
leave out.

`mode="int8"` and `mode="fp8"` are `llama_ref`'s controls: both operands
of every weight matmul rounded (the gate's among them).
`fault="half_batch"` plants a wrong step; `passes=3` another: a pass left
out (the stack run three times, the third pass's state read as the last).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops, weights_looped
from benchmarks.reference.llama_ref import (adamw_update, attention, make_mm,
                                            rms_norm, rope)

# tokens of a sequence whose logits exist together in the walk
HEAD_CHUNK = 2048

# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def layer_fwd(x, p, cfg: Dict, mm):
    """One layer over a block of rows, x [r, t, d]: a norm before and a
    norm after each of its two branches."""
    r, t, _ = x.shape
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], flops.head_dim(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads = lambda y, n: y.reshape(r, t, n, hd).transpose(0, 2, 1, 3)
    h = rms_norm(x, p["attn_norm"], eps)
    q, k, v = (heads(mm(h, p[w]), n) for w, n in (("wq", nq), ("wk", nkv), ("wv", nkv)))
    a = attention(rope(q, theta), rope(k, theta), v, None)
    a = mm(a.transpose(0, 2, 1, 3).reshape(r, t, nq * hd), p["wo"])
    x = x + rms_norm(a, p["post_attn_norm"], eps)
    h = rms_norm(x, p["mlp_norm"], eps)
    y = mm(jax.nn.silu(mm(h, p["w1"])) * mm(h, p["w3"]), p["w2"])
    return x + rms_norm(y, p["post_mlp_norm"], eps)


def head_and_gate(h, head, gate, targets, mm):
    """Each token's cross entropy and gate logit from a pass's normed
    state h [r, t, d]."""
    logp = jax.nn.log_softmax(mm(h, head), axis=-1)
    ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return ce, mm(h, gate["w"])[..., 0] + gate["b"][0]


def exit_distribution(z):
    """p [T, ...] from the gates' logits z [T, ...]; the last pass takes
    what is left."""
    lam = jax.nn.sigmoid(z)
    left = jnp.cumprod(1.0 - lam, axis=0)  # prod_{j<=t}(1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(left[:1]), left[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], axis=0)


def objective(ce, z, beta: float):
    """Summed over tokens: sum_t p_t CE_t - beta H(p); and each pass's
    summed exit probability."""
    p = exit_distribution(z)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
    return jnp.sum(jnp.sum(p * ce, axis=0) - beta * entropy), jnp.sum(
        p, axis=tuple(range(1, p.ndim)))


def loss(params, tokens, cfg: Dict, mm=None, passes: Optional[int] = None):
    """The whole model's loss in one piece, for sizes at which everything
    fits at once (the tests): what the walk below computes block by
    block. Returns (loss, mean exit probability of each pass)."""
    mm = mm or make_mm("f32")
    passes = passes or cfg["total_ut_steps"]
    h, targets = params["embed"][tokens[:, :-1]], tokens[:, 1:]
    ces, zs = [], []
    for _ in range(passes):
        for p in params["layers"]:
            h = layer_fwd(h, p, cfg, mm)
        h = rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])
        ce, z = head_and_gate(h, params["lm_head"], params["exit_gate"], targets, mm)
        ces.append(ce), zs.append(z)
    total, mass = objective(jnp.stack(ces), jnp.stack(zs), cfg["exit_entropy_beta"])
    return total / targets.size, mass / targets.size


# ---------------------------------------------------------------------------
# the walk: a block of rows at a time, application by application
# ---------------------------------------------------------------------------


class Reference:
    """Follows a cell's first steps from the seed, as
    `llama_ref.Reference` does: `run(batches, n)` returns every loss, the
    per-leaf norms of the first gradient and of the parameters' change
    over the n steps, and `exit_mass`: each step's mean exit probability
    of every pass."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int, devices,
                 mode: str = "f32", fault: Optional[str] = None,
                 passes: Optional[int] = None):
        if cfg.get("tie_word_embeddings"):
            raise ValueError("this reference keeps the head apart from the embedding")
        if fault not in (None, "half_batch"):
            raise ValueError(f"fault {fault!r} is not planted here (half_batch, passes=)")
        self.cfg, self.cell, self.seed, self.fault = cfg, cell, seed, fault
        self.passes = int(passes or cfg["total_ut_steps"])
        self.beta = float(cfg["exit_entropy_beta"])
        self.opt = cell["optimizer"]
        self.mm = make_mm(mode)
        self.device = list(devices)[0]
        self.block = int(cell["reference"]["row_block"])
        self.make_weights = weights_looped.maker(cfg)
        with jax.default_device(self.device):
            self.params = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t))(self.make_weights(seed))
        # gradients of earlier steps, for AdamW's moments: on the host
        # where parameters, the running gradient and a copy of the last
        # one would crowd a chip
        self.history_on_host = 3 * 4 * sum(
            int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(self.params)) > 5e9
        self.history: List[Dict] = []
        self._jits()

    def _jits(self):
        cfg, mm, opt, beta = self.cfg, self.mm, self.opt, self.beta
        eps = cfg["rms_norm_eps"]
        tmap = jax.tree_util.tree_map
        self._embed = jax.jit(lambda table, ids: table[ids])
        self._layer = jax.jit(lambda x, p: layer_fwd(x, p, cfg, mm))

        def layer_back(x, p, dy):
            _, vjp = jax.vjp(lambda x_, p_: layer_fwd(x_, p_, cfg, mm), x, p)
            return vjp(dy)

        self._layer_back = jax.jit(layer_back)
        self._norm = jax.jit(lambda u, w: rms_norm(u, w, eps))

        def norm_back(u, w, dh):
            _, vjp = jax.vjp(lambda u_, w_: rms_norm(u_, w_, eps), u, w)
            return vjp(dh)

        self._norm_back = jax.jit(norm_back)
        self._head = jax.jit(lambda h, head, gate, targets: head_and_gate(
            h, head, gate, targets, mm))

        def head_back(h, head, gate, targets, dce, dz):
            _, vjp = jax.vjp(lambda h_, w_, g_: head_and_gate(
                h_, w_, g_, targets, mm), h, head, gate)
            return vjp((dce, dz))

        self._head_back = jax.jit(head_back)
        # the objective's value, each pass's summed exit probability, and
        # its gradient in every token's CE_t and gate logit
        self._objective = jax.jit(lambda ce, z: jax.value_and_grad(
            lambda c, z_: objective(c, z_, beta), argnums=(0, 1), has_aux=True)(ce, z))
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

    # -- one block of rows --------------------------------------------------

    def _put(self, arr):
        return jax.device_put(arr, self.device)

    def _blocks(self, tokens: np.ndarray):
        rows = tokens.shape[0]
        if self.fault == "half_batch":
            rows = max(rows // 2, 1)
        blk = min(self.block, rows)
        return [(lo, min(lo + blk, rows)) for lo in range(0, rows, blk)], rows

    def _chunks(self, t: int):
        size = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t
        return [(lo, lo + size) for lo in range(0, t, size)]

    def _head_of(self, h, targets):
        """(CE, gate logit) of every token of a pass's state, a chunk of
        the sequence's logits at a time."""
        prm = self.params
        out = [self._head(h[:, lo:hi], prm["lm_head"], prm["exit_gate"], targets[:, lo:hi])
               for lo, hi in self._chunks(h.shape[1])]
        return tuple(jnp.concatenate(part, axis=1) for part in zip(*out))

    def _head_back_of(self, h, targets, dce, dz, g: Dict):
        """The head's and the gate's gradients summed into g; returns dh."""
        prm, dhs = self.params, []
        for lo, hi in self._chunks(h.shape[1]):
            dh, dhead, dgate = self._head_back(
                h[:, lo:hi], prm["lm_head"], prm["exit_gate"], targets[:, lo:hi],
                dce[:, lo:hi], dz[:, lo:hi])
            dhs.append(dh)
            self._acc(g, "lm_head", dhead), self._acc(g, "exit_gate", dgate)
        return jnp.concatenate(dhs, axis=1)

    def _acc(self, g, key, new):
        g[key] = new if g[key] is None else self._add(g[key], new)

    def _forward(self, ids, targets, keep: bool):
        """Every pass over one block: (each application's input by pass
        and layer, each pass's pre-norm and normed state) where kept, and
        each token's CE_t and gate logit, [T, r, t]."""
        prm = self.params
        h = self._embed(prm["embed"], ids)
        inputs, pre, states, ces, zs = [], [], [], [], []
        for _ in range(self.passes):
            x, ins = h, []
            for p in prm["layers"]:
                if keep:
                    ins.append(x)
                x = self._layer(x, p)
            h = self._norm(x, prm["final_norm"])
            ce, z = self._head_of(h, targets)
            ces.append(ce), zs.append(z)
            if keep:
                inputs.append(ins), pre.append(x), states.append(h)
        return inputs, pre, states, jnp.stack(ces), jnp.stack(zs)

    def _block_grads(self, tokens: np.ndarray, g: Dict):
        """The block's gradient summed into g, leaf by leaf; returns (its
        summed objective, its summed exit probabilities)."""
        prm = self.params
        ids, targets = self._put(tokens[:, :-1]), self._put(tokens[:, 1:])
        inputs, pre, states, ce, z = self._forward(ids, targets, keep=True)
        (total, mass), (dce, dz) = self._objective(ce, z)
        dh_next = None  # what the pass after this one sends back into h_t
        for t in reversed(range(self.passes)):
            dh = self._head_back_of(states[t], targets, dce[t], dz[t], g)
            states[t] = None
            if dh_next is not None:
                dh = self._add(dh, dh_next)
            dx, dfn = self._norm_back(pre[t], prm["final_norm"], dh)
            pre[t] = None
            self._acc(g, "final_norm", dfn)
            for i in reversed(range(len(prm["layers"]))):
                dx, dlayer = self._layer_back(inputs[t][i], prm["layers"][i], dx)
                inputs[t][i] = None
                self._acc(g["layers"], i, dlayer)
            dh_next = dx
        self._acc(g, "embed", self._embed_back(ids, dh_next, prm["embed"]))
        return float(total), np.asarray(mass)

    # -- one step -----------------------------------------------------------

    def loss_only(self, tokens: np.ndarray) -> Dict:
        blocks, rows = self._blocks(tokens)
        total, mass = 0.0, 0.0
        for lo, hi in blocks:
            ids, targets = self._put(tokens[lo:hi, :-1]), self._put(tokens[lo:hi, 1:])
            *_, ce, z = self._forward(ids, targets, keep=False)
            (v, m), _ = self._objective(ce, z)
            total, mass = total + float(v), mass + np.asarray(m)
        n = rows * (tokens.shape[1] - 1)
        return {"loss": total / n, "exit_mass": [float(m) / n for m in mass]}

    def full_step(self, tokens: np.ndarray) -> Dict:
        """Loss and gradient of one batch, then AdamW on every leaf, a
        group of leaves at a time."""
        blocks, rows = self._blocks(tokens)
        inv = np.float32(1.0 / (rows * (tokens.shape[1] - 1)))
        total, mass = 0.0, 0.0
        grads = dict.fromkeys(self.params)  # each leaf group's running sum
        grads["layers"] = [None] * len(self.params["layers"])
        for lo, hi in blocks:
            v, m = self._block_grads(tokens[lo:hi], grads)
            total, mass = total + v, mass + m
        grads = self._scale(grads, inv)
        grad_sq = jax.device_get(self._sq(grads))
        groups = [(self.params, k) for k in self.params if k != "layers"]
        groups += [(self.params["layers"], i) for i in range(len(self.params["layers"]))]
        for where, key in groups:
            mine = lambda t: t[key] if where is self.params else t["layers"][key]
            past = [mine(h) for h in self.history]
            if self.history_on_host:
                past = [self._put(h) for h in past]
            where[key] = self._adam(where[key], past + [mine(grads)])
        self.history.append(jax.device_get(grads) if self.history_on_host else grads)
        return {"loss": total * float(inv), "grad_sq": grad_sq,
                "exit_mass": [float(m) * float(inv) for m in mass]}

    # -- the readings -------------------------------------------------------

    def run(self, batches: List[np.ndarray], full_steps: int) -> Dict:
        root = lambda t: jax.tree_util.tree_map(lambda s: float(np.sqrt(s)), t)
        out = {"loss": [], "exit_mass": [], "grad_norm": None, "change_norm": None}
        with jax.default_device(self.device):
            for k, tokens in enumerate(batches):
                r = self.full_step(tokens) if k < full_steps else self.loss_only(tokens)
                out["loss"].append(r["loss"])
                out["exit_mass"].append(r["exit_mass"])
                if k == 0 and k < full_steps:
                    out["grad_norm"] = root(r["grad_sq"])
                if k == full_steps - 1:
                    self.history = []
                    start = self.make_weights(self.seed)
                    out["change_norm"] = root(jax.device_get(
                        self._diff_sq(self.params, start)))
        return out
