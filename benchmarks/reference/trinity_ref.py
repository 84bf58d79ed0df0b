"""Plain float32 reference of the training step of a model of gated
grouped-query attention layers, windowed with RoPE or full with no
position embedding by layer, four norms a layer, a dense FFN first and
expert FFNs with a shared expert after it (Trinity-Large-Preview's
`afmoe` layer equations, PERF.md section 4): next-token cross entropy
over an untied head, AdamW.

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`:
no kernels, no bf16, no program code, no program arrays; nothing is
imported from `kubedl_tpu`. Its own weights from the seed
(`benchmarks/weights_trinity.py`), the cell's first steps on the same
token batches, layer by layer, one sequence at a time with each layer's
float32 input kept, a `jax.vjp` a layer, attention in blocks of queries
with each layer's own mask and its own RoPE or none, the head in pieces
of 2,048 tokens, AdamW on a layer as soon as its gradient is whole: the
walk of `xing_ref.py` over one residual stream, whose SwiGLU and head it
shares, with `llama_ref.py`'s norm, RoPE, attention, AdamW and control
arithmetic, and `lfm2_ref.py`'s router. One device.

The equations (d 3072, 48 query and 8 key/value heads of 128 at the
published sizes; u, h the normed inputs):

  embed   x_0 = E[t] * sqrt(d)                                   mup_enabled (assumed: the only muP multiplier)
  layer   h = rmsnorm_in(x)
          q = h W_q -> 48 x 128;  k = h W_k, v = h W_v -> 8 x 128;  q, k <- rmsnorm over each head's 128
          sliding_attention: q, k <- RoPE(q), RoPE(k) (theta 10000, half-split pairs); full_attention: no
          position embedding (assumed: NoPE on full)
          o = softmax(q k^T / sqrt(128) + mask) v, each key/value head shared by 6 query heads; mask causal,
          and in a sliding layer key j visible to query i iff i - j < sliding_window
          o <- o * sigmoid(h W_g)      W_g [d, 48 * 128]: one gate a head and channel (assumed: elementwise)
          x <- x + rmsnorm_post_attn(o W_o)                                            four norms a layer
          u = rmsnorm_pre_mlp(x);  x <- x + rmsnorm_post_mlp(FFN(u))
  FFN     dense layers: (silu(u W_1) * (u W_3)) W_2
          expert layers: s = sigmoid(u W_r) over all router outputs, float32; chosen = top-k of s + b
          (b = expert_bias: selects only, no gradient, no update: assumed); g = route_scale s[chosen] /
          (sum s[chosen] + 1e-20) (route_norm; the eps assumed, as Xing's); FFN(u) = sum over chosen and
          held e of g_e SwiGLU_e(u) + SwiGLU_shared(u)                         n_group 1: no group limit
  out     logits = rmsnorm_final(x_L) W_head;  loss = mean CE(next token)

What is marked assumed is the family's modelling code as remembered:
config.json gives the sizes and not these choices.

`mode="int8"` and `mode="fp8"` are `llama_ref`'s controls: both operands
of every bf16 weight matmul (the gate's among them) rounded; the router,
which the configuration states in float32, keeps its precision. Faults
plant a wrong step: "half_batch" (the second half of the rows left out;
at one row, the second half of its positions left out of the loss),
"no_gate" (the gate left out), "rope_on_full" (RoPE on the full layers
too), "window_on_full" (the full layers windowed at sliding_window).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops, flops_afmoe, weights_trinity
from benchmarks.reference import xing_ref
from benchmarks.reference.lfm2_ref import ROUTE_LEAVES, route
from benchmarks.reference.llama_ref import adamw_update, attention, make_mm, rms_norm, rope

FAULTS = (None, "half_batch", "no_gate", "rope_on_full", "window_on_full")
GATE_COUNTERS = ("attn_gate_mean", "attn_gate_spread")  # the program's names


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def layer_kinds(cfg: Dict, fault: Optional[str] = None):
    """Each layer's (window, roped): a sliding layer's window and RoPE, a
    full layer's neither, but where a fault gives it one."""
    out = []
    for window in flops_afmoe.layer_windows(cfg):
        full = window is None
        if full and fault == "window_on_full":
            window = cfg["sliding_window"]
        out.append((window, not full or fault == "rope_on_full"))
    return out


def gated_attention(h, p, cfg: Dict, mm, window, roped: bool, fault=None):
    """(rmsnorm_post_attn(o W_o) over the normed input h [r, t, d], [the
    mean gate, the mean of (gate - 1/2)^2]: 1 and 1/4 where the gate is
    left out)."""
    r, t, _ = h.shape
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], flops.head_dim(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads = lambda x, n: x.reshape(r, t, n, hd).transpose(0, 2, 1, 3)
    q = rms_norm(heads(mm(h, p["wq"]), nq), p["q_norm"], eps)
    k = rms_norm(heads(mm(h, p["wk"]), nkv), p["k_norm"], eps)
    v = heads(mm(h, p["wv"]), nkv)
    if roped:
        q, k = rope(q, theta), rope(k, theta)
    o = attention(q, k, v, window).transpose(0, 2, 1, 3).reshape(r, t, nq * hd)
    gate = jax.nn.sigmoid(mm(h, p["wg"]))
    if fault == "no_gate":
        gate = jnp.ones_like(gate)
    return (rms_norm(mm(o * gate, p["wo"]), p["post_attn_norm"], eps),
            jnp.stack([jnp.mean(gate), jnp.mean(jnp.square(gate - 0.5))]))


def expert_ffn(u, p, cfg: Dict, mm):
    """(the held experts' part of the routed FFN plus the shared expert,
    choices that flip under a bfloat16 input). Each held expert over
    every row, weighed by its routing weight, 0 where it was not chosen:
    no room to run out of, however unevenly a seeded router sends the
    rows (`xing_ref`'s gather into four times an even share left 2,479
    of one layer's rows out on the chip, with the post-sublayer norms'
    gains at 1), each expert recomputed in the backward pass."""
    k, first = cfg["num_experts_per_tok"], cfg["first_expert"]
    chosen, s = route(u, p["router"], p["router_bias"], k)
    picked = s * chosen
    g = cfg["route_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + cfg["router_norm_eps"])
    held = p["w1"].shape[0]

    @jax.checkpoint
    def one(y, expert):
        w1, w3, w2, weight = expert
        return y + weight[..., None] * xing_ref.swiglu(u, w1, w3, w2, mm), None

    y, _ = jax.lax.scan(one, xing_ref.swiglu(u, p["shared_w1"], p["shared_w3"],
                                             p["shared_w2"], mm),
                        (p["w1"], p["w3"], p["w2"],
                         jnp.moveaxis(g[..., first:first + held], -1, 0)))
    rounded = u.astype(jnp.bfloat16).astype(jnp.float32)
    chosen_bf16, _ = route(rounded, p["router"], p["router_bias"], k)
    return y, jax.lax.stop_gradient(jnp.sum(chosen * (1.0 - chosen_bf16)))


def layer_fwd(x, p, cfg: Dict, mm, window, roped: bool, fault=None):
    """One layer over x [r, t, d]: (x, [flipped choices, mean gate, gate
    spread])."""
    eps = cfg["rms_norm_eps"]
    a, gate = gated_attention(rms_norm(x, p["attn_norm"], eps), p, cfg, mm, window, roped, fault)
    x = x + a
    u = rms_norm(x, p["mlp_norm"], eps)
    if "moe" in p:
        y, flips = expert_ffn(u, p["moe"], cfg, mm)
    else:
        y, flips = xing_ref.swiglu(u, p["w1"], p["w3"], p["w2"], mm), jnp.zeros(())
    x = x + rms_norm(y, p["post_mlp_norm"], eps)
    return x, jax.lax.stop_gradient(jnp.concatenate([flips[None], gate]))


def embed(table, ids, cfg: Dict):
    return table[ids] * np.float32(np.sqrt(cfg["hidden_size"]))


def loss_and_counters(params, tokens, cfg: Dict, mm=None, fault=None):
    """The whole model's mean next-token loss in one piece, for sizes at
    which everything fits at once (the tests): what the walk below
    computes layer by layer. Counters: attn_gate_mean and
    attn_gate_spread (over the layers)."""
    mm = mm or make_mm("f32")
    with jax.default_matmul_precision("highest"):
        x, gates = embed(params["embed"], tokens[:, :-1], cfg), 0.0
        for p, (window, roped) in zip(params["layers"], layer_kinds(cfg, fault)):
            x, aux = layer_fwd(x, p, cfg, mm, window, roped, fault)
            gates += aux[1:]
        targets = tokens[:, 1:]
        nll = xing_ref.head_nll(x, params["final_norm"], params["lm_head"], targets,
                                jnp.ones(targets.shape, jnp.float32), cfg, mm)
        gates = gates / len(params["layers"])
        return nll / targets.size, dict(zip(GATE_COUNTERS, gates))


def loss(params, tokens, cfg: Dict, mm=None, fault=None):
    return loss_and_counters(params, tokens, cfg, mm, fault)[0]


# ---------------------------------------------------------------------------
# the walk: layer by layer, one sequence at a time
# ---------------------------------------------------------------------------


def _highest(fn, **kw):
    """`fn` jitted, traced under the highest matmul precision."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(run, **kw)


class Reference:
    """Follows a cell's first steps from the seed, as `xing_ref.Reference`
    does: `run(batches, n)` returns every loss, the per-leaf norms of the
    first gradient and of the parameters' change over the n steps,
    `route_flip_share`, `selection_leaves`, `counters` (the first step's
    GATE_COUNTERS, as the program's step counts them) and `seconds`: where
    the run's time went, by phase."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int, devices,
                 mode: str = "f32", fault: Optional[str] = None):
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r} is not planted here {FAULTS[1:]}")
        if cfg.get("tie_word_embeddings"):
            raise ValueError("this reference's head is untied")
        self.cfg, self.cell, self.seed, self.fault = cfg, cell, seed, fault
        self.opt = cell["optimizer"]
        self.mm = make_mm(mode)
        self.device = list(devices)[0]
        self.block = int(cell["reference"]["row_block"])
        self.kinds = layer_kinds(cfg, fault)
        self.make_weights = weights_trinity.maker(cfg)
        with jax.default_device(self.device):
            self.params = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t))(self.make_weights(seed))
        # gradients of earlier steps, for AdamW's moments, and the layers'
        # saved inputs: on the host where they and the parameters would
        # crowd a chip
        self.history_on_host = 2 * 4 * sum(
            int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(self.params)) > 5e9
        self.history: List[Dict] = []
        self.flips = self.pairs = 0.0
        self.seconds: Dict[str, float] = {}  # where a run's time went, by phase
        self._jits()

    @contextlib.contextmanager
    def _timed(self, phase: str):
        """The host's seconds in `phase`, whatever it dispatched ended."""
        t0 = time.perf_counter()
        done: List = []
        yield done
        jax.block_until_ready(done)
        self.seconds[phase] = self.seconds.get(phase, 0.0) + time.perf_counter() - t0

    def _jits(self):
        cfg, mm, opt, fault = self.cfg, self.mm, self.opt, self.fault
        tmap = jax.tree_util.tree_map
        self._embed = jax.jit(lambda table, ids: embed(table, ids, cfg))
        self._layer = _highest(
            lambda x, p, window, roped: layer_fwd(x, p, cfg, mm, window, roped, fault),
            static_argnums=(2, 3))

        def layer_back(x, p, dy, window, roped):
            _, vjp, _ = jax.vjp(
                lambda x_, p_: layer_fwd(x_, p_, cfg, mm, window, roped, fault),
                x, p, has_aux=True)
            return vjp(dy)

        self._layer_back = _highest(layer_back, static_argnums=(3, 4))
        nll = lambda x, norm_w, head, targets, mask: xing_ref.head_nll(
            x, norm_w, head, targets, mask, cfg, mm)
        self._head = _highest(nll)
        self._head_back = _highest(
            lambda x, norm_w, head, targets, mask, scale: jax.value_and_grad(
                lambda a, b, c: scale * nll(a, b, c, targets, mask),
                argnums=(0, 1, 2))(x, norm_w, head))
        scale = np.float32(np.sqrt(cfg["hidden_size"]))
        self._embed_back = jax.jit(
            lambda ids, dx, like: jnp.zeros_like(like).at[ids].add(dx * scale))
        self._add = jax.jit(lambda a, b: tmap(jnp.add, a, b), donate_argnums=(0,))
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
        if self.fault == "half_batch" and rows > 1:
            rows //= 2
        blk = min(self.block, rows)
        return [(lo, min(lo + blk, rows)) for lo in range(0, rows, blk)], rows

    def _kept(self, tokens: np.ndarray) -> int:
        """The target positions of a row that count in the loss: every
        one, but the first half alone where "half_batch" meets a batch of
        one row (a step that trains on part of its tokens)."""
        t = tokens.shape[1] - 1
        return t // 2 if self.fault == "half_batch" and tokens.shape[0] == 1 else t

    def _mask(self, tokens: np.ndarray, fed):
        t = tokens.shape[1] - 1
        return self._put(np.broadcast_to(
            np.arange(t) < self._kept(tokens), (fed.shape[0], t)).astype(np.float32))

    def _forward(self, tokens: np.ndarray, keep: bool):
        """Per block of rows: the fed tokens, every layer's input (kept
        only for a full step) and the last layer's output; and the layers'
        mean gate counters summed, weighed by the block's rows."""
        blocks, rows = self._blocks(tokens)
        acts, gates = [], np.zeros(len(GATE_COUNTERS))
        for lo, hi in blocks:
            fed = self._put(tokens[lo:hi])
            x = self._embed(self.params["embed"], fed[:, :-1])
            inputs = []
            for p, (window, roped) in zip(self.params["layers"], self.kinds):
                if keep:
                    with self._timed("inputs_to_host_s"):
                        inputs.append(jax.device_get(x) if self.history_on_host else x)
                with self._timed("forward_s") as done:
                    x, aux = self._layer(x, p, window, roped)
                    done.append(x)
                if keep:
                    gates += np.asarray(aux[1:]) * (hi - lo)
                    self._count_flips(p, aux, fed)
            acts.append([fed, inputs, x])
        return blocks, rows, acts, gates

    def _count_flips(self, p, aux, fed):
        if "moe" in p:
            self.flips += float(aux[0])
            self.pairs += float((fed.shape[1] - 1) * fed.shape[0]
                                * self.cfg["num_experts_per_tok"])

    def loss_only(self, tokens: np.ndarray) -> float:
        _, rows, acts, _ = self._forward(tokens, keep=False)
        nll = sum(float(self._head(x, self.params["final_norm"], self.params["lm_head"],
                                   fed[:, 1:], self._mask(tokens, fed))) for fed, _, x in acts)
        return nll / (rows * self._kept(tokens))

    def _acc(self, acc, g):
        return g if acc is None else self._add(acc, g)

    def _settle(self, grads_now: Dict, grad_sq: Dict, name: str, g, index=None):
        """A leaf group's gradient is whole: norm it, apply AdamW, keep it
        for the next step's moments."""
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
        layer is updated as soon as its gradient is whole; the embedding
        last."""
        blocks, rows, acts, gates = self._forward(tokens, keep=True)
        n_layers = len(self.params["layers"])
        inv = np.float32(1.0 / (rows * self._kept(tokens)))
        grads_now = {"layers": [None] * n_layers}
        grad_sq = {"layers": [None] * n_layers}

        total, dxs, g_tail = 0.0, [], None
        p = self.params
        for act in acts:
            with self._timed("tail_s") as done:
                part, (dx, d_norm, d_head) = self._head_back(
                    act[2], p["final_norm"], p["lm_head"], act[0][:, 1:],
                    self._mask(tokens, act[0]), inv)
                done.append(dx)
            act[2] = None
            total += float(part)
            dxs.append(dx)
            g_tail = self._acc(g_tail, {"final_norm": d_norm, "lm_head": d_head})
        for name in list(g_tail):
            self._settle(grads_now, grad_sq, name, g_tail.pop(name))

        for i in reversed(range(n_layers)):
            window, roped = self.kinds[i]
            g_layer = None
            for b, act in enumerate(acts):
                with self._timed("inputs_from_host_s") as done:
                    x = self._put(act[1][i])
                    done.append(x)
                with self._timed("layers_back_s") as done:
                    dxs[b], g = self._layer_back(x, p["layers"][i], dxs[b], window, roped)
                    done.append(dxs[b])
                act[1][i] = x = None
                g_layer = self._acc(g_layer, g)
            with self._timed("adamw_and_history_s") as done:
                self._settle(grads_now, grad_sq, "layers", g_layer, index=i)
                done.append(p["layers"][i])
            del g_layer

        g_embed = None
        for b, act in enumerate(acts):
            g_embed = self._acc(g_embed, self._embed_back(
                act[0][:, :-1], dxs[b], p["embed"]))
        self._settle(grads_now, grad_sq, "embed", g_embed)
        self.history.append(grads_now)
        return {"loss": total, "grad_sq": jax.device_get(grad_sq),
                "counters": dict(zip(GATE_COUNTERS, map(float, gates / (rows * n_layers))))}

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
                    out["counters"] = r["counters"]
                if k == full_steps - 1:
                    self.history = []
                    start = self.make_weights(self.seed)
                    out["change_norm"] = root(jax.device_get(
                        self._diff_sq(self.params, start)))
        out["seconds"] = dict(self.seconds)
        out["route_flip_share"] = self.flips / self.pairs if self.pairs else 0.0
        out["selection_leaves"] = sorted(
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(self.params)[0]
            if getattr(path[-1], "key", None) in ROUTE_LEAVES)
        return out
