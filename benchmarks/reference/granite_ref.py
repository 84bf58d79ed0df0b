"""Plain float32 reference of the training step of a model whose layers
are Mamba-2 state-space mixers and grouped-query attention without a
position embedding, a dense SwiGLU in every layer, four scalar
multipliers and a tied head (Granite-4.0-H's layer equations, PERF.md
section 4): next-token cross entropy, AdamW.

Straightforward `jax.numpy` at `highest` matmul precision: no kernels, no
bf16, no program code, no program arrays. Its own weights from the seed
(`benchmarks/weights_ssm.py`), the cell's first steps on the same token
batches, layer by layer (a `jax.vjp` per layer) over blocks of rows (one
sequence at a time), AdamW on a layer as soon as its gradient is whole:
the walk of `llama_ref.py`, whose norm, attention, AdamW and control
arithmetic it shares. One device.

**The state-space layer is the recurrence, token by token** (a `lax.scan`
over tokens), not the chunked algorithm the program runs, so that a fault
of the chunking is not shared. So that it fits, the token scan runs
inside a `jax.checkpoint` over blocks of `mamba_chunk_size` tokens (a
block keeps 256 states of 2.1 MB where a whole sequence's would be 17
GB), and the head's logits exist for `HEAD_CHUNK` tokens at a time.

The equations, `u` the normed input of a mixer or the FFN:

  model      h0 = embedding_multiplier * E[tokens]
             layer: h = h + residual_multiplier * mixer(rmsnorm(h))
                    h = h + residual_multiplier * W_2(silu(u W_1) * (u W_3))
             logits = rmsnorm(h_L) E^T / logits_scaling
  attention  q, k, v = u W_q, u W_k, u W_v in heads of 64, no RoPE, no
             bias; softmax(q k^T * attention_multiplier + causal) v, the
             key/value heads shared by groups of query heads; W_o
  mamba      per token t and head j (x_t^j of mamba_d_head entries; B_t,
             C_t of mamba_d_state entries, shared by all heads):
             [z, xBC, dt] = split(u W_in, [inner, inner + 2 state, heads])
             xBC = silu(causal_conv(xBC) + conv_bias)   depthwise, K taps,
                                          K - 1 zeros before the sequence
             [x, B, C] = split(xBC, [inner, state, state])
             dt_t = softplus(dt_t + dt_bias); a_t^j = exp(dt_t^j A^j),
             A = -exp(A_log)
             S_t^j = a_t^j S_{t-1}^j + dt_t^j x_t^j B_t^T,   S_0 = 0
             y_t^j = S_t^j C_t + D^j x_t^j
             mixer = W_out(rmsnorm(y * silu(z)) * w)

What config.json does not give and this file assumes (the family's
modelling code, as remembered; the configuration file's `assumed` has the
same list): the gate before the norm and one norm over all `inner`
channels (one group); the SiLU after the convolution; both branches of a
layer scaled by `residual_multiplier`; `head_dim` 64 = hidden / heads;
the seeded values of a state-space layer's leaves (`weights_ssm.py`).

`mode="int8"` and `mode="fp8"` are `llama_ref`'s controls: both operands
of every weight matmul rounded; the recurrence keeps its precision.
`fault="half_batch"` plants a wrong step; `fault="no_carry"` another: the
state set to zero at every multiple of `mamba_chunk_size` tokens, which
is what a chunked scan that drops its chunk-to-chunk pass computes.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops, weights_ssm
from benchmarks.reference.llama_ref import (HIGHEST, adamw_update, attention,
                                            make_mm, rms_norm)

# tokens of a sequence whose logits exist together
HEAD_CHUNK = 2048
FAULTS = (None, "half_batch", "no_carry")


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def recurrence(x, a, dt, b_, c_, block: int, no_carry: bool = False):
    """y_t = S_t C_t for S_t = a_t S_{t-1} + dt_t x_t B_t^T, token by token.
    x [r, t, h, p]; a, dt [r, t, h]; b_, c_ [r, t, n]. The scan is cut
    into blocks of `block` tokens for the backward pass's memory alone
    (and for `no_carry`, which forgets the state where a block starts)."""
    r, t, h, p = x.shape

    def token(state, inp):
        x_t, a_t, dt_t, b_t, c_t = inp
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("rhpn,rn->rhp", state, c_t, precision=HIGHEST)

    @jax.checkpoint
    def tokens(state, inps):
        if no_carry:
            state = jnp.zeros_like(state)
        return jax.lax.scan(token, state, inps)

    by_time = [jnp.moveaxis(v, 1, 0) for v in (x, a, dt, b_, c_)]
    whole = t // block * block
    state, ys = jnp.zeros((r, h, p, b_.shape[-1]), x.dtype), []
    if whole:
        state, y = jax.lax.scan(tokens, state, [
            v[:whole].reshape((whole // block, block) + v.shape[1:]) for v in by_time])
        ys.append(y.reshape((whole,) + y.shape[2:]))
    if whole < t:
        ys.append(tokens(state, [v[whole:] for v in by_time])[1])
    return jnp.moveaxis(jnp.concatenate(ys), 0, 1)


def mamba(u, p, cfg: Dict, mm, no_carry: bool = False):
    """The state-space mixer over u [r, t, d]."""
    r, t, _ = u.shape
    s = weights_ssm.ssm_sizes(cfg)
    inner, n, h = s["inner"], s["state"], s["heads"]
    z, xbc, dt = jnp.split(mm(u, p["ssm_in"]), [inner, inner + s["conv"]], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (s["taps"] - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * p["ssm_conv_w"][:, j] for j in range(s["taps"]))
    x, b_, c_ = jnp.split(jax.nn.silu(conv + p["ssm_conv_b"]), [inner, inner + n], axis=-1)
    x = x.reshape(r, t, h, s["head_dim"])
    dt = jax.nn.softplus(dt + p["ssm_dt_bias"])
    a = jnp.exp(dt * -jnp.exp(p["ssm_A_log"]))
    y = recurrence(x, a, dt, b_, c_, cfg["mamba_chunk_size"], no_carry)
    y = (y + p["ssm_D"][:, None] * x).reshape(r, t, inner)
    g = rms_norm(y * jax.nn.silu(z), p["ssm_gate_norm"], cfg["rms_norm_eps"])
    return mm(g, p["ssm_out"])


def gqa(u, p, cfg: Dict, mm):
    """Grouped-query attention with no position embedding, the scores
    scaled by attention_multiplier."""
    r, t, _ = u.shape
    nq, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], flops.head_dim(cfg)
    heads = lambda x, n: x.reshape(r, t, n, hd).transpose(0, 2, 1, 3)
    # `attention` divides the scores by sqrt(hd): net attention_multiplier
    q = heads(mm(u, p["wq"]), nq) * (cfg["attention_multiplier"] * np.sqrt(hd))
    a = attention(q, heads(mm(u, p["wk"]), nkv), heads(mm(u, p["wv"]), nkv), None)
    return mm(a.transpose(0, 2, 1, 3).reshape(r, t, nq * hd), p["wo"])


def layer_fwd(x, p, cfg: Dict, mm, no_carry: bool = False):
    """One layer over a block of rows, x [r, t, d]."""
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    if "ssm_in" in p:
        x = x + rm * mamba(rms_norm(x, p["ssm_norm"], eps), p, cfg, mm, no_carry)
    else:
        x = x + rm * gqa(rms_norm(x, p["attn_norm"], eps), p, cfg, mm)
    u = rms_norm(x, p["mlp_norm"], eps)
    return x + rm * mm(jax.nn.silu(mm(u, p["w1"])) * mm(u, p["w3"]), p["w2"])


def head_nll(x, final_norm, embed, targets, cfg: Dict, mm):
    """Summed next-token negative log likelihood of a block of rows, the
    head the embedding's transpose, HEAD_CHUNK tokens' logits at a time
    (computed again in the backward pass)."""
    r, t, d = x.shape
    h = rms_norm(x, final_norm, cfg["rms_norm_eps"])

    @jax.checkpoint
    def piece(args):
        h_c, want = args
        logp = jax.nn.log_softmax(mm(h_c, embed.T) / cfg["logits_scaling"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, want[..., None], axis=-1))

    c = t // HEAD_CHUNK if t % HEAD_CHUNK == 0 else 1
    return jnp.sum(jax.lax.map(piece, (
        jnp.moveaxis(h.reshape(r, c, t // c, d), 1, 0),
        jnp.moveaxis(targets.reshape(r, c, t // c), 1, 0))))


def loss(params, tokens, cfg: Dict, mm=None, no_carry: bool = False):
    """The whole model's mean next-token loss in one piece, for sizes at
    which everything fits at once (the tests): what the walk below
    computes block by block."""
    mm = mm or make_mm("f32")
    x = cfg["embedding_multiplier"] * params["embed"][tokens[:, :-1]]
    for p in params["layers"]:
        x = layer_fwd(x, p, cfg, mm, no_carry)
    nll = head_nll(x, params["final_norm"], params["embed"], tokens[:, 1:], cfg, mm)
    return nll / tokens[:, 1:].size


# ---------------------------------------------------------------------------
# the walk: layer by layer, block of rows by block of rows
# ---------------------------------------------------------------------------


class Reference:
    """Follows a cell's first steps from the seed, as
    `llama_ref.Reference` does: `run(batches, n)` returns every loss and
    the per-leaf norms of the first gradient and of the parameters'
    change over the n steps."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int, devices,
                 mode: str = "f32", fault: Optional[str] = None):
        if not cfg.get("tie_word_embeddings"):
            raise ValueError("this reference ties the head to the embedding")
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r} is not planted here {FAULTS[1:]}")
        self.cfg, self.cell, self.seed, self.fault = cfg, cell, seed, fault
        self.opt = cell["optimizer"]
        self.mm = make_mm(mode)
        self.device = list(devices)[0]
        self.block = int(cell["reference"]["row_block"])
        self.make_weights = weights_ssm.maker(cfg)
        with jax.default_device(self.device):
            self.params = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t))(self.make_weights(seed))
        # gradients of earlier steps, for AdamW's moments: on the host
        # where parameters and a copy of the gradients would crowd a chip
        self.history_on_host = 2 * 4 * sum(
            int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(self.params)) > 5e9
        self.history: List[Dict] = []
        self._jits()

    def _jits(self):
        cfg, mm, opt = self.cfg, self.mm, self.opt
        no_carry = self.fault == "no_carry"
        tmap = jax.tree_util.tree_map
        scale = float(cfg["embedding_multiplier"])
        self._embed = jax.jit(lambda table, ids: scale * table[ids])
        self._layer = jax.jit(lambda x, p: layer_fwd(x, p, cfg, mm, no_carry))

        def layer_back(x, p, dy):
            _, vjp = jax.vjp(
                lambda x_, p_: layer_fwd(x_, p_, cfg, mm, no_carry), x, p)
            return vjp(dy)

        self._layer_back = jax.jit(layer_back)
        self._head_back = jax.jit(lambda x, fn, emb, targets: jax.value_and_grad(
            lambda x_, f_, e_: head_nll(x_, f_, e_, targets, cfg, mm),
            argnums=(0, 1, 2))(x, fn, emb))
        self._head = jax.jit(
            lambda x, fn, emb, targets: head_nll(x, fn, emb, targets, cfg, mm))
        self._embed_back = jax.jit(
            lambda ids, dx, like: jnp.zeros_like(like).at[ids].add(scale * dx))
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
                x = self._layer(x, p)
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
        return out
