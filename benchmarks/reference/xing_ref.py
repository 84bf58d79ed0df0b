"""Plain float32 reference of the training step of a model of latent
attention (MLA) on four residual streams mixed by manifold-constrained
hyper-connections (mHC), a dense FFN first and expert FFNs with a shared
expert after it, and one multi-token prediction (MTP) module:
Xing4.0-29B-A4B's layer equations (PERF.md section 4), next-token cross
entropy plus the module's loss over an untied head, AdamW.

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`:
no kernels, no bf16, no program code, no program arrays; nothing is
imported from `kubedl_tpu`. Its own weights from the seed
(`benchmarks/weights_xing.py`), the cell's first steps on the same token
batches, block by block, one sequence at a time, a `jax.vjp` a block, the
heads in pieces of 2,048 tokens, AdamW on a block as soon as its gradient
is whole: the walk of `lfm2_ref.py`, whose AdamW, norm and control
arithmetic (`llama_ref.py`) it shares. One device.

The equations (d 3584, 32 heads, n = hc_mult = 4 at the published sizes):

  streams  X_0[i] = E[tokens] for i in 0..n-1 (assumed: replicated); X in R^{n x d} a token
  block    X <- HC_a(X; F = MLA(rmsnorm(.)));  X <- HC_f(X; F = FFN(rmsnorm(.)))
           two mappings a block, own leaves each (assumed); FFN = SwiGLU in the first
           first_k_dense_replace layers, else the expert layer
  HC(X; F) x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)       over all n*d, no learned scale (assumed)
           H~pre = a_pre (x~ P_pre) + b_pre in R^n;  H~post = a_post (x~ P_post) + b_post in R^n
           H~res = a_res mat(x~ P_res) + b_res in R^{n x n}  mat row-major: entry [i, j] is column i*n + j (assumed)
           H_pre = sigmoid(H~pre);  H_post = 2 sigmoid(H~post)
           M = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))   the clamp before the exponential (assumed)
           hc_sinkhorn_iters times: M <- M / (colsum(M) + hc_eps); M <- M / (rowsum(M) + hc_eps)
                                    columns first, hc_eps in both denominators (assumed); H_res = M
           u = sum_j H_pre[j] X[j];  y = F(u);  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y     all float32 (assumed)
  out      h = sum_i X_L[i] (assumed);  logits = rmsnorm(h) W_head                            untied head
  MLA(u)   cq = rmsnorm(u W_qa);  q = cq W_qb -> heads x [q_nope | q_rope]
           [ckv | kr] = u W_kva;  c = rmsnorm(ckv);  [k_nope | v] a head = c W_kvb
           k_rope = RoPE(kr), one for all heads;  q_h = [q_nope | RoPE(q_rope)];  k_h = [k_nope | k_rope]
           o = softmax(q k^T s + causal) v;  out = concat(o) W_o
           RoPE: half-split pairs (the repo's layout; a checkpoint's interleaved layout is a column
           permutation an importer would apply: assumed), YaRN over the rotary dims: per frequency a blend
           of 1/theta^(2i/dim) and the same over `factor` by the linear ramp between the two correction
           dims (beta_fast, beta_slow rotations within original_max_position_embeddings); cos and sin times
           mscale / mscale_all_dim
           s = (nope + rope)^-0.5 (0.1 mscale_all_dim ln factor + 1)^2
  experts  p = sigmoid(u W_r), float32 (router matrix and bias float32: assumed);  top-k of p + bias
           (the bias selects only and takes no gradient: assumed; n_group 1: no group limit)
           g = routed_scaling_factor p[chosen] / (sum p[chosen] + 1e-20)                      norm_topk_prob
           out = sum over chosen-and-held e of g_e SwiGLU_e(u) + SwiGLU_shared(u)
  MTP      position i, tokens t:  z_i = W_eh [rmsnorm_e(E[t_{i+1}]); rmsnorm_h(h_i)]  (this order: assumed)
           h_i the main stack's summed streams before its final norm (assumed); E and W_head the main
           model's; one expert block under HC on n replicated streams of z, summed (assumed)
           logits2_i = rmsnorm_s(block(z)_i) W_head predicts t_{i+2}
  loss     mean_i CE(logits_i, t_{i+1}) + mtp_loss_weight mean_{i with t_{i+2} fed} CE(logits2_i, t_{i+2})
           (0.3: DeepSeek-V3's first phase, assumed)

What is marked assumed is the papers' form, as remembered (mHC,
arXiv:2512.24880; Hyper-Connections, arXiv:2409.19606; DeepSeek-V3,
arXiv:2412.19437): config.json gives the sizes and not these choices.

`mode="int8"` and `mode="fp8"` are `llama_ref`'s controls: both operands
of every bf16 weight matmul rounded; the router and the mappings'
projections, which the configuration states in float32, keep their
precision. Faults plant a wrong step: "half_batch" (the second half of
the rows left out), "no_mix" (H_res the identity: streams that never
mix), "no_mtp" (the loss without its second term), "no_rope_key"
(k_rope zero: latent attention without its decoupled rotary key).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights_xing
from benchmarks.reference.lfm2_ref import ROUTE_LEAVES, route
from benchmarks.reference.llama_ref import adamw_update, make_mm, rms_norm

ROUTER_NORM_EPS = 1e-20
HEAD_PIECE = 2048  # tokens whose logits a head holds at a time
FAULTS = (None, "half_batch", "no_mix", "no_mtp", "no_rope_key")


# ---------------------------------------------------------------------------
# the mathematics
# ---------------------------------------------------------------------------


def yarn_inv_freq(cfg: Dict) -> np.ndarray:
    """The rotary dims' inverse frequencies under YaRN."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    plain = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if sc is None:
        return plain.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(np.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / sc["factor"] * ramp + plain * (1 - ramp)).astype(np.float32)


def yarn_magnitude(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def softmax_scale(cfg: Dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg["rope_scaling"]
    if sc is not None and sc.get("mscale_all_dim"):
        s *= yarn_magnitude(sc["factor"], sc["mscale_all_dim"]) ** 2
    return s


def rope(x, cfg: Dict):
    """Rotary embedding over [.., t, rope dims], half-split pairs."""
    t, half = x.shape[-2], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    sc = cfg["rope_scaling"]
    mag = 1.0 if sc is None else (yarn_magnitude(sc["factor"], sc["mscale"])
                                  / yarn_magnitude(sc["factor"], sc["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * mag, jnp.sin(ang) * mag
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(q, k, v, scale: float, q_block: int = 2048, head_group: int = 8):
    """Causal softmax attention, q and k [r, h, t, dk], v [r, h, t, dv]:
    plain softmax over the scores, a group of heads and a block of
    queries at a time over the keys up to the block's last query,
    recomputed in the backward pass, so that the [t, t] scores of all
    heads never exist together."""
    r, h, t, _ = q.shape
    g = head_group if h % head_group == 0 else h
    qb = q_block if t % q_block == 0 else t

    @jax.checkpoint
    def block(qblk, kblk, vblk):  # [r, g, qb, dk], keys 0 .. the block's end
        keys = kblk.shape[2]
        i = keys - qb + jnp.arange(qb, dtype=jnp.int32)[:, None]
        j = jnp.arange(keys, dtype=jnp.int32)[None, :]
        s = jnp.einsum("rgqd,rgkd->rgqk", qblk, kblk) * scale
        p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
        return jnp.einsum("rgqk,rgkd->rgqd", p, vblk)

    def group(qg, kg, vg):  # [r, g, t, .]
        return jnp.concatenate([
            block(qg[:, :, lo:lo + qb], kg[:, :, :lo + qb], vg[:, :, :lo + qb])
            for lo in range(0, t, qb)], axis=2)

    split = lambda x: x.reshape(r, h // g, g, t, x.shape[-1]).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda a: group(*a), (split(q), split(k), split(v)))
    return out.transpose(1, 0, 2, 3, 4).reshape(r, h, t, v.shape[-1])


def mla(u, p, cfg: Dict, mm, fault=None):
    """Latent attention over the normed input u [r, t, d]."""
    r, t, _ = u.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rd, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kvr = cfg["kv_lora_rank"]
    heads = lambda x, w: x.reshape(r, t, h, w).transpose(0, 2, 1, 3)
    q = heads(mm(rms_norm(mm(u, p["wq_a"]), p["q_a_norm"], eps), p["wq_b"]), nope + rd)
    kv_a = mm(u, p["wkv_a"])
    c = rms_norm(kv_a[..., :kvr], p["kv_a_norm"], eps)
    kv = heads(mm(c, p["wkv_b"]), nope + vd)
    k_rope = rope(kv_a[:, None, :, kvr:], cfg)  # [r, 1, t, rd], one for all heads
    if fault == "no_rope_key":
        k_rope = jnp.zeros_like(k_rope)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (r, h, t, rd))], axis=-1)
    o = attention(q, k, kv[..., nope:], softmax_scale(cfg))
    return mm(o.transpose(0, 2, 1, 3).reshape(r, t, h * vd), p["wo"])


def swiglu(u, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(u, w1)) * mm(u, w3), w2)


# rows an expert is given room for, over what even routing sends it: the
# seeded routers' fullest expert reads 1.5-1.9 times the mean (PERF.md)
EXPERT_ROOM = 4


def expert_ffn(u, p, cfg: Dict, mm):
    """(the held experts' part of the routed FFN plus the shared expert,
    [choices that flip under a bfloat16 input, rows that found no room]).
    The experts one at a time, each over the rows routed to it: gathered
    into an array with room for EXPERT_ROOM times an even share (all rows
    where that is more), computed, weighed and added back. A row that
    found no room would be left out, so the count of such rows is handed
    back and the walk refuses a step on which it is not 0."""
    k, first = cfg["num_experts_per_tok"], cfg.get("first_expert", 0)
    chosen, s = route(u, p["router"], p["router_bias"], k)
    picked = s * chosen
    g = picked / (jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    g = g * cfg["routed_scaling_factor"]
    y = swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], mm)
    d = u.shape[-1]
    rows = u.size // d
    room = min(rows, EXPERT_ROOM * -(-rows * k // s.shape[-1]))
    flat = jnp.concatenate([u.reshape(rows, d), jnp.zeros((1, d), u.dtype)])
    pad = lambda a: jnp.concatenate(  # [rows, held] -> [held, rows + 1], 0 for the row of no one
        [a.reshape(rows, -1)[:, first:first + p["w1"].shape[0]],
         jnp.zeros((1, p["w1"].shape[0]), a.dtype)]).T

    def one(carry, expert):
        routed, left_out = carry
        w1, w3, w2, mine, weight = expert
        idx = jnp.nonzero(mine[:rows], size=room, fill_value=rows)[0]
        out = swiglu(flat[idx], w1, w3, w2, mm)
        routed = routed.at[idx].add(weight[idx, None] * out)
        return (routed, left_out + jnp.maximum(jnp.sum(mine) - room, 0.0)), None

    (routed, left_out), _ = jax.lax.scan(
        one, (jnp.zeros((rows + 1, d), u.dtype), jnp.zeros((), jnp.float32)),
        (p["w1"], p["w3"], p["w2"], pad(chosen), pad(g)))
    y = y + routed[:rows].reshape(u.shape)
    rounded = u.astype(jnp.bfloat16).astype(jnp.float32)
    chosen_bf16, _ = route(rounded, p["router"], p["router_bias"], k)
    flips = jnp.sum(chosen * (1.0 - chosen_bf16))
    return y, jax.lax.stop_gradient(jnp.stack([flips, left_out]))


def sinkhorn(logits, cfg: Dict):
    """The Sinkhorn-Knopp loop, written out: [.., n, n] -> H_res."""
    eps = cfg["hc_eps"]
    m = jnp.exp(jnp.clip(logits, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))

    def both(m, _):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)  # columns
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps), None  # rows

    return jax.lax.scan(both, m, None, length=cfg["hc_sinkhorn_iters"])[0]


def hyper(x, hc, cfg: Dict, branch, fault=None):
    """One sublayer under hyper-connections over the streams x
    [r, n, t, d] (the streams a leading axis, so that a stream is a whole
    [t, d] array): (the streams after it, branch's extra, mean
    off-diagonal mass of H_res). `branch(u) -> (y, extra)`."""
    r, n, t, d = x.shape
    rms = jnp.sqrt(jnp.mean(x * x, axis=(1, 3))[..., None] + cfg["hc_eps"])  # [r, t, 1]

    def project(p):  # x~ P: stream j meets rows j*d .. (j+1)*d of P
        rows = p.reshape(n, d, p.shape[-1])
        return sum(jnp.matmul(x[:, j], rows[j]) for j in range(n)) / rms

    pre = jax.nn.sigmoid(hc["a_pre"] * project(hc["p_pre"]) + hc["b_pre"])
    post = 2.0 * jax.nn.sigmoid(hc["a_post"] * project(hc["p_post"]) + hc["b_post"])
    res = sinkhorn(hc["a_res"] * project(hc["p_res"]).reshape(r, t, n, n)
                   + hc["b_res"], cfg)
    if fault == "no_mix":
        res = jnp.broadcast_to(jnp.eye(n, dtype=x.dtype), res.shape)
    u = sum(pre[..., j, None] * x[:, j] for j in range(n))
    y, extra = branch(u)
    out = jnp.stack([
        sum(res[..., i, j, None] * x[:, j] for j in range(n)) + post[..., i, None] * y
        for i in range(n)], axis=1)
    off = 1.0 - jnp.mean(jnp.sum(jnp.diagonal(res, axis1=-2, axis2=-1), axis=-1)) / n
    return out, extra, jax.lax.stop_gradient(off)


def layer_fwd(x, p, cfg: Dict, mm, fault=None):
    """One block over the streams x [r, n, t, d]: (streams, [flipped
    choices, summed off-diagonal mass of its two H_res, routed rows that
    found no room])."""
    eps = cfg["rms_norm_eps"]
    zero = jnp.zeros((), jnp.float32)
    x, _, off_a = hyper(
        x, p["hc_mixer"], cfg,
        lambda u: (mla(rms_norm(u, p["attn_norm"], eps), p, cfg, mm, fault), zero), fault)
    if "moe" in p:
        ffn = lambda u: expert_ffn(rms_norm(u, p["mlp_norm"], eps), p["moe"], cfg, mm)
    else:
        ffn = lambda u: (swiglu(rms_norm(u, p["mlp_norm"], eps),
                                p["w1"], p["w3"], p["w2"], mm), jnp.zeros((2,)))
    x, routing, off_f = hyper(x, p["hc_mlp"], cfg, ffn, fault)
    return x, jnp.stack([routing[0], off_a + off_f, routing[1]])


def head_nll(x, norm_w, head, targets, weights, cfg: Dict, mm):
    """Summed weighted negative log likelihood of x [r, t, d] under
    rmsnorm and the head, in pieces of HEAD_PIECE tokens, each recomputed
    in the backward pass."""
    r, t, d = x.shape
    piece = HEAD_PIECE if t % HEAD_PIECE == 0 else t
    n = t // piece

    @jax.checkpoint
    def one(xp, tp, wp):
        logits = mm(rms_norm(xp, norm_w, cfg["rms_norm_eps"]), head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, tp[..., None], axis=-1)[..., 0] * wp)

    pieces = lambda a: jnp.moveaxis(a.reshape((r, n, piece) + a.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(lambda a: one(*a), (pieces(x), pieces(targets), pieces(weights))))


def streams_of(x, cfg: Dict):
    """[r, t, d] -> the streams [r, n, t, d], each a copy of x."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg["hc_mult"]) + x.shape[1:])


def mtp_streams(x, embed, m, tokens, cfg: Dict, mm):
    """The MTP module's input streams from the last block's streams x
    [r, n, t, d] and the fed tokens: n copies of
    W_eh [rmsnorm_e(E[t_{i+1}]); rmsnorm_h(h_i)]."""
    eps = cfg["rms_norm_eps"]
    h, e = jnp.sum(x, axis=1), embed[tokens[:, 1:]]
    z = mm(jnp.concatenate([rms_norm(e, m["embed_norm"], eps),
                            rms_norm(h, m["hidden_norm"], eps)], axis=-1), m["w_eh"])
    return streams_of(z, cfg)


def second_next(tokens):
    """(targets, weights) of the module's head over the fed tokens
    [r, t + 1]: position i's target is token i + 2, and the last
    position, whose second-next token was not fed, weighs nothing."""
    r, t = tokens.shape[0], tokens.shape[1] - 1
    targets = jnp.concatenate([tokens[:, 2:], jnp.zeros((r, 1), tokens.dtype)], axis=1)
    return targets, jnp.ones((r, t), jnp.float32).at[:, -1].set(0.0)


def tail(x, tp, tokens, cfg: Dict, mm, fault=None):
    """From the last block's streams x [r, n, t, d] and the fed tokens
    [r, t + 1]: (summed nll of the next token, summed nll of the
    second-next token through the MTP module (0 without one), the
    module's block's [flips, off-diagonal mass, rows left out])."""
    r, t = tokens.shape[0], tokens.shape[1] - 1
    h = jnp.sum(x, axis=1)
    ones = jnp.ones((r, t), jnp.float32)
    nll = head_nll(h, tp["final_norm"], tp["lm_head"], tokens[:, 1:], ones, cfg, mm)
    if "mtp" not in tp or fault == "no_mtp":
        return nll, jnp.zeros((), jnp.float32), jnp.zeros((3,), jnp.float32)
    m = tp["mtp"]
    zs, aux = layer_fwd(mtp_streams(x, tp["embed"], m, tokens, cfg, mm), m["block"], cfg, mm, fault)
    targets, fed = second_next(tokens)
    nll2 = head_nll(jnp.sum(zs, axis=1), m["final_norm"], tp["lm_head"], targets, fed, cfg, mm)
    return nll, nll2, aux


def mtp_weight(cfg: Dict) -> float:
    return float(cfg["mtp_loss_weight"]) if cfg["num_nextn_predict_layers"] else 0.0


def tail_params(params: Dict) -> Dict:
    return {k: params[k] for k in ("final_norm", "lm_head", "embed", "mtp") if k in params}


def loss_and_counters(params, tokens, cfg: Dict, mm=None, fault=None):
    """The whole model's loss in one piece, for sizes at which everything
    fits at once (the tests): what the walk below computes block by
    block. Counters: ce, mtp_ce, hc_res_offdiag (mean over the mappings)."""
    mm = mm or make_mm("f32")
    with jax.default_matmul_precision("highest"):
        rows, t = tokens.shape[0], tokens.shape[1] - 1
        x = streams_of(params["embed"][tokens[:, :-1]], cfg)
        off = 0.0
        for p in params["layers"]:
            x, aux = layer_fwd(x, p, cfg, mm, fault)
            off += aux[1]
        nll, nll2, aux = tail(x, tail_params(params), tokens, cfg, mm, fault)
        ce, ce2 = nll / (rows * t), nll2 / (rows * (t - 1))
        maps = 2 * (len(params["layers"]) + ("mtp" in params and fault != "no_mtp"))
        return ce + mtp_weight(cfg) * ce2, {
            "ce": ce, "mtp_ce": ce2, "hc_res_offdiag": (off + aux[1]) / maps}


def loss(params, tokens, cfg: Dict, mm=None, fault=None):
    return loss_and_counters(params, tokens, cfg, mm, fault)[0]


# ---------------------------------------------------------------------------
# the walk: block by block, one sequence at a time
# ---------------------------------------------------------------------------


def _highest(fn, **kw):
    """`fn` jitted, traced under the highest matmul precision."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(run, **kw)


class Reference:
    """Follows a cell's first steps from the seed, as `lfm2_ref.Reference`
    does: `run(batches, n)` returns every loss, the per-leaf norms of the
    first gradient and of the parameters' change over the n steps,
    `route_flip_share`, `selection_leaves`, `counters` (the first step's
    ce, mtp_ce and hc_res_offdiag, as the program's step counts them) and
    `seconds`: where the run's time went, by phase."""

    def __init__(self, cfg: Dict, cell: Dict, seed: int, devices,
                 mode: str = "f32", fault: Optional[str] = None):
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r} is not planted here {FAULTS[1:]}")
        self.cfg, self.cell, self.seed, self.fault = cfg, cell, seed, fault
        self.opt = cell["optimizer"]
        self.mm = make_mm(mode)
        self.device = list(devices)[0]
        self.block = int(cell["reference"]["row_block"])
        self.make_weights = weights_xing.maker(cfg)
        with jax.default_device(self.device):
            self.params = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), t))(self.make_weights(seed))
        # gradients of earlier steps, for AdamW's moments, and the blocks'
        # saved inputs (four float32 streams a token): on the host where
        # they and the parameters would crowd a chip
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
        w2 = mtp_weight(cfg)
        self._embed = jax.jit(lambda table, ids: streams_of(table[ids], cfg))
        self._layer = _highest(lambda x, p: layer_fwd(x, p, cfg, mm, fault))

        def layer_back(x, p, dy):
            _, vjp, _ = jax.vjp(
                lambda x_, p_: layer_fwd(x_, p_, cfg, mm, fault), x, p, has_aux=True)
            return vjp(dy)

        self._layer_back = _highest(layer_back)

        def tail_loss(x, tp, tokens, inv, inv2):
            nll, nll2, aux = tail(x, tp, tokens, cfg, mm, fault)
            return nll * inv + w2 * nll2 * inv2, (nll, nll2, aux)

        self._tail = _highest(tail_loss)  # a step whose loss alone is wanted
        # the tail of a full step, in pieces, so that the module's block goes
        # through the blocks' own two programs and both heads through one:
        # a head over summed streams, and the module's input streams
        self._head_back = _highest(
            lambda xs, norm_w, head, targets, weights, scale: jax.value_and_grad(
                lambda a, b, c: scale * head_nll(
                    jnp.sum(a, axis=1), b, c, targets, weights, cfg, mm),
                argnums=(0, 1, 2))(xs, norm_w, head))
        self._mtp_in = _highest(
            lambda x, embed, m, tokens: mtp_streams(x, embed, m, tokens, cfg, mm))
        self._mtp_in_back = _highest(lambda x, embed, m, tokens, dzs: jax.vjp(
            lambda a, b, c: mtp_streams(a, b, c, tokens, cfg, mm), x, embed, m)[1](dzs))
        # every stream started as the embedding: its gradient is their sum
        self._embed_back = jax.jit(lambda ids, dx, like: jnp.zeros_like(like).at[ids].add(
            jnp.sum(dx, axis=1)))
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
        if self.fault == "half_batch":
            rows = max(rows // 2, 1)
        blk = min(self.block, rows)
        return [(lo, min(lo + blk, rows)) for lo in range(0, rows, blk)], rows

    def _forward(self, tokens: np.ndarray, keep: bool):
        """Per block of rows: the fed tokens, every layer's input (kept
        only for a full step), the last layer's streams, and the layers'
        summed off-diagonal mass."""
        blocks, rows = self._blocks(tokens)
        acts, off = [], 0.0
        for lo, hi in blocks:
            fed = self._put(tokens[lo:hi])
            x = self._embed(self.params["embed"], fed[:, :-1])
            inputs = []
            for p in self.params["layers"]:
                if keep:
                    with self._timed("inputs_to_host_s"):
                        inputs.append(jax.device_get(x) if self.history_on_host else x)
                with self._timed("forward_s") as done:
                    x, aux = self._layer(x, p)
                    done.append(x)
                if keep:
                    off += float(aux[1]) * (hi - lo)
                    self._count_flips(p, aux, fed)
            acts.append([fed, inputs, x])
        return blocks, rows, acts, off

    def _count_flips(self, p, aux, fed):
        if float(aux[2]):
            raise RuntimeError(
                f"{float(aux[2]):.0f} routed rows found no room in an expert's "
                f"{EXPERT_ROOM} even shares (xing_ref.EXPERT_ROOM): the step "
                "would leave them out")
        if "moe" in p:
            self.flips += float(aux[0])
            self.pairs += float((fed.shape[1] - 1) * fed.shape[0]
                                * self.cfg["num_experts_per_tok"])

    def _scales(self, rows: int, t: int):
        return np.float32(1.0 / (rows * t)), np.float32(1.0 / (rows * (t - 1)))

    def loss_only(self, tokens: np.ndarray) -> float:
        blocks, rows, acts, _ = self._forward(tokens, keep=False)
        inv, inv2 = self._scales(rows, tokens.shape[1] - 1)
        tp = tail_params(self.params)
        return sum(float(self._tail(x, tp, fed, inv, inv2)[0]) for fed, _, x in acts)

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

    def _tail_back(self, x, fed, inv, inv2, has_mtp: bool):
        """The tail of one block of rows, backwards: ((next token's scaled
        loss, the module's), the gradient of the last block's streams,
        the gradients of the tail's leaves, the module's block's aux)."""
        p = self.params
        ones = jnp.ones((fed.shape[0], fed.shape[1] - 1), jnp.float32)
        part, (dx, d_norm, d_head) = self._head_back(
            x, p["final_norm"], p["lm_head"], fed[:, 1:], ones, inv)
        grads = {"final_norm": d_norm, "lm_head": d_head,
                 "embed": jnp.zeros_like(p["embed"])}
        if "mtp" not in p:
            return (float(part), 0.0), dx, grads, None
        m = p["mtp"]
        m_in = {k: m[k] for k in ("embed_norm", "hidden_norm", "w_eh")}
        if not has_mtp:  # the fault: the loss without its second term
            grads["mtp"] = jax.tree_util.tree_map(jnp.zeros_like, m)
            return (float(part), 0.0), dx, grads, None
        zs0 = self._mtp_in(x, p["embed"], m_in, fed)
        zs1, aux = self._layer(zs0, m["block"])
        targets, weights = second_next(fed)
        part2, (dzs1, d_norm2, d_head2) = self._head_back(
            zs1, m["final_norm"], p["lm_head"], targets, weights,
            np.float32(mtp_weight(self.cfg)) * inv2)
        del zs1
        dzs0, g_block = self._layer_back(zs0, m["block"], dzs1)
        dx2, d_embed, d_in = self._mtp_in_back(x, p["embed"], m_in, fed, dzs0)
        grads.update({"lm_head": self._add(d_head, d_head2), "embed": d_embed,
                      "mtp": {**d_in, "block": g_block, "final_norm": d_norm2}})
        return (float(part), float(part2)), self._add(dx, dx2), grads, aux

    def full_step(self, tokens: np.ndarray) -> Dict:
        """Loss and gradient of one batch, then AdamW on every leaf. A
        block is updated as soon as its gradient is whole; the embedding,
        which the MTP module reads too, last."""
        blocks, rows, acts, off = self._forward(tokens, keep=True)
        n_layers, t = len(self.params["layers"]), tokens.shape[1] - 1
        inv, inv2 = self._scales(rows, t)
        grads_now = {"layers": [None] * n_layers}
        grad_sq = {"layers": [None] * n_layers}

        total, nll, nll2, dxs, g_tail = 0.0, 0.0, 0.0, [], None
        has_mtp = "mtp" in self.params and self.fault != "no_mtp"
        for act in acts:
            with self._timed("tail_s") as done:
                part, dx, dtp, aux = self._tail_back(act[2], act[0], inv, inv2, has_mtp)
                done.append(dx)
            act[2] = None
            total, nll, nll2 = total + sum(part), nll + part[0], nll2 + part[1]
            if has_mtp:
                off += float(aux[1]) * act[0].shape[0]
                self._count_flips(self.params["mtp"]["block"], aux, act[0])
            dxs.append(dx)
            g_tail = self._acc(g_tail, dtp)
        nll, nll2 = nll / float(inv), nll2 / (float(inv2) * mtp_weight(self.cfg) or 1.0)
        g_embed = g_tail.pop("embed")
        for name in list(g_tail):
            self._settle(grads_now, grad_sq, name, g_tail.pop(name))

        for i in reversed(range(n_layers)):
            g_layer = None
            for b, act in enumerate(acts):
                with self._timed("inputs_from_host_s") as done:
                    x = self._put(act[1][i])
                    done.append(x)
                with self._timed("layers_back_s") as done:
                    dxs[b], g = self._layer_back(x, self.params["layers"][i], dxs[b])
                    done.append(dxs[b])
                act[1][i] = x = None
                g_layer = self._acc(g_layer, g)
            with self._timed("adamw_and_history_s") as done:
                self._settle(grads_now, grad_sq, "layers", g_layer, index=i)
                done.append(self.params["layers"][i])
            del g_layer

        for b, act in enumerate(acts):
            g_embed = self._add(g_embed, self._embed_back(
                act[0][:, :-1], dxs[b], self.params["embed"]))
        self._settle(grads_now, grad_sq, "embed", g_embed)
        self.history.append(grads_now)
        maps = 2 * (n_layers + has_mtp)
        return {"loss": total, "grad_sq": jax.device_get(grad_sq),
                "counters": {"ce": nll * float(inv), "mtp_ce": nll2 * float(inv2),
                             "hc_res_offdiag": off / (rows * maps)}}

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
