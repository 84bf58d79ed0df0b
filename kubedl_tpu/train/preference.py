"""Preference optimization (DPO) — post-training on the same machinery.

Direct Preference Optimization (Rafailov et al., 2023): given pairs of
(chosen, rejected) continuations for a shared prompt, push the policy's
log-ratio over a frozen reference model apart by the preference margin:

    L = -log sigmoid(beta * ((pi_c - ref_c) - (pi_r - ref_r)))

Built the same TPU-first way as pretraining (train/trainer.py): pure
loss function over the Llama backbone, sharded through
parallel/train_step.make_train_step, so dp/fsdp/tp meshes and grad
accumulation apply unchanged. Reference logprobs are computed ONCE per
batch outside the gradient (stop-gradient by construction) with the
same forward — no second backward, no reference optimizer state — and
the reference tree is SHARDED like the policy, passed as a jit argument
(a closure capture would bake a replicated copy into the executable).

MoE configs keep their router load-balancing term: the policy forward
returns the aux loss and dpo_loss adds `moe_aux_coef * aux`, matching
pretraining's llama.loss_fn.

`config.ce_chunks > 1` computes per-token target logprobs with an
online-logsumexp over vocab chunks instead of materializing the
[b, T, V] f32 log-softmax — the same memory knob the pretraining CE
uses, indispensable at DPO's 2x-batch (pair) footprint.

Batch layout: tokens [b, 2, T] int32 (dim 1 = chosen|rejected),
`prompt_lens` [b] marking where continuations start — prompt positions
are excluded from the sequence logprob, pad positions (after
`seq_lens`) likewise.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from kubedl_tpu.models import llama

NEG_INF = -1e30


def _target_logprobs_chunked(x, params, config, targets):
    """log p(targets) at each position without [.., V] logits: online
    logsumexp over `config.ce_chunks` vocab chunks + an in-chunk gather
    of the target logit. x [n, t, d] f32-castable, targets [n, t]."""
    head = llama._head_matrix(params, config)  # [d, V]
    # x arrives PRE-norm from the backbone; the head path applies the
    # final RMSNorm first (llama._lm_head does the same)
    x = llama.rms_norm(x, params["final_norm"], config.rms_eps,
                       config.norm_offset)
    v = head.shape[1]
    chunks = config.ce_chunks
    csize = -(-v // chunks)
    m = jnp.full(targets.shape, NEG_INF, jnp.float32)
    s = jnp.zeros(targets.shape, jnp.float32)
    tgt = jnp.zeros(targets.shape, jnp.float32)
    for i in range(chunks):
        lo = i * csize
        hi = min(lo + csize, v)
        logits = jnp.einsum(
            "ntd,dv->ntv", x, head[:, lo:hi].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        if config.final_logit_softcap:
            # elementwise cap per chunk == capping the full logits; the
            # policy/reference logprobs must match the distribution the
            # decode stack (capped _lm_head) actually samples from
            logits = llama.softcap(logits, config.final_logit_softcap)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1)
        m = m_new
        idx = targets - lo
        in_chunk = (idx >= 0) & (idx < hi - lo)
        picked = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, hi - lo - 1)[..., None], axis=-1)[..., 0]
        tgt = jnp.where(in_chunk, picked, tgt)
    return tgt - (m + jnp.log(s))


def _pair_logprobs(
    params: Dict,
    tokens: jax.Array,  # [b, 2, T]
    prompt_lens: jax.Array,  # [b]
    seq_lens: jax.Array,  # [b, 2]
    config: llama.LlamaConfig,
    mesh=None,
    rules=None,
) -> Tuple[jax.Array, jax.Array]:
    """([b, 2] continuation logprobs, MoE aux loss). THE single place the
    [b, 2, T] -> [2b, T] pair layout is flattened — policy and reference
    must share it or chosen/rejected silently misalign."""
    b, _, t = tokens.shape
    flat = tokens.reshape(b * 2, t)
    lp = sequence_logprobs(
        params, flat, jnp.repeat(prompt_lens, 2), seq_lens.reshape(-1),
        config, mesh=mesh, rules=rules, with_aux=True,
    )
    lp, aux = lp
    return lp.reshape(b, 2), aux


def sequence_logprobs(
    params: Dict,
    tokens: jax.Array,  # [n, T] int32
    prompt_lens: jax.Array,  # [n] int32 — continuation starts here
    seq_lens: jax.Array,  # [n] int32 — true length incl. prompt
    config: llama.LlamaConfig,
    mesh=None,
    rules=None,
    with_aux: bool = False,
    per_token: bool = False,
):
    """Sum log p(token_i | <i) over continuation positions — [n] f32
    (+ the MoE aux loss when with_aux). per_token=True skips the sum and
    returns ([n, T-1] logprobs, [n, T-1] f32 continuation mask) instead —
    the shape GRPO's per-token importance ratios need (train/rl.py)."""
    rules_ = rules
    x, aux, _ = llama._backbone(params, tokens, config, mesh, rules_ or
                                llama.ShardingRules())
    targets = tokens[:, 1:]
    head_is_plain = isinstance(
        llama._head_matrix(params, config), jax.Array)
    if config.ce_chunks > 1 and head_is_plain:
        pred = _target_logprobs_chunked(x[:, :-1], params, config, targets)
    else:
        logits = llama._lm_head(x, params, config).astype(jnp.float32)
        logps = jax.nn.log_softmax(logits, axis=-1)
        pred = jnp.take_along_axis(
            logps[:, :-1], targets[..., None], axis=-1)[..., 0]  # [n, T-1]
    pos = jnp.arange(tokens.shape[1] - 1)[None, :]
    # target token at position i+1 belongs to the continuation iff
    # i+1 >= prompt_len and i+1 < seq_len
    mask = (pos + 1 >= prompt_lens[:, None]) & (pos + 1 < seq_lens[:, None])
    if per_token:
        out = (pred, mask.astype(jnp.float32))
    else:
        out = jnp.sum(pred * mask, axis=-1)
    return (out, aux) if with_aux else out


def dpo_loss(
    params: Dict,
    ref_logprobs: jax.Array,  # [b, 2] — precomputed reference logprobs
    tokens: jax.Array,  # [b, 2, T]
    prompt_lens: jax.Array,  # [b]
    seq_lens: jax.Array,  # [b, 2]
    config: llama.LlamaConfig,
    beta: float = 0.1,
    mesh=None,
    rules=None,
) -> Tuple[jax.Array, Dict]:
    """(scalar loss, metrics) — metrics carry the implicit reward margin
    and preference accuracy, the numbers worth plotting."""
    lp, aux = _pair_logprobs(
        params, tokens, prompt_lens, seq_lens, config, mesh=mesh, rules=rules)
    pi_ratio = lp[:, 0] - lp[:, 1]
    ref_ratio = ref_logprobs[:, 0] - ref_logprobs[:, 1]
    margin = beta * (pi_ratio - ref_ratio)
    loss = jnp.mean(-jax.nn.log_sigmoid(margin))
    if config.n_experts > 0:
        # router balance term, same coefficient as pretraining — dropping
        # it for the whole DPO phase invites expert collapse
        loss = loss + config.moe_aux_coef * aux
    metrics = {
        "reward_margin": jnp.mean(margin),
        "preference_accuracy": jnp.mean((margin > 0).astype(jnp.float32)),
        "chosen_logprob": jnp.mean(lp[:, 0]),
        "rejected_logprob": jnp.mean(lp[:, 1]),
    }
    return loss, metrics


def make_dpo_step(
    ref_params: Dict,
    config: llama.LlamaConfig,
    tx,
    mesh,
    rules=None,
    beta: float = 0.1,
    param_spec_tree=None,
    accum_steps: int = 1,
):
    """(init_state, ref_logprob_fn, dpo_step) over the mesh.

    `ref_logprob_fn(batch) -> [b, 2]` runs the FROZEN reference once per
    batch (jitted, no grad); `dpo_step(state, batch_with_ref_lp)` is the
    donated sharded update. Splitting the two keeps the reference
    forward out of the differentiated graph entirely.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubedl_tpu.parallel.mesh import ShardingRules
    from kubedl_tpu.parallel.train_step import make_train_step

    rules = rules or ShardingRules()
    if param_spec_tree is None:
        param_spec_tree = llama.param_specs(config, rules)
    param_sharding = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    # the reference tree is an ARGUMENT with explicit shardings — a jit
    # closure would bake a fully-replicated copy into the executable,
    # OOMing exactly at the scales DPO targets
    ref_sharded = jax.device_put(ref_params, param_sharding)

    @jax.jit
    def _ref_fn(ref, batch):
        tokens, prompt_lens, seq_lens = batch
        lp, _ = _pair_logprobs(
            ref, tokens, prompt_lens, seq_lens, config, mesh=mesh, rules=rules)
        return lp

    def ref_logprob_fn(batch):
        return _ref_fn(ref_sharded, batch)

    def loss_fn(params, batch):
        tokens, prompt_lens, seq_lens, ref_lp = batch
        return dpo_loss(
            params, ref_lp, tokens, prompt_lens, seq_lens, config,
            beta=beta, mesh=mesh, rules=rules,
        )

    batch_spec = (
        rules.spec("batch", None, None),  # tokens [b, 2, T]
        rules.spec("batch"),              # prompt_lens [b]
        rules.spec("batch", None),        # seq_lens [b, 2]
        rules.spec("batch", None),        # ref logprobs [b, 2]
    )
    init_state, train_step = make_train_step(
        loss_fn, tx, mesh, param_spec_tree, batch_spec, rules,
        accum_steps=accum_steps, has_aux=True,
    )
    return init_state, ref_logprob_fn, train_step
