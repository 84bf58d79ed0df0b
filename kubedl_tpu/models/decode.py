"""KV-cache autoregressive decoding for the Llama family.

Inference companion to models/llama.py, built the XLA way:

  * static-shape caches ([b, kv_heads, max_len, head_dim]); uniform
    batches carry ONE scalar length (single-slice cache writes — the
    fast path), ragged (right-padded) batches carry per-row `lengths`
    [b], each row masking and writing at its own position;
  * one-pass prefill: the whole [b, t] prompt runs through a single
    full-sequence forward (large MXU matmuls, flash attention), writing
    every K/V row at once — not a token-at-a-time loop;
  * a `lax.scan` token loop for generation — no data-dependent Python
    control flow, so the whole generation compiles once and replays from
    the HLO cache for any prompt of the same padded shape;
  * attention over the cache is one masked dot product (decode is
    bandwidth-bound at t_q = 1; a fused kernel buys nothing there).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kubedl_tpu.models.llama import (
    LlamaConfig,
    _lm_head,
    _mlp_block,
    _proj,
    _rope,
    rms_norm,
)

NEG_INF = -1e30


def init_kv_cache(
    config: LlamaConfig,
    batch: int,
    max_len: int,
    uniform: bool = False,
    kv_dtype: Optional[str] = None,
    ring: bool = False,
) -> Dict:
    """Per-layer K/V buffers (model dtype) + write positions.

    `lengths` [b] tracks each row's number of valid cache entries, so a
    batch may mix prompt lengths (right-padded): row i attends only
    k_pos < lengths[i] and writes its next token at position lengths[i].

    uniform=True stores ONE scalar length for the whole batch instead:
    every row then writes at the same position, which lowers to a single
    dynamic_update_slice instead of a per-row scatter — measured 2.2x
    decode throughput at 150M/b8 on v5e, because the scatter write was
    costing more than the weight reads. generate() picks this mode
    automatically when no per-row lengths are passed. The mode is a
    trace-time (shape) property, so both variants compile once each.

    kv_dtype="int8" stores K/V as int8 with a per-position-per-head
    scale (amax/127 over head_dim) in extra "ks"/"vs" buffers: half the
    cache HBM and half the per-token cache read at long contexts. The
    scales fold EXACTLY into the attention einsums (scores scale per key
    position; value scales fold into the softmax weights), so a
    dequantized cache never materializes.

    K/V are LISTS of per-layer arrays, not a stacked [n_layers, ...]
    tensor: in the scan token loop each leaf is its own donated carry
    buffer, so the per-step write is in place — a stacked cache forced
    an unstack/update/restack that recopied cache memory every token.

    ring=True (sliding-window models only): the buffers hold just the
    WINDOW most recent positions, [b, h, window, d], written at
    `lengths % window` — O(window) HBM instead of O(max_len), the
    long-context serving memory win on top of the window-narrowed read.
    `lengths` still counts TOTAL tokens (it may exceed the buffer), and
    the dict carries a "ring" marker key so decode paths pick the
    wrapped-position attention (a pytree-STRUCTURE property: ring and
    flat caches compile separately, like uniform/ragged). Single-token
    decode only — block verify would need window+T-1 rows."""
    config.require_kv_state_only("the cached decode path (init_kv_cache)")
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
    if ring:
        if not config.sliding_window:
            raise ValueError("ring=True requires config.sliding_window")
        if config.layer_windows is not None:
            # ring buffers are sized by ONE window shared across the
            # per-layer K/V lists; per-layer windows would need
            # per-layer buffer shapes and wrap formulas
            raise ValueError("ring=True is unsupported with layer_windows")
        if max_len < int(config.sliding_window):
            # a buffer below the window would wrap away keys the window
            # mask still expects — silent divergence. A cache this small
            # doesn't benefit from ring anyway; use a flat cache.
            raise ValueError(
                f"ring cache needs max_len >= sliding_window "
                f"({config.sliding_window}), got {max_len}; drop ring=True")
        max_len = int(config.sliding_window)
    shape = (batch, config.n_kv_heads, max_len, config.head_dim)
    store_dt = jnp.int8 if kv_dtype == "int8" else config.dtype
    cache = {
        "k": [jnp.zeros(shape, store_dt) for _ in range(config.n_layers)],
        "v": [jnp.zeros(shape, store_dt) for _ in range(config.n_layers)],
        "lengths": (jnp.zeros((), jnp.int32) if uniform
                    else jnp.zeros((batch,), jnp.int32)),
    }
    if kv_dtype == "int8":
        sshape = (batch, config.n_kv_heads, max_len)
        cache["ks"] = [jnp.ones(sshape, jnp.bfloat16) for _ in range(config.n_layers)]
        cache["vs"] = [jnp.ones(sshape, jnp.bfloat16) for _ in range(config.n_layers)]
    if ring:
        cache["ring"] = jnp.zeros((), jnp.int32)  # structure marker only
    return cache


def _ring_positions(total, L):
    """Absolute position held by each ring slot, given `total` tokens seen.

    Slot j holds the LAST write whose index ≡ j (mod L): that is
    p(j) = total-1 - ((total-1 - j) mod L); slots never written yet
    (total < L) come out negative and must be masked. `total` is [b]
    (or scalar); returns [b, L] (or [L])."""
    total = jnp.asarray(total)
    j = jnp.arange(L)
    last = total[..., None] - 1  # broadcast over slots
    return last - jnp.mod(last - j, L)


def _quantize_kv(x):
    """[b, h, t, d] -> (int8 codes, [b, h, t] bf16 scales); amax/127 over d.

    Like quant.quantize, the scale is rounded to its stored bf16 value
    BEFORE the codes are computed, so the codes compensate the scale's
    own rounding; bf16 scales keep the int8 cache read at ~half the bf16
    cache read (f32 scales would cost 53% at head_dim=64)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.bfloat16)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / s.astype(jnp.float32)[..., None]),
        -127, 127,
    )
    return q.astype(jnp.int8), s


def _attend_cached(q, ck, cv, limits, n_rep, k_scale=None, v_scale=None,
                   window=None, ring_total=None, softcap=None):
    """q [b,hq,tq,d] vs cache [b,hkv,L,d]; query t in row i attends cache
    positions < its limit. `limits` is [b] (per-row limit, tq == 1) or
    [b, tq] (per-row per-query — the block verify path, where query t
    may see t more positions than query 0).

    GQA runs as a grouped einsum (q reshaped to [b,hkv,g,tq,d]) instead
    of jnp.repeat-ing the cache — the cache read is the bandwidth bill
    here and must stay at hkv heads. Scores accumulate in f32 on bf16
    operands (preferred_element_type), so the cache is never upcast in
    HBM.

    int8 caches pass per-position scales ([b,hkv,L]); the K scale
    multiplies the scores (q . (s*k) == s * (q . k)) and the V scale
    folds into the softmax weights (sum_k p_k*(s_k*v_k) ==
    sum_k (p_k*s_k)*v_k) — exact, no dequantized cache tensor.

    With a sliding window, the cache READ is first narrowed to the
    window + tq - 1 rows any query can attend (per-row dynamic slice):
    decode is bandwidth-bound, so at long contexts the per-token cache
    traffic scales with the WINDOW, not max_len. Ring caches
    (init_kv_cache(ring=True)) shrink the BUFFERS to O(window) too;
    `ring_total` then carries the per-row total token count and slot
    positions are recovered modulo the buffer length."""
    b, hq, tq, d = q.shape
    hkv, L = ck.shape[1], ck.shape[2]
    cd = q.dtype  # compute dtype; int8 codes convert on the operand read
    limits = jnp.asarray(limits)
    if limits.ndim == 1:
        lim = limits[:, None]  # [b] -> per-row, tq must be 1
    else:
        lim = limits  # [b, tq]
    if ring_total is not None:
        # ring cache: L == window rows hold the latest positions wrapped
        # at lengths % L; recover each slot's ABSOLUTE position so the
        # standard window mask applies; never-written slots (negative
        # position) are masked out
        totals = jnp.broadcast_to(  # scalar (uniform) or [b] (ragged)
            jnp.reshape(jnp.asarray(ring_total), (-1,)), (b,))
        k_pos = _ring_positions(totals, L)
    elif window is not None and L > window + tq - 1:
        ws = window + tq - 1  # static: covers every query's window
        start = jnp.clip(lim[:, 0] - window, 0, L - ws)  # [b]

        def rows(cache_leaf, axis):
            return jax.vmap(
                lambda leaf, s0: jax.lax.dynamic_slice_in_dim(leaf, s0, ws, axis=axis)
            )(cache_leaf, start)

        ck = rows(ck, axis=1)
        cv = rows(cv, axis=1)
        if k_scale is not None:
            k_scale = rows(k_scale, axis=1)
        if v_scale is not None:
            v_scale = rows(v_scale, axis=1)
        k_pos = start[:, None] + jnp.arange(ws)[None, :]  # [b, ws] absolute
    else:
        k_pos = jnp.broadcast_to(jnp.arange(L)[None, :], (b, L))
    qg = q.reshape(b, hkv, n_rep, tq, d)  # group queries under their kv head
    s = jnp.einsum(
        "bhgtd,bhkd->bhgtk", qg, ck.astype(cd), preferred_element_type=jnp.float32
    )
    if k_scale is not None:
        s = s * k_scale[:, :, None, None, :]
    s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap  # Gemma-2 attn softcapping
    attend = k_pos[:, None, None, None, :] < lim[:, None, None, :, None]
    if window is not None:
        # sliding window: the query at position lim-1 sees keys in
        # (lim-1-window, lim-1], i.e. k_pos >= lim - window
        attend &= k_pos[:, None, None, None, :] >= (
            lim[:, None, None, :, None] - window)
    if ring_total is not None:
        attend &= k_pos[:, None, None, None, :] >= 0  # unwritten ring slots
    s = jnp.where(attend, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, :, None, None, :]
    out = jnp.einsum(
        "bhgtk,bhkd->bhgtd", p.astype(cd), cv.astype(cd),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, hq, tq, d)


def decode_step(
    params: Dict,
    token: jax.Array,  # [b] int32
    cache: Dict,
    config: LlamaConfig,
    lora: Optional[Dict] = None,       # stacked adapters (llama._proj)
    adapter_ids: Optional[jax.Array] = None,  # [b] int32, 0 = base
) -> Tuple[jax.Array, Dict]:
    """One decode step: returns (logits [b, vocab], updated cache).

    Uniform cache (scalar lengths): the T=1 case of decode_block_step —
    all rows write one position with a single dynamic_update_slice, the
    fast path. Ragged cache: each row writes at its own position via a
    vmapped dynamic_update_slice that lowers to a scatter (measurably
    slower on TPU; a one-hot select over the whole cache would be even
    worse at O(max_len) traffic)."""
    c = config
    b = token.shape[0]
    pos = cache["lengths"]  # [b], or scalar in uniform mode
    int8_kv = "ks" in cache
    if pos.ndim == 0:
        logits, cache = decode_block_step(
            params, token[:, None], cache, config,
            lora=lora, adapter_ids=adapter_ids)
        return logits[:, 0], cache
    max_cap = cache["k"][0].shape[2]
    ring = "ring" in cache
    if (not ring and not isinstance(pos, jax.core.Tracer)
            and int(jnp.max(pos)) + 1 > max_cap):
        # same guard as decode_block_step: a clamped write offset would
        # silently overwrite the last cache position for the full rows
        raise ValueError(
            f"ragged cache row at {int(jnp.max(pos))} of {max_cap} positions; "
            f"appending 1 more overflows it — init a larger max_len"
        )
    wpos = jnp.mod(pos, max_cap) if ring else pos  # ring: wrap the write

    positions = pos[:, None]  # [b, 1] — per-row RoPE positions
    write_row = jax.vmap(
        lambda cache_row, new_row, p: jax.lax.dynamic_update_slice_in_dim(
            cache_row, new_row, p, axis=1
        )
    )  # [b,hkv,L,d], [b,hkv,1,d], [b] -> per-row update at its own offset
    write_scale = jax.vmap(
        lambda scale_row, new_scale, p: jax.lax.dynamic_update_slice_in_dim(
            scale_row, new_scale, p, axis=1
        )
    )  # [b,hkv,L], [b,hkv,1], [b]

    x = params["embed"][token][:, None, :].astype(c.dtype)  # [b, 1, d]
    if c.embed_scale != 1.0:
        x = x * jnp.asarray(c.embed_scale, c.dtype)
    new_k, new_v, new_ks, new_vs = [], [], [], []
    for i, layer in enumerate(params["layers"]):
        llayer = None if lora is None else lora["layers"][i]
        h = rms_norm(x, layer["attn_norm"], c.rms_eps, c.norm_offset)
        q = _proj(h, layer, "q", llayer, adapter_ids).reshape(b, 1, c.n_heads, c.head_dim).transpose(0, 2, 1, 3)
        k = _proj(h, layer, "k", llayer, adapter_ids).reshape(b, 1, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        v = _proj(h, layer, "v", llayer, adapter_ids).reshape(b, 1, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        q = _rope(q, positions, c.rope_theta, c.rope_scaling)
        k = _rope(k, positions, c.rope_theta, c.rope_scaling)
        if c.q_prescale != 1.0:
            q = q * jnp.asarray(c.q_prescale, q.dtype)
        cks = cvs = None
        if int8_kv:
            qk, sk = _quantize_kv(k)
            qv, sv = _quantize_kv(v)
            ck = write_row(cache["k"][i], qk, wpos)
            cv = write_row(cache["v"][i], qv, wpos)
            cks = write_scale(cache["ks"][i], sk, wpos)
            cvs = write_scale(cache["vs"][i], sv, wpos)
            new_ks.append(cks)
            new_vs.append(cvs)
        else:
            ck = write_row(cache["k"][i], k.astype(c.dtype), wpos)
            cv = write_row(cache["v"][i], v.astype(c.dtype), wpos)
        new_k.append(ck)
        new_v.append(cv)
        attn = _attend_cached(q, ck, cv, pos + 1, c.n_heads // c.n_kv_heads,
                              k_scale=cks, v_scale=cvs,
                              window=c.window_for(i),
                              softcap=c.attn_logit_softcap or None,
                              ring_total=(pos + 1) if ring else None)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, 1, c.n_heads * c.head_dim)
        attn_out = _proj(attn.astype(c.dtype), layer, "o",
                         llayer, adapter_ids).astype(c.dtype)
        if "post_attn_norm" in layer:
            attn_out = rms_norm(attn_out, layer["post_attn_norm"],
                                c.rms_eps, c.norm_offset)
        x = x + attn_out
        x, _, _ = _mlp_block(x, layer, c, lora=llayer, adapter_ids=adapter_ids)

    out_cache = {
        "k": new_k,
        "v": new_v,
        "lengths": pos + 1,
    }
    if int8_kv:
        out_cache["ks"] = new_ks
        out_cache["vs"] = new_vs
    if ring:
        out_cache["ring"] = cache["ring"]
    cache = out_cache
    logits = _lm_head(x, params, c)[:, 0]  # [b, vocab]
    return logits, cache


def decode_block_step(
    params: Dict,
    tokens: jax.Array,  # [b, T] int32 — T new tokens per row
    cache: Dict,
    config: LlamaConfig,
    return_hidden: bool = False,
    lora: Optional[Dict] = None,
    adapter_ids: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Chunked decode: T tokens forward through the cache in ONE dispatch.

    Returns (logits [b, T, vocab], cache advanced by T) — or, with
    return_hidden=True, (pre-head activations [b, T, d], cache).
    logits[:, i] predicts the token AFTER tokens[:, i]. Query i attends
    the full cache plus the block prefix up to itself (causal within the
    block). Uniform (scalar-length) caches take one dynamic_update_slice
    per layer; RAGGED caches ([b] lengths — the serving batch) append
    each row's T tokens at ITS OWN length via vmapped per-row writes
    (the speculative-serving verify path). Ring caches are single-token
    only.

    A caller that accepts fewer than T positions (speculative decoding)
    rolls back by shrinking cache["lengths"]: entries past the length
    are masked out of attention and overwritten by later writes."""
    c = config
    b, T = tokens.shape
    pos = cache["lengths"]
    ragged = pos.ndim == 1
    max_cap = cache["k"][0].shape[2]
    ring = "ring" in cache
    if ring and (T > 1 or ragged):
        # a T-block can wrap over its own writes and earlier queries of
        # the block would need positions the ring already evicted
        raise ValueError("ring caches support uniform single-token steps only")
    if T > max_cap:
        raise ValueError(f"block of {T} tokens exceeds cache max_len {max_cap}")
    if not ring and not isinstance(pos, jax.core.Tracer):
        top = int(jnp.max(pos)) if ragged else int(pos)
        if top + T > max_cap:
            # appending past capacity would CLAMP the write offset and
            # silently corrupt earlier positions — the multi-turn footgun
            raise ValueError(
                f"cache holds {top} of {max_cap} positions; appending "
                f"{T} more overflows it — init a larger max_len"
            )
    wpos = pos if not ring else jnp.mod(pos, max_cap)  # ring: wrap the write
    int8_kv = "ks" in cache
    if ragged:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # [b, T]
        write_row = jax.vmap(
            lambda cache_row, new_row, p: jax.lax.dynamic_update_slice_in_dim(
                cache_row, new_row, p, axis=1
            )
        )  # [b,hkv,L,d], [b,hkv,T,d], [b] -> per-row block at its offset
        write_scale = jax.vmap(
            lambda scale_row, new_scale, p: jax.lax.dynamic_update_slice_in_dim(
                scale_row, new_scale, p, axis=1
            )
        )  # [b,hkv,L], [b,hkv,T], [b]
    else:
        positions = jnp.broadcast_to(
            (pos + jnp.arange(T, dtype=jnp.int32))[None], (b, T))
    limits = positions + 1  # query i sees cache < pos + i + 1

    x = params["embed"][tokens].astype(c.dtype)  # [b, T, d]
    if c.embed_scale != 1.0:
        x = x * jnp.asarray(c.embed_scale, c.dtype)
    new_k, new_v, new_ks, new_vs = [], [], [], []
    for i, layer in enumerate(params["layers"]):
        llayer = None if lora is None else lora["layers"][i]
        h = rms_norm(x, layer["attn_norm"], c.rms_eps, c.norm_offset)
        q = _proj(h, layer, "q", llayer, adapter_ids).reshape(b, T, c.n_heads, c.head_dim).transpose(0, 2, 1, 3)
        k = _proj(h, layer, "k", llayer, adapter_ids).reshape(b, T, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        v = _proj(h, layer, "v", llayer, adapter_ids).reshape(b, T, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        q = _rope(q, positions, c.rope_theta, c.rope_scaling)
        k = _rope(k, positions, c.rope_theta, c.rope_scaling)
        if c.q_prescale != 1.0:
            q = q * jnp.asarray(c.q_prescale, q.dtype)
        cks = cvs = None
        if int8_kv:
            qk, sk = _quantize_kv(k)
            qv, sv = _quantize_kv(v)
            if ragged:
                ck = write_row(cache["k"][i], qk, wpos)
                cv = write_row(cache["v"][i], qv, wpos)
                cks = write_scale(cache["ks"][i], sk, wpos)
                cvs = write_scale(cache["vs"][i], sv, wpos)
            else:
                ck = jax.lax.dynamic_update_slice(cache["k"][i], qk, (0, 0, wpos, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"][i], qv, (0, 0, wpos, 0))
                cks = jax.lax.dynamic_update_slice(cache["ks"][i], sk, (0, 0, wpos))
                cvs = jax.lax.dynamic_update_slice(cache["vs"][i], sv, (0, 0, wpos))
            new_ks.append(cks)
            new_vs.append(cvs)
        elif ragged:
            ck = write_row(cache["k"][i], k.astype(c.dtype), wpos)
            cv = write_row(cache["v"][i], v.astype(c.dtype), wpos)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"][i], k.astype(c.dtype), (0, 0, wpos, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"][i], v.astype(c.dtype), (0, 0, wpos, 0))
        new_k.append(ck)
        new_v.append(cv)
        attn = _attend_cached(q, ck, cv, limits, c.n_heads // c.n_kv_heads,
                              k_scale=cks, v_scale=cvs,
                              window=c.window_for(i),
                              softcap=c.attn_logit_softcap or None,
                              ring_total=(pos + T) if ring else None)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, T, c.n_heads * c.head_dim)
        attn_out = _proj(attn.astype(c.dtype), layer, "o",
                         llayer, adapter_ids).astype(c.dtype)
        if "post_attn_norm" in layer:
            attn_out = rms_norm(attn_out, layer["post_attn_norm"],
                                c.rms_eps, c.norm_offset)
        x = x + attn_out
        x, _, _ = _mlp_block(x, layer, c, lora=llayer, adapter_ids=adapter_ids)

    out_cache = {"k": new_k, "v": new_v, "lengths": pos + T}
    if int8_kv:
        out_cache["ks"] = new_ks
        out_cache["vs"] = new_vs
    if ring:
        out_cache["ring"] = cache["ring"]
    if return_hidden:
        # pre-head activations for callers that only head a subset (the
        # chunked prefill heads ONE row after its scan; the full
        # [b, T, vocab] head matmul would dominate every chunk)
        return x, out_cache
    return _lm_head(x, params, c), out_cache


def prefill_chunked(
    params: Dict,
    tokens: jax.Array,  # [b, t] int32, uniform batches only
    cache: Dict,
    config: LlamaConfig,
    chunk_size: int = 2048,
) -> Tuple[jax.Array, Dict]:
    """Incremental prefill: run the prompt through the cache in fixed
    chunks of decode_block_step. The point is APPENDING to a non-empty
    cache — multi-turn serving ingests each new user turn into the
    session's cache without re-running earlier turns; projection/MLP
    activations stay O(b * chunk * d).

    Memory note: the block attention materializes O(chunk * cache_len)
    f32 scores per layer, so for SINGLE-SHOT long prompts the one-pass
    `prefill` (flash kernel, O(t) streaming scores) is the better tool;
    this path trades that for cache-append ability and bounded
    projection activations. The LM head runs ONCE on the final hidden
    row — chunks carry pre-head activations, never [chunk, vocab]
    logits. Returns (last-token logits [b, vocab], cache). Uniform
    caches only; a trailing partial chunk runs as one extra block step
    (padding instead would bake pad tokens into attended cache state)."""
    b, t = tokens.shape
    if cache["lengths"].ndim != 0:
        raise ValueError("prefill_chunked requires a uniform cache "
                         "(init_kv_cache(..., uniform=True))")
    # whole-append capacity check up front: inside the scan the length is
    # a tracer and the per-block check cannot fire
    max_cap = cache["k"][0].shape[2]
    pos0 = cache["lengths"]
    if not isinstance(pos0, jax.core.Tracer) and int(pos0) + t > max_cap:
        raise ValueError(
            f"cache holds {int(pos0)} of {max_cap} positions; appending "
            f"{t} more overflows it — init a larger max_len"
        )
    n_full = t // chunk_size
    rem = t - n_full * chunk_size
    x_last = None
    if n_full:
        # lax.scan over equal chunks: one compiled block step reused
        # n_full times, not n_full separately-traced programs
        chunks = tokens[:, : n_full * chunk_size].reshape(
            b, n_full, chunk_size).transpose(1, 0, 2)

        def body(carry, chunk):
            cache, _ = carry
            x, cache = decode_block_step(params, chunk, cache, config,
                                         return_hidden=True)
            return (cache, x[:, -1]), None

        init = (cache, jnp.zeros((b, config.d_model), config.dtype))
        (cache, x_last), _ = jax.lax.scan(body, init, chunks)
    if rem:
        x, cache = decode_block_step(params, tokens[:, n_full * chunk_size:],
                                     cache, config, return_hidden=True)
        x_last = x[:, -1]
    return _lm_head(x_last[:, None], params, config)[:, 0], cache


def prefill(
    params: Dict,
    tokens: jax.Array,  # [b, t] int32, right-padded when ragged
    cache: Dict,
    config: LlamaConfig,
    lengths: Optional[jax.Array] = None,  # [b] unpadded lengths; default t
    lora: Optional[Dict] = None,
    adapter_ids: Optional[jax.Array] = None,
):
    """One full-sequence forward over the prompt, writing all K/V at once.

    Returns (logits at each row's last real token [b, vocab], cache).
    Right-padding is safe under a causal mask: a real query at position
    i < lengths[row] only attends keys <= i, which are all real; pad
    positions' K/V are never attended (per-row mask) and are overwritten
    as generation advances."""
    c = config
    b, t = tokens.shape
    uniform = cache["lengths"].ndim == 0
    if uniform:
        if lengths is not None:
            raise ValueError(
                "per-row lengths need a ragged cache: "
                "init_kv_cache(..., uniform=False)"
            )
    elif lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    import functools

    if c.use_flash:
        from kubedl_tpu.ops.flash_attention import flash_attention

        _attn = functools.partial(
            flash_attention, softcap=c.attn_logit_softcap or None)
    else:
        from kubedl_tpu.ops.flash_attention import attention_reference

        _attn = functools.partial(
            attention_reference, softcap=c.attn_logit_softcap or None)

    x = params["embed"][tokens].astype(c.dtype)
    if c.embed_scale != 1.0:
        x = x * jnp.asarray(c.embed_scale, c.dtype)
    ks, vs = [], []
    for i, layer in enumerate(params["layers"]):
        llayer = None if lora is None else lora["layers"][i]
        h = rms_norm(x, layer["attn_norm"], c.rms_eps, c.norm_offset)
        q = _proj(h, layer, "q", llayer, adapter_ids).reshape(b, t, c.n_heads, c.head_dim).transpose(0, 2, 1, 3)
        k = _proj(h, layer, "k", llayer, adapter_ids).reshape(b, t, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        v = _proj(h, layer, "v", llayer, adapter_ids).reshape(b, t, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
        q = _rope(q, positions, c.rope_theta, c.rope_scaling)
        k = _rope(k, positions, c.rope_theta, c.rope_scaling)
        if c.q_prescale != 1.0:
            q = q * jnp.asarray(c.q_prescale, q.dtype)
        ks.append(k.astype(c.dtype))
        vs.append(v.astype(c.dtype))
        # GQA broadcast happens inside the attention entry points
        attn = _attn(q, k, v, causal=True, window=c.window_for(i))
        attn = attn.transpose(0, 2, 1, 3).reshape(b, t, c.n_heads * c.head_dim)
        attn_out = _proj(attn.astype(c.dtype), layer, "o",
                         llayer, adapter_ids).astype(c.dtype)
        if "post_attn_norm" in layer:
            attn_out = rms_norm(attn_out, layer["post_attn_norm"],
                                c.rms_eps, c.norm_offset)
        x = x + attn_out
        x, _, _ = _mlp_block(x, layer, c, lora=llayer, adapter_ids=adapter_ids)

    int8_kv = "ks" in cache
    if int8_kv:
        qks, kscales = zip(*(_quantize_kv(kl) for kl in ks))
        qvs, vscales = zip(*(_quantize_kv(vl) for vl in vs))
        ks, vs = list(qks), list(qvs)
    out_cache = {
        "k": [
            jax.lax.dynamic_update_slice_in_dim(buf, kl, 0, axis=2)
            for buf, kl in zip(cache["k"], ks)
        ],
        "v": [
            jax.lax.dynamic_update_slice_in_dim(buf, vl, 0, axis=2)
            for buf, vl in zip(cache["v"], vs)
        ],
        "lengths": jnp.asarray(t, jnp.int32) if uniform else lengths,
    }
    if int8_kv:
        out_cache["ks"] = [
            jax.lax.dynamic_update_slice_in_dim(buf, sl, 0, axis=2)
            for buf, sl in zip(cache["ks"], kscales)
        ]
        out_cache["vs"] = [
            jax.lax.dynamic_update_slice_in_dim(buf, sl, 0, axis=2)
            for buf, sl in zip(cache["vs"], vscales)
        ]
    cache = out_cache
    logits_all = _lm_head(x, params, c)  # [b, t, vocab]
    if uniform:
        last = logits_all[:, t - 1]
    else:
        last = jnp.take_along_axis(
            logits_all, (lengths - 1)[:, None, None], axis=1
        )[:, 0]
    return last, cache


def generate(
    params: Dict,
    prompt: jax.Array,  # [b, t] int32, right-padded when ragged
    config: LlamaConfig,
    max_new_tokens: int,
    max_len: Optional[int] = None,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,  # [b] unpadded prompt lengths
    kv_dtype: Optional[str] = None,  # None (model dtype) | "int8"
    with_logprobs: bool = False,
) -> jax.Array:
    """Greedy (temperature=0) or sampled continuation: [b, max_new_tokens].

    Ragged batches: pass right-padded `prompt` plus per-row `lengths`;
    row i's continuation starts after its own last real token. Without
    `lengths` the batch is uniform and the cache takes the scalar-length
    fast path (single-slice writes instead of per-row scatters).
    kv_dtype="int8" halves KV-cache memory and read traffic (per-position
    scales fold exactly into the attention einsums).

    with_logprobs=True also returns [b, max_new_tokens] f32 behavior
    log-probs of each emitted token under the model's UNTEMPERED
    distribution (log_softmax of the raw logits — the same convention as
    train/preference.sequence_logprobs and the serving engines'
    chosen_logprob), captured from the logits that sampled the token.
    They are free at sample time — one gather next to the sampling op —
    where recomputing them later costs a full forward; the RL actor
    runtime ships them with each trajectory and train/rl.py's recompute
    stays as the parity oracle (pinned in tests/test_rl.py)."""
    b, t = prompt.shape
    max_len = max_len or (t + max_new_tokens)
    cache = init_kv_cache(
        config, b, max_len, uniform=lengths is None, kv_dtype=kv_dtype
    )
    logits, cache = prefill(params, prompt, cache, config, lengths=lengths)
    if key is None:
        key = jax.random.PRNGKey(0)

    def pick(logits, k):
        if temperature > 0:
            return jax.random.categorical(k, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def body(carry, k):
        logits, cache = carry
        tok = pick(logits, k).astype(jnp.int32)
        ys = tok
        if with_logprobs:  # static flag: the lp gather exists only when asked
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                tok[:, None], axis=-1)[:, 0]
            ys = (tok, lp)
        logits, cache = decode_step(params, tok, cache, config)
        return (logits, cache), ys

    keys = jax.random.split(key, max_new_tokens)
    (_, _), ys = jax.lax.scan(body, (logits, cache), keys)
    if with_logprobs:
        toks, lps = ys
        return toks.T, lps.T  # [b, max_new_tokens] each
    return ys.T  # [b, max_new_tokens]


def generate_speculative(
    params: Dict,
    draft_params: Dict,
    prompt: jax.Array,  # [1, t] int32 — single sequence
    config: LlamaConfig,
    draft_config: LlamaConfig,
    max_new_tokens: int,
    k: int = 4,
    kv_dtype: Optional[str] = None,
    return_stats: bool = False,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Speculative decoding: [1, max_new_tokens] from the target model's
    distribution, produced in fewer target passes. temperature=0 (the
    default) is greedy and emits EXACTLY the target's greedy
    continuation; temperature>0 samples with the standard rejection
    scheme — accept draft token x with prob min(1, p(x)/q(x)), else
    resample from the residual normalize(max(p-q, 0)) — which preserves
    the target distribution exactly (Leviathan et al.'s identity).
    With return_stats=True, returns (tokens, {"rounds", "acceptance"})
    — acceptance = mean accepted drafts per round / (k-1), the number to
    watch when tuning k or judging a draft model.

    Each round a small draft model proposes k tokens one at a time; the
    target verifies all of them in ONE decode_block_step and keeps the
    longest matching prefix plus its own next token (the bonus).
    Acceptance is capped at k-1 so the draft cache — which only ever saw
    k inputs — stays position-aligned with the target cache; both roll
    back by shrinking their scalar cache lengths. Latency-bound serving
    is batch=1 by nature, and b=1 keeps every length scalar (the
    uniform fast path); larger batches diverge per row and are not
    supported.

    Exactness (temperature=0): every emitted token is the target's
    argmax given the previously emitted prefix — a mismatched draft only
    costs speed. At temperature>0 the guarantee is distributional: the
    emitted sequence is a sample from the target's own sampling
    distribution (pinned by a statistical test against exact
    enumeration). Either way, logits come from the block verify, whose
    reductions may order differently than single-token steps; greedy
    near-ties can resolve differently than vanilla generate(), and
    sampled probabilities can differ in the last ulps, as between any
    two compiled schedules."""
    b, t = prompt.shape
    if b != 1:
        raise ValueError(f"speculative decoding is batch=1 (got batch {b})")
    if k < 2:
        raise ValueError(f"k must be >= 2 (got {k}); k=1 degenerates to "
                         "vanilla greedy with an extra draft pass")
    if draft_config.vocab_size != config.vocab_size:
        # JAX clamps out-of-range gathers, so a smaller draft vocab would
        # not crash — it would silently floor acceptance to ~0
        raise ValueError(
            f"draft vocab {draft_config.vocab_size} != target vocab "
            f"{config.vocab_size}; the models must share a tokenizer"
        )
    max_len = t + max_new_tokens + k  # slack: final block may overshoot

    sampled = temperature > 0
    if key is None:
        key = jax.random.PRNGKey(0)

    t_cache = init_kv_cache(config, 1, max_len, uniform=True, kv_dtype=kv_dtype)
    logits, t_cache = prefill(params, prompt, t_cache, config)
    d_cache = init_kv_cache(draft_config, 1, max_len, uniform=True,
                            kv_dtype=kv_dtype)
    _, d_cache = prefill(draft_params, prompt, d_cache, draft_config)

    key, k0 = jax.random.split(key)
    if sampled:
        cur = jax.random.categorical(k0, logits / temperature, axis=-1)
        cur = cur.astype(jnp.int32)  # [1] — first token
    else:
        cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = jnp.zeros((1, max_new_tokens + k), jnp.int32)
    out = jax.lax.dynamic_update_slice(out, cur[None], (0, 0))

    def draft_round(d_cache, cur, rkey):
        """Greedy: (cache, drafted [k]). Sampled: also each step's full
        draft distribution q [k, V] (the rejection test needs q(x) and
        the residual needs the whole q)."""
        def body(carry, kk):
            tok, cache = carry
            lg, cache = decode_step(draft_params, tok, cache, draft_config)
            if sampled:
                nxt = jax.random.categorical(kk, lg / temperature, axis=-1)
                nxt = nxt.astype(jnp.int32)
                q = jax.nn.softmax(lg[0] / temperature)
                return (nxt, cache), (nxt[0], q)
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return (nxt, cache), (nxt[0], jnp.zeros((), jnp.float32))
        keys = jax.random.split(rkey, k)
        (_, d_cache), (drafted, q) = jax.lax.scan(body, (cur, d_cache), keys)
        return d_cache, drafted, q

    def cond(state):
        _, n, _, _, _, _, _, _ = state
        return n < max_new_tokens

    def round_body(state):
        cur, n, out, t_cache, d_cache, rounds, acc, key = state
        key, kd, ka, kf = jax.random.split(key, 4)
        pos = t_cache["lengths"]  # == d_cache["lengths"]
        d_cache, drafted, q = draft_round(d_cache, cur, kd)  # [k], [k, V]
        blk = jnp.concatenate([cur, drafted])[None]  # [1, k+1]
        blk_logits, t_cache = decode_block_step(params, blk, t_cache, config)
        if sampled:
            p = jax.nn.softmax(blk_logits[0] / temperature)  # [k+1, V]
            # accept draft i (i < k-1 cap) with prob min(1, p_i(x)/q_i(x))
            px = jnp.take_along_axis(
                p[: k - 1], drafted[: k - 1, None], axis=1)[:, 0]
            qx = jnp.take_along_axis(
                q[: k - 1], drafted[: k - 1, None], axis=1)[:, 0]
            u = jax.random.uniform(ka, (k - 1,))
            accept = (u * qx < px).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(accept))
            # the token at slot a: residual max(p_a - q_a, 0) after a
            # rejection; plain p_a after full acceptance (a == k-1, the
            # capped slot whose draft was never tested)
            p_a = p[a]
            residual = jnp.maximum(p_a - q[a], 0.0)
            rs = jnp.sum(residual)
            final_dist = jnp.where(
                (a == k - 1) | (rs <= 0), p_a, residual / jnp.maximum(rs, 1e-30)
            )
            bonus = jax.random.categorical(kf, jnp.log(final_dist))
            bonus = bonus.astype(jnp.int32)
        else:
            ta = jnp.argmax(blk_logits[0], axis=-1).astype(jnp.int32)  # [k+1]
            # longest matching prefix of the drafts, capped at k-1 (see doc)
            matches = (drafted[: k - 1] == ta[: k - 1]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(matches))
            bonus = jax.lax.dynamic_index_in_dim(ta, a, keepdims=False)
        # emit drafted[:a] then the slot-a token; tail junk is overwritten
        # by later rounds and trimmed at the end
        slots = jnp.arange(k)
        emit = jnp.where(slots < a, drafted, 0)
        emit = jnp.where(slots == a, bonus, emit)
        out = jax.lax.dynamic_update_slice(out, emit[None], (0, n))
        # roll both caches back to the accepted prefix (cur + a drafts)
        t_cache = dict(t_cache, lengths=pos + a + 1)
        d_cache = dict(d_cache, lengths=pos + a + 1)
        return (bonus[None], n + a + 1, out, t_cache, d_cache, rounds + 1,
                acc + a, key)

    state = (cur, jnp.asarray(1, jnp.int32), out, t_cache, d_cache,
             jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32), key)
    _, n, out, _, _, rounds, acc, _ = jax.lax.while_loop(cond, round_body, state)
    toks = out[:, :max_new_tokens]
    if not return_stats:
        return toks
    # Acceptance comes from a DIRECT count of verifier-accepted drafts
    # (`acc`), not from n-arithmetic: the final round can overshoot
    # max_new_tokens and deriving from the trimmed n would misreport the
    # draft-quality stat either way (inflated if untrimmed, deflated if
    # clamped). Zero rounds (max_new_tokens == 1: prefill alone
    # suffices) reports acceptance 0 — there was nothing to accept.
    r = jnp.maximum(rounds, 1).astype(jnp.float32)
    mean_accepted = jnp.where(rounds > 0, acc.astype(jnp.float32) / r, 0.0)
    stats = {"rounds": rounds, "acceptance": mean_accepted / (k - 1)}
    return toks, stats
