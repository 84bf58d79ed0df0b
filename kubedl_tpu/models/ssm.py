"""State-space mixer (Mamba-2, arXiv:2405.21060): the token mixer of a
layer whose state is a matrix a head, carried along the sequence.

Per token t and head j (x_t^j of ssm_head_dim entries; B_t, C_t of
ssm_state entries, shared by all heads: one group):

    [z, xBC, dt] = split(u @ W_in)            W_in [d, 2 d_inner + 2 n + h]
    xBC   = silu(causal_taps(xBC) + conv_bias)    depthwise, K taps
    [x, B, C] = split(xBC)
    dt_t  = softplus(dt_t + dt_bias);  a_t^j = exp(dt_t^j A^j),  A = -exp(A_log)
    S_t^j = a_t^j S_{t-1}^j + dt_t^j x_t^j B_t^T      S_0 = 0
    y_t^j = S_t^j C_t + D^j x_t^j
    mixer = (rmsnorm(y * silu(z)) * w) @ W_out    the gate before the norm

`u` is the layer's normed input. The recurrence is computed in chunks
(`chunked_scan`, the state-space duality form): inside a chunk a masked,
decay-weighted C B^T product against x, quadratic in the chunk and all
matmuls; a chunk's end state from B, x and the decays; the states passed
from chunk to chunk in float32; the carried state's part of each output.
Every decay is a cumulative sum in float32, matmul operands are in the
model's dtype.

Which steps run as which kernels, on a TPU under no mesh that shards the
inner channels (both choices from shapes, backend and mesh alone; every
other shape, the CPU and such a mesh take the same steps as XLA
operations, whose backward is autodiff; all under the layer's remat):

- the second and third lines above, where the sequence is whole 128-token
  blocks and the inner width and the state are whole 128-lane blocks
  (`conv_takes_kernel`): ops/causal_conv.py's `ssm_conv_fwd`, which reads
  xBC once where it lies in the in projection's output and writes x, B
  and C once, and a hand-written backward `ssm_conv_bwd`, which walks a
  sequence's tokens in reverse and sums the taps' and the bias's
  gradients in float32; the projections, z and dt stay XLA's.
  `ssm_conv_kernel_layers` counts the layers that took them;
- the recurrence, at shapes of whole tiles (a chunk and a state that are
  multiples of 128, heads in blocks of 8; `scan_takes_kernel`): all four
  steps as ops/ssm_scan.py's `ssm_scan_fwd`, which walks a sequence's
  chunks in order with the scores and the state in VMEM and writes y
  once, token before head, and a hand-written backward `ssm_scan_bwd`,
  which walks them in reverse; XLA keeps the decays' cumulative sums.
  `ssm_kernel_chunks` counts the chunks they took.

What a decoder would carry from step to step is S (heads x head size x
state size a layer) and the convolution's last K - 1 inputs, not keys and
values: training needs no state at all, and the cached paths
(models/decode.py, models/serving.py) have none for it and refuse such a
layer (LlamaConfig.require_kv_state_only).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from kubedl_tpu.models.quant import matmul as _mm
from kubedl_tpu.models.short_conv import causal_taps
from kubedl_tpu.ops import causal_conv, interpret, ssm_scan
from kubedl_tpu.parallel.mesh import ShardingRules


def ssm_param_specs(rules: Optional[ShardingRules] = None) -> Dict:
    """PartitionSpec pytree matching ssm_init()."""
    r = rules or ShardingRules()
    return {
        "ssm_in": r.spec("embed", "mlp"),
        "ssm_conv_w": r.spec("mlp", None),
        "ssm_conv_b": r.spec("mlp"),
        "ssm_dt_bias": r.spec(None),
        "ssm_A_log": r.spec(None),
        "ssm_D": r.spec(None),
        "ssm_gate_norm": r.spec("mlp"),
        "ssm_out": r.spec("mlp", "embed"),
    }


def ssm_init(key: jax.Array, d_model: int, heads: int, head_dim: int,
             state: int, kernel: int, dtype=jnp.bfloat16) -> Dict:
    """The paper's initialisation: A uniform in [1, 16], dt log-uniform in
    [0.001, 0.1] through the inverse softplus, D and the gated norm ones."""
    ks = jax.random.split(key, 5)
    d_inner, d_conv = heads * head_dim, heads * head_dim + 2 * state

    def dense(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * (1.0 / np.sqrt(fan_in))).astype(dtype)

    dt = jnp.exp(jax.random.uniform(
        ks[3], (heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return {
        "ssm_in": dense(ks[0], (d_model, 2 * d_inner + 2 * state + heads), d_model),
        # float32 like the layer's other small leaves: taps of 0.1-0.5 in
        # bf16 would drop every update under half an ulp (5e-4 at 0.25)
        "ssm_conv_w": dense(ks[1], (d_conv, kernel), kernel).astype(jnp.float32),
        "ssm_conv_b": jnp.zeros((d_conv,), jnp.float32),
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "ssm_A_log": jnp.log(jax.random.uniform(
            ks[4], (heads,), jnp.float32, 1.0, 16.0)),
        "ssm_D": jnp.ones((heads,), jnp.float32),
        "ssm_gate_norm": jnp.ones((d_inner,), jnp.float32),
        "ssm_out": dense(ks[2], (d_inner, d_model), d_inner),
    }


def scan_takes_kernel(x_shape: Tuple[int, ...], state: int, chunk: int,
                      mesh=None) -> bool:
    """Whether chunked_scan runs as the Pallas kernels (ops/ssm_scan.py):
    on a TPU, at shapes of whole tiles, and under no mesh that shards the
    inner channels (a Mosaic call cannot be partitioned; over `batch` it
    rides a shard_map)."""
    _, t, h, p = x_shape
    if interpret() or not ssm_scan.supports(h, p, state, chunk, t):
        return False
    return mesh is None or mesh.shape.get("tensor", 1) == 1


@jax.named_scope("ssm_scan")
def chunked_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_: jax.Array,
                 c_: jax.Array, chunk: int, mesh=None,
                 rules: Optional[ShardingRules] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """y_t = S_t C_t for S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, S_0 = 0.

    x [b, t, h, p] and b_, c_ [b, t, n] in the model's dtype; dt [b, t, h]
    (after the softplus) and a [h] (negative) float32. Returns y
    [b, t, h, p] float32 and, [b, chunks, h], the product of each chunk's
    decays: the share of a chunk's incoming state that leaves it.

    A sequence that is no multiple of the chunk is padded with steps of
    dt = 0, which leave the state alone and whose outputs are dropped.

    One algorithm in two forms, chosen by `scan_takes_kernel` from the
    shapes and the mesh: ops/ssm_scan.py's kernels, which take x, dt, B,
    C and the decays' cumulative sums and keep the scores and the state
    in VMEM, or the XLA operations below."""
    bsz, t, h, p = x.shape
    n = b_.shape[-1]
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, b_, c_ = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                         for v in (x, dt, b_, c_))
    nc = (t + pad) // q
    f32, dtype = jnp.float32, x.dtype
    if scan_takes_kernel((bsz, t, h, p), n, chunk, mesh):
        cum = jnp.cumsum((dt * a).reshape(bsz, nc, q, h), axis=2)
        scan = functools.partial(ssm_scan.scan, chunk=q)
        if mesh is not None and mesh.size > 1:
            # GSPMD cannot partition a Mosaic kernel: each device scans
            # its own sequences (the scan mixes no two)
            rows = (rules or ShardingRules()).spec("batch", None, None)
            scan = jax.shard_map(scan, mesh=mesh, in_specs=(rows,) * 5,
                                 out_specs=rows, check_vma=False)
        y = scan(x.reshape(bsz, nc * q, h * p), dt, cum.reshape(bsz, nc * q, h),
                 b_, c_)
        return y.reshape(bsz, nc * q, h, p)[:, :t], jnp.exp(cum[:, :, -1])
    xdt = (x.astype(f32) * dt[..., None]).astype(dtype).reshape(bsz, nc, q, h, p)
    b_, c_ = b_.reshape(bsz, nc, q, n), c_.reshape(bsz, nc, q, n)
    # log of the decay from a chunk's start through each of its tokens
    cum = jnp.cumsum((dt * a).reshape(bsz, nc, q, h), axis=2)  # [b, c, l, h]
    cum_h = cum.transpose(0, 1, 3, 2)  # [b, c, h, l]

    # inside a chunk: token l reads token s <= l through the decays between
    cb = jnp.einsum("bcln,bcsn->bcls", c_, b_, preferred_element_type=f32)
    between = cum_h[..., :, None] - cum_h[..., None, :]  # [b, c, h, l, s]
    causal = jnp.tril(jnp.ones((q, q), bool))
    weights = cb[:, :, None] * jnp.exp(jnp.where(causal, between, -jnp.inf))
    y = jnp.einsum("bchls,bcshp->bclhp", weights.astype(dtype), xdt,
                   preferred_element_type=f32)

    # what a chunk adds to the state by its end
    to_end = jnp.exp(cum_h[..., -1:] - cum_h).transpose(0, 1, 3, 2)  # [b, c, s, h]
    ends = jnp.einsum(
        "bcsn,bcshp->bchpn", b_,
        (xdt.astype(f32) * to_end[..., None]).astype(dtype),
        preferred_element_type=f32)
    through = jnp.exp(cum_h[..., -1])  # [b, c, h]

    with jax.named_scope("ssm_carry"):
        def carry(state, inp):
            decay, end = inp
            return state * decay[..., None, None] + end, state

        _, entering = jax.lax.scan(
            carry, jnp.zeros((bsz, h, p, n), f32),
            (through.transpose(1, 0, 2), ends.transpose(1, 0, 2, 3, 4)))

    # the carried state's part of each output
    y = y + jnp.einsum(
        "bcln,cbhpn->bclhp", c_, entering.astype(dtype),
        preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(bsz, nc * q, h, p)[:, :t], through


def conv_takes_kernel(seq: int, d_inner: int, state: int, taps: int,
                      mesh=None) -> bool:
    """Whether the convolution runs as the Pallas kernels
    (ops/causal_conv.py): on a TPU, where xBC and its three parts are
    whole 128-lane blocks of the in projection's output and the sequence
    whole 128-token blocks, and under no mesh that shards the inner
    channels (over `batch` it rides a shard_map, as the scan does)."""
    if interpret() or not causal_conv.supports(
            seq, d_inner, (d_inner, state, state), taps):
        return False
    return mesh is None or mesh.shape.get("tensor", 1) == 1


def split_conv(h: jax.Array, w: jax.Array, bias: jax.Array, d_inner: int,
               state: int, mesh=None, rules: Optional[ShardingRules] = None
               ) -> Tuple[Tuple[jax.Array, ...], bool]:
    """(z, x, B, C, dt) of the in projection's output h [b, t, 2 d_inner +
    2 state + heads]: [z, xBC, dt] = split(h), [x, B, C] =
    split(silu(causal_taps(xBC, w) + bias)), in h's dtype; and whether the
    kernels made them.

    One step in two forms, chosen by `conv_takes_kernel` from the shapes
    and the mesh: ops/causal_conv.py's kernels, which read xBC in place
    and write x, B and C apart, or the XLA operations below."""
    widths = (d_inner, state, state)
    if conv_takes_kernel(h.shape[1], d_inner, state, w.shape[1], mesh):
        conv = functools.partial(causal_conv.split_conv, offset=d_inner,
                                 widths=widths)
        if mesh is not None and mesh.size > 1:
            # each device its own sequences; the taps' and the bias's
            # gradients are summed over the devices by the map's transpose
            rows = (rules or ShardingRules()).spec("batch", None, None)
            conv = jax.shard_map(conv, mesh=mesh, in_specs=(rows, P(), P()),
                                 out_specs=(rows,) * 5, check_vma=False)
        with jax.named_scope("ssm_conv"):
            return conv(h, w, bias), True
    z, xbc, dt = jnp.split(h, [d_inner, 2 * d_inner + 2 * state], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(
            causal_taps(xbc, w).astype(jnp.float32) + bias).astype(h.dtype)
    x, b_, c_ = jnp.split(xbc, [d_inner, d_inner + state], axis=-1)
    return (z, x, b_, c_, dt), False


def ssm_mixer(u: jax.Array, layer: Dict, heads: int, head_dim: int,
              state: int, chunk: int, eps: float, mesh=None,
              rules: Optional[ShardingRules] = None) -> Tuple[jax.Array, Dict]:
    """The mixer's output for normed input u [b, t, d], and the layer's
    counters: whether the convolution ran as its kernels, chunks scanned,
    how many of them went through the scan's kernels, the mean step size
    after the softplus, the mean share of a chunk's incoming state that
    leaves it."""
    bsz, t, _ = u.shape
    d_inner, f32 = heads * head_dim, jnp.float32
    (z, x, b_, c_, dt), conv_kernel = split_conv(
        _mm(u, layer["ssm_in"]), layer["ssm_conv_w"], layer["ssm_conv_b"],
        d_inner, state, mesh, rules)
    x = x.reshape(bsz, t, heads, head_dim)
    dt = jax.nn.softplus(dt.astype(f32) + layer["ssm_dt_bias"])
    y, through = chunked_scan(x, dt, -jnp.exp(layer["ssm_A_log"]), b_, c_, chunk,
                              mesh, rules)
    y = y + x.astype(f32) * layer["ssm_D"][:, None]
    with jax.named_scope("ssm_gate_norm"):
        g = y.reshape(bsz, t, d_inner) * jax.nn.silu(z.astype(f32))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        g = (g * layer["ssm_gate_norm"]).astype(u.dtype)
    chunks = through.shape[0] * through.shape[1]
    stats = {"ssm_layers": jnp.ones((), f32),
             "ssm_conv_kernel_layers": jnp.asarray(conv_kernel, f32),
             "ssm_chunks": jnp.asarray(chunks, f32),
             "ssm_kernel_chunks": jnp.asarray(
                 chunks * scan_takes_kernel(x.shape, state, chunk, mesh), f32),
             "ssm_dt_mean": jnp.mean(dt),
             "ssm_state_carry": jnp.mean(through)}
    return _mm(g, layer["ssm_out"]), stats
