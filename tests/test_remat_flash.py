"""What a rematerialised layer keeps (models/llama.py:_remat_policy): the
flash kernel's output and log-sum-exp, tagged in the custom VJP's forward
rule (ops/flash_attention.py), so the backward holds one forward kernel a
layer and not two. Counted in the gradient's jaxpr and compared bit for
bit with a bare `jax.checkpoint(layer_fn)`, on the CPU in interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubedl_tpu.models import llama
from kubedl_tpu.parallel.mesh import ShardingRules, build_mesh

KERNELS = ("flash_fwd", "flash_bwd_dqkv")


def _value_and_grad(config, mesh):
    rules = ShardingRules() if mesh is not None else None
    return jax.value_and_grad(
        lambda p, t: llama.loss_fn(p, t, config, mesh=mesh, rules=rules))


def _inputs(config):
    params = llama.init(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, config.vocab_size)
    return params, tokens


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (jit, remat, custom_vjp, shard_map), in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _gradient_equations(config, params, tokens, mesh=None):
    step = _value_and_grad(config, mesh)
    return list(_equations(jax.make_jaxpr(step)(params, tokens).jaxpr))


def _kernel_calls(equations):
    names = [e.params["name"] for e in equations
             if e.primitive.name == "pallas_call"]
    assert set(names) <= set(KERNELS), names
    return {k: names.count(k) for k in KERNELS}


@pytest.fixture
def bare_checkpoint(monkeypatch):
    """Switches the model to `jax.checkpoint(layer_fn)` with no policy,
    which saves nothing but the layer's input."""
    def switch():
        monkeypatch.setattr(llama, "_remat_policy", lambda name: None)
    return switch


@pytest.mark.parametrize("mesh_axes", [None, {"fsdp": 4}], ids=["one", "fsdp4"])
@pytest.mark.parametrize("remat_policy", [None, "dots"])
def test_backward_holds_one_forward_kernel_a_layer(
        remat_policy, mesh_axes, bare_checkpoint):
    # float32 for the comparison bit for bit: in bf16 the CPU's compiler
    # keeps excess precision through the operations the interpreted kernel
    # is inlined into (xla_allow_excess_precision), and not alike for a
    # recomputed value and a kept one. On a TPU the kernel is one opaque
    # call whose results are what they are.
    config = llama.LlamaConfig.tiny(
        remat_policy=remat_policy, dtype=jnp.float32)
    mesh = mesh_axes and build_mesh(mesh_axes, devices=jax.devices()[:4])
    params, tokens = _inputs(config)
    n = config.n_layers

    calls = _kernel_calls(_gradient_equations(config, params, tokens, mesh))
    assert calls == dict.fromkeys(KERNELS, n)
    loss, grads = jax.jit(_value_and_grad(config, mesh))(params, tokens)

    bare_checkpoint()
    calls = _kernel_calls(_gradient_equations(config, params, tokens, mesh))
    assert calls == {"flash_fwd": 2 * n, "flash_bwd_dqkv": n}
    loss0, grads0 = jax.jit(_value_and_grad(config, mesh))(params, tokens)

    assert np.asarray(loss) == np.asarray(loss0)
    got, want = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, grads0))
    assert len(got) == len(want) > 0
    for (path, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path))


def test_padded_head_dim_holds_one_forward_kernel_a_layer():
    """head_dim 64 is zero-padded to 128 for the kernel and sliced after:
    the `wo` gradient's input and the backward's residual both derive
    from the saved output."""
    config = llama.LlamaConfig.tiny(n_heads=2, n_kv_heads=1)
    assert config.head_dim == 64
    params, tokens = _inputs(config)
    calls = _kernel_calls(_gradient_equations(config, params, tokens))
    assert calls == dict.fromkeys(KERNELS, config.n_layers)


def test_without_a_flash_kernel_nothing_more_is_kept(bare_checkpoint):
    """A layer with plain-XLA attention holds neither name: its remat
    step is the bare checkpoint's, equation for equation. (Compared as
    primitive and shapes: the printed text also holds the policy's
    address and shares sub-jaxprs by object identity.)"""
    config = llama.LlamaConfig.tiny(use_flash=False)
    params, tokens = _inputs(config)

    def described():
        return [(e.primitive.name, [str(v.aval) for v in e.invars],
                 [str(v.aval) for v in e.outvars])
                for e in _gradient_equations(config, params, tokens)]

    with_names = described()
    bare_checkpoint()
    assert with_names == described()
    primitives = {d[0] for d in with_names}
    assert "remat2" in primitives and "pallas_call" not in primitives


def test_the_tag_is_the_identity_outside_a_policy():
    """No `jax.checkpoint` around it (serving, remat off): the step holds
    one forward kernel a layer, as before the names."""
    config = llama.LlamaConfig.tiny(remat=False)
    params, tokens = _inputs(config)
    calls = _kernel_calls(_gradient_equations(config, params, tokens))
    assert calls == dict.fromkeys(KERNELS, config.n_layers)


def test_a_window_adds_no_kernel():
    """Edge and interior blocks are visits of the loops inside the
    kernels, not kernels of their own: the benchmark's by-name metrics
    count calls."""
    config = llama.LlamaConfig.tiny(sliding_window=8)
    params, tokens = _inputs(config)
    calls = _kernel_calls(_gradient_equations(config, params, tokens))
    assert calls == dict.fromkeys(KERNELS, config.n_layers)


def test_unknown_policy_is_refused():
    config = llama.LlamaConfig.tiny(remat_policy="all")
    params, tokens = _inputs(config)
    with pytest.raises(ValueError, match="unknown remat_policy"):
        _value_and_grad(config, None)(params, tokens)
