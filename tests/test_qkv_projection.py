"""The q/k/v projection (three products over the one stored kernel) against
the form it replaced, ``nn.DenseGeneral((3, h, d), name="qkv")`` then slices:
the same parameter tree with bit-identical initial values, and the same loss
and gradients to the rounding of the compute dtype. The old form lives here
as the yardstick.
"""

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from katib_tpu.models import transformer
from katib_tpu.models.transformer import TransformerConfig, TransformerLM
from katib_tpu.parallel.mesh import make_mesh
from katib_tpu.parallel.train import lm_loss


class _SlicedDenseGeneral(nn.DenseGeneral):
    """The old form: one [E] -> [3, H, D] DenseGeneral, its result sliced."""

    def __call__(self, x):
        qkv = super().__call__(x)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _old_projection(num_heads, head_dim, dtype, name):
    return _SlicedDenseGeneral(
        (3, num_heads, head_dim), use_bias=False, dtype=dtype, name=name
    )


def _model2_mesh():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 virtual devices")
    return make_mesh(devs[:2], model=2)


@pytest.fixture(params=["no_mesh", "model2"])
def mesh(request):
    return None if request.param == "no_mesh" else _model2_mesh()


def _config(dtype):
    return TransformerConfig(
        vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, max_seq_len=32,
        dtype=dtype,
    )


def _batch():
    data = np.random.default_rng(0).integers(0, 64, size=(2, 33), dtype=np.int32)
    positions = np.broadcast_to(np.arange(32, dtype=np.int32), (2, 32))
    return jnp.asarray(data[:, :-1]), jnp.asarray(data[:, 1:]), jnp.asarray(positions)


def _model_and_params(dtype, mesh):
    """Built with whatever ``transformer.QKVProjection`` is when called."""
    model = TransformerLM(_config(dtype), mesh=mesh)
    params = model.init(jax.random.PRNGKey(7), jnp.zeros((2, 16), jnp.int32))["params"]
    return model, params if mesh is None else transformer.shard_params(params, mesh)


def _loss_and_grads(model, params):
    tokens, targets, positions = _batch()

    def loss_fn(p):
        return lm_loss(model.apply({"params": p}, tokens, positions), targets)

    return jax.jit(jax.value_and_grad(loss_fn))(params)


def test_parameter_tree_and_initial_values_are_dense_generals(monkeypatch, mesh):
    _, new_params = _model_and_params(jnp.bfloat16, mesh)
    monkeypatch.setattr(transformer, "QKVProjection", _old_projection)
    _, old_params = _model_and_params(jnp.bfloat16, mesh)
    new_flat = flax.traverse_util.flatten_dict(new_params, sep="/")
    old_flat = flax.traverse_util.flatten_dict(old_params, sep="/")
    assert list(new_flat) == list(old_flat)
    assert [k for k in new_flat if "qkv" in k] == [
        "block0/attn/qkv/kernel", "block1/attn/qkv/kernel",
    ]
    for path, leaf in new_flat.items():
        assert leaf.shape == old_flat[path].shape, path
        assert leaf.dtype == old_flat[path].dtype, path
        assert leaf.sharding == old_flat[path].sharding, path
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(old_flat[path]), err_msg=path)
    kernel = new_flat["block0/attn/qkv/kernel"]
    assert kernel.shape == (32, 3, 4, 8) and kernel.dtype == jnp.float32


# The loss is the same number in both forms (the forward products are the
# same). A gradient is compared against its leaf's largest entry. float32:
# the same products summed in another order (read: 4e-7 of it). bfloat16: the
# input's gradient is three products rounded to bfloat16 and then added, where
# the old form rounded their float32 sum once, and that rounding (2**-8) runs
# through everything below the projection (read: 1.1e-2 and 1.6e-2 of it).
@pytest.mark.parametrize(
    "dtype,rtol,atol",
    [
        pytest.param(jnp.float32, 1e-5, 1e-5, id="float32"),
        pytest.param(jnp.bfloat16, 2e-2, 4e-2, id="bfloat16"),
    ],
)
def test_loss_and_every_gradient_agree_with_dense_general(monkeypatch, mesh, dtype, rtol, atol):
    new_loss, new_grads = _loss_and_grads(*_model_and_params(dtype, mesh))
    monkeypatch.setattr(transformer, "QKVProjection", _old_projection)
    old_loss, old_grads = _loss_and_grads(*_model_and_params(dtype, mesh))
    np.testing.assert_allclose(float(new_loss), float(old_loss), rtol=rtol)
    new_flat = flax.traverse_util.flatten_dict(new_grads, sep="/")
    old_flat = flax.traverse_util.flatten_dict(old_grads, sep="/")
    assert list(new_flat) == list(old_flat)
    for path, grad in new_flat.items():
        assert grad.shape == old_flat[path].shape and grad.dtype == jnp.float32, path
        scale = float(jnp.abs(old_flat[path]).max())
        np.testing.assert_allclose(
            np.asarray(grad), np.asarray(old_flat[path]),
            rtol=rtol, atol=atol * scale, err_msg=path,
        )


def test_sliced_kernels_keep_the_rule_of_the_stored_one():
    """On a mesh the stored kernel is P(fsdp, None, model, None); the
    gradient that comes back through the three slices has its sharding."""
    model, params = _model_and_params(jnp.float32, _model2_mesh())
    _, grads = _loss_and_grads(model, params)
    kernel = params["block0"]["attn"]["qkv"]["kernel"]
    assert tuple(kernel.sharding.spec) == ("fsdp", None, "model", None)
    grad = grads["block0"]["attn"]["qkv"]["kernel"]
    assert grad.sharding.is_equivalent_to(kernel.sharding, grad.ndim)


# The grouped projection (PR 33): each of q, k and v is a HeadProjection, whose
# weight gradient comes back with its layout stated, against
# nn.DenseGeneral((h, d)) over the same parameters. 6 query heads over 2 KV heads.
class _DenseGeneralProjections(nn.Module):
    """The old form of GroupedQKVProjection."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    dtype: object

    @nn.compact
    def __call__(self, x):
        def project(name, heads):
            return nn.DenseGeneral(
                (heads, self.head_dim), use_bias=False, dtype=self.dtype, name=name)(x)

        return project("q", self.num_heads), project("k", self.num_kv_heads), project(
            "v", self.num_kv_heads)


def _grouped(cls, dtype):
    return cls(6, 2, 8, dtype=dtype)


def _grouped_input(dtype):
    return jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32), dtype)


def test_grouped_parameter_tree_and_initial_values_are_dense_generals():
    x = _grouped_input(jnp.bfloat16)
    new = _grouped(transformer.GroupedQKVProjection, jnp.bfloat16).init(jax.random.PRNGKey(3), x)
    old = _grouped(_DenseGeneralProjections, jnp.bfloat16).init(jax.random.PRNGKey(3), x)
    new_flat = flax.traverse_util.flatten_dict(new["params"], sep="/")
    old_flat = flax.traverse_util.flatten_dict(old["params"], sep="/")
    assert {k: v.shape for k, v in new_flat.items()} == {
        "q/kernel": (32, 6, 8), "k/kernel": (32, 2, 8), "v/kernel": (32, 2, 8)}
    assert sorted(new_flat) == sorted(old_flat)
    for path, leaf in new_flat.items():
        assert leaf.dtype == old_flat[path].dtype == jnp.float32, path
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(old_flat[path]), err_msg=path)


@pytest.mark.parametrize("dtype", [
    pytest.param(jnp.float32, id="float32"), pytest.param(jnp.bfloat16, id="bfloat16")])
def test_grouped_value_and_gradients_are_dense_generals(dtype):
    """Value, input gradient and the three weight gradients: the same products
    with the same contractions (the weight gradient rounded to the compute
    dtype once, as DenseGeneral's is), so the same bits in either precision."""
    x = _grouped_input(dtype)
    new, old = _grouped(transformer.GroupedQKVProjection, dtype), _grouped(_DenseGeneralProjections, dtype)
    params = new.init(jax.random.PRNGKey(3), x)["params"]
    weights = [jax.random.normal(jax.random.PRNGKey(5 + i), (2, 24, h, 8), jnp.float32)
               for i, h in enumerate((6, 2, 2))]

    def run(module):
        def loss(p, x):
            outs = module.apply({"params": p}, x)
            return sum((o.astype(jnp.float32) * w).sum() for o, w in zip(outs, weights)), outs

        (_, outs), (d_params, d_x) = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(params, x)
        return {"d_x": d_x, **{f"out_{n}": o for n, o in zip("qkv", outs)},
                **{f"d_{n}": d_params[n]["kernel"] for n in "qkv"}}

    got, want = run(new), run(old)
    for name, leaf in got.items():
        assert leaf.shape == want[name].shape and leaf.dtype == want[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want[name]), err_msg=name)


def test_grouped_weight_gradients_come_back_with_their_layout_stated():
    """What keeps XLA from folding the weight gradient's turn, with those of
    the kernel and both moments, into its AdamW (tests/test_tpu_aot_compile.py
    holds the compiled form to it)."""
    x = _grouped_input(jnp.bfloat16)
    module = _grouped(transformer.GroupedQKVProjection, jnp.bfloat16)
    params = module.init(jax.random.PRNGKey(3), x)["params"]
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: sum(o.astype(jnp.float32).sum() for o in module.apply({"params": p}, x))))(params))
    assert text.count("layout_constraint") == 3          # one a kernel
