"""Shape/param sanity for the model zoo (ref: the reference's only model test
is a param/FLOP counter, fedml_api/model/cv/test_cnn.py:1-14 — we check
init+apply shapes, dtype, and train-mode mutability instead)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model

# heavy=True cases only shape-check via jax.eval_shape (no XLA compile):
# compiling mobilenet_v3/efficientnet/etc. on the CPU test mesh costs
# 10-45 s EACH and dominated the suite. Execution
# coverage for the conv families is kept by the executed rows below
# (resnet56 BN, mobilenet depthwise) plus the federated integration tests
CASES = [
    # (model, dataset, input_shape, num_classes, kw, logits_shape_fn, heavy)
    ("lr", "mnist", (28, 28, 1), 10, {}, lambda B: (B, 10), False),
    ("cnn", "femnist", (28, 28, 1), 62, {}, lambda B: (B, 62), False),
    ("cnn_dropout", "femnist", (28, 28, 1), 62, {}, lambda B: (B, 62), False),
    ("rnn", "shakespeare", (20,), 90, {}, lambda B: (B, 90), False),
    ("rnn", "fed_shakespeare", (20,), 90, {}, lambda B: (B, 20, 90), False),
    ("rnn", "stackoverflow_nwp", (20,), 10004, {}, lambda B: (B, 20, 10004), True),
    ("resnet56", "cifar10", (32, 32, 3), 10, {}, lambda B: (B, 10), False),
    ("resnet18_gn", "fed_cifar100", (24, 24, 3), 100, {}, lambda B: (B, 100), True),
    ("mobilenet", "cifar100", (32, 32, 3), 100, {}, lambda B: (B, 100), False),
    ("mobilenet_v3", "cifar10", (32, 32, 3), 10, {}, lambda B: (B, 10), True),
    ("vgg11", "cifar10", (32, 32, 3), 10, {}, lambda B: (B, 10), True),
    ("vgg16_bn", "cifar10", (32, 32, 3), 10, {}, lambda B: (B, 10), True),
    ("efficientnet", "cifar10", (32, 32, 3), 10, {}, lambda B: (B, 10), True),
]


@pytest.mark.parametrize(
    "name,ds,shape,classes,kw,out_fn,heavy",
    CASES,
    ids=[f"{c[0]}-{c[1]}" for c in CASES],
)
def test_model_shapes(name, ds, shape, classes, kw, out_fn, heavy):
    model = create_model(name, ds, shape, classes, **kw)
    rng = jax.random.PRNGKey(0)
    B = 2
    in_dtype = (
        jnp.int32 if model.input_dtype == jnp.int32 else jnp.float32
    )
    if heavy:
        # abstract trace: checks init/apply wiring and logits shapes for
        # BOTH modes without compiling or executing anything
        variables = jax.eval_shape(model.init, rng)
        xs = jax.ShapeDtypeStruct((B,) + shape, in_dtype)
        out, _ = jax.eval_shape(
            lambda v, x: model.apply(v, x, train=False), variables, xs
        )
        assert out.shape == out_fn(B)
        out_t, vars_train = jax.eval_shape(
            lambda v, x, r: model.apply(v, x, train=True, rng=r),
            variables,
            xs,
            jax.random.fold_in(rng, 1),
        )
        assert out_t.shape == out_fn(B)
        if model.has_batch_stats:
            assert "batch_stats" in vars_train
        return
    variables = model.init(rng)
    if in_dtype == jnp.int32:
        x = jnp.ones((B,) + shape, jnp.int32)
    else:
        x = jnp.zeros((B,) + shape, jnp.float32)
    # eval mode
    out, vars_eval = model.apply(variables, x, train=False)
    assert out.shape == out_fn(B)
    assert np.all(np.isfinite(np.asarray(out)))
    # train mode must run and (for BN models) mutate batch_stats
    out_t, vars_train = model.apply(
        variables, x, train=True, rng=jax.random.fold_in(rng, 1)
    )
    assert out_t.shape == out_fn(B)
    if model.has_batch_stats:
        assert "batch_stats" in vars_train


def test_gan_shapes():
    from fedml_tpu.models.gan import MNISTGan

    m = MNISTGan()
    z = jnp.zeros((4, 100))
    x = jnp.zeros((4, 28, 28, 1))
    variables = m.init(
        {"params": jax.random.PRNGKey(0)}, z, x, train=False
    )
    fake, d_fake, d_real = m.apply(variables, z, x, train=False)
    assert fake.shape == (4, 28, 28, 1)
    assert d_fake.shape == (4, 1) and d_real.shape == (4, 1)


def test_vfl_models():
    from fedml_tpu.models.vfl import VFLClassifier, VFLFeatureExtractor

    fe = VFLFeatureExtractor(output_dim=16)
    v = fe.init(jax.random.PRNGKey(0), jnp.zeros((3, 30)))
    feats = fe.apply(v, jnp.zeros((3, 30)))
    assert feats.shape == (3, 16)
    clf = VFLClassifier(output_dim=2)
    vc = clf.init(jax.random.PRNGKey(1), feats)
    assert clf.apply(vc, feats).shape == (3, 2)


def test_registry_unknown_raises():
    with pytest.raises(KeyError):
        create_model("nope", "mnist", (1,), 2)
