"""Pretrained-weights path (ref resnet56(pretrained=True, path=...),
fedml_api/model/cv/resnet.py:200-222): torch .pth import into the Flax
resnet56, export back, and the npz save/load recipe."""

import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.pretrained import (
    export_torch_state_dict,
    import_torch_state_dict,
    load_pretrained,
    load_torch_checkpoint,
    save_pretrained,
)


@pytest.fixture(scope="module")
def template():
    import jax

    model = create_model("resnet56", "cifar10", (16, 16, 3), 10)
    return model, model.init(jax.random.PRNGKey(0))


def _leaves(tree):
    import jax

    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def test_torch_roundtrip(template):
    _, variables = template
    sd = export_torch_state_dict(variables)
    # reference naming spot checks
    assert "conv1.weight" in sd
    assert "layer1.0.conv1.weight" in sd
    assert "layer2.0.downsample.0.weight" in sd
    assert "layer2.0.downsample.1.running_mean" in sd
    assert "fc.weight" in sd and "fc.bias" in sd
    assert sd["conv1.weight"].shape[0] == 16  # torch OIHW: O first
    back = import_torch_state_dict(sd, variables)
    for a, b in zip(_leaves(variables), _leaves(back)):
        np.testing.assert_array_equal(a, b)


def test_torch_pth_file_with_module_prefix(template, tmp_path):
    torch = pytest.importorskip("torch")
    _, variables = template
    sd = {
        "module." + k: torch.from_numpy(np.ascontiguousarray(v))
        for k, v in export_torch_state_dict(variables).items()
    }
    path = tmp_path / "resnet56.pth"
    torch.save({"state_dict": sd}, path)  # reference checkpoint format
    back = load_torch_checkpoint(str(path), variables)
    for a, b in zip(_leaves(variables), _leaves(back)):
        np.testing.assert_array_equal(a, b)


def test_imported_weights_run_forward(template):
    import jax

    model, variables = template
    back = import_torch_state_dict(export_torch_state_dict(variables), variables)
    x = np.random.default_rng(0).normal(size=(2, 16, 16, 3)).astype(np.float32)
    ref_out, _ = model.apply(variables, x, train=False)
    out, _ = model.apply(back, x, train=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=1e-6)


def test_npz_recipe_and_shape_guard(template, tmp_path):
    _, variables = template
    path = str(tmp_path / "weights.npz")
    save_pretrained(path, variables)
    back = load_pretrained(path, variables)
    for a, b in zip(_leaves(variables), _leaves(back)):
        np.testing.assert_array_equal(a, b)

    sd = export_torch_state_dict(variables)
    sd["fc.weight"] = sd["fc.weight"][:, :3]
    with pytest.raises(ValueError):
        import_torch_state_dict(sd, variables)
    del sd["fc.weight"]
    with pytest.raises(KeyError):
        import_torch_state_dict(sd, variables)


def test_create_model_pretrained_kwarg(template, tmp_path):
    import jax

    _, variables = template
    path = str(tmp_path / "w.npz")
    save_pretrained(path, variables)
    loaded = create_model(
        "resnet56", "cifar10", (16, 16, 3), 10, pretrained=path
    )
    got = loaded.init(jax.random.PRNGKey(123))  # rng must not matter
    for a, b in zip(_leaves(variables), _leaves(got)):
        np.testing.assert_array_equal(a, b)


def test_committed_pretrained_resnet56_artifact_loads_and_performs():
    """The repo ships a REAL trained checkpoint:
    fedml_tpu/models/pretrained_weights/resnet56_cifar10_synth.npz,
    trained by examples/train_pretrained_resnet56.py on the synthetic
    cross-silo CIFAR-10 regime (the ref ships torch .pth checkpoints for
    resnet56 — resnet.py:200-222; real downloads are unavailable here, so
    the artifact's regime is the synthetic stand-in, recorded in the
    sibling .json). create_model(pretrained=...) must load it and
    reproduce the recorded accuracy on the regenerated dataset."""
    import json
    import os

    import jax
    import numpy as np

    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models import create_model
    from fedml_tpu.train.evaluate import evaluate

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(
        root, "fedml_tpu", "models", "pretrained_weights",
        "resnet56_cifar10_synth.npz",
    )
    with open(path.replace(".npz", ".json")) as f:
        meta = json.load(f)
    model = create_model(
        "resnet56", "cifar10", (32, 32, 3), 10, pretrained=path
    )
    variables = model.init(jax.random.PRNGKey(123))  # = the loaded weights
    # regenerate the EXACT dataset the meta records (deterministic seed)
    data = synthetic_classification(
        num_clients=10, num_classes=10, feat_shape=(32, 32, 3),
        samples_per_client=512, partition_method="homo", ragged=False,
        seed=0,
    )
    _, acc = evaluate(model, variables, data.test_x, data.test_y)
    # recorded 1.0 on-chip; CPU forward numerics may flip a borderline
    # sample or two
    assert float(acc) >= meta["test_acc"] - 0.03, (acc, meta)
    # and an untrained init is nowhere near it (the artifact carries real
    # training, not a lucky init)
    plain = create_model("resnet56", "cifar10", (32, 32, 3), 10)
    _, acc0 = evaluate(
        plain, plain.init(jax.random.PRNGKey(0)), data.test_x, data.test_y
    )
    assert float(acc0) < 0.5
