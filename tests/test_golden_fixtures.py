"""Loaders vs COMMITTED golden fixtures: the
fixtures in tests/golden/ are one-client byte-level files built to the real
formats' published specs (leaf benchmark JSON layout, TFF federated-EMNIST
h5 group structure, GLD-23k mapping CSV) — independent artifacts, not
files the loader tests synthesized from the loader's own assumptions."""

import os

import numpy as np

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


def test_leaf_golden_json():
    from fedml_tpu.data.leaf import load_femnist_leaf

    ds = load_femnist_leaf(os.path.join(GOLDEN, "leaf_femnist"))
    assert ds.num_clients == 1
    assert ds.client_x[0].shape == (3, 28, 28, 1)
    assert ds.client_y[0].dtype == np.int32
    assert ds.client_test_x[0].shape[0] == 2
    assert 0.0 <= ds.client_x[0].min() and ds.client_x[0].max() <= 1.0
    assert ds.num_classes == 62


def test_tff_h5_golden():
    import shutil
    import tempfile

    from fedml_tpu.data import tff_h5

    with tempfile.TemporaryDirectory() as d:
        shutil.copy(
            os.path.join(GOLDEN, "fed_emnist_train.h5"),
            os.path.join(d, tff_h5.FEMNIST_TRAIN),
        )
        shutil.copy(
            os.path.join(GOLDEN, "fed_emnist_test.h5"),
            os.path.join(d, tff_h5.FEMNIST_TEST),
        )
        ds = tff_h5.load_femnist(d)
    assert ds.num_clients == 1
    assert ds.client_x[0].shape == (4, 28, 28, 1)
    assert ds.client_x[0].dtype == np.float32
    assert ds.test_x.shape[0] == 2


def test_landmarks_golden_csv():
    from fedml_tpu.data.landmarks import load_landmarks

    ds = load_landmarks(
        os.path.join(GOLDEN, "landmarks"),
        train_map_file="federated_train.csv",
        test_map_file="test.csv",
        image_size=8,
    )
    assert ds.num_clients == 1
    assert ds.client_x[0].shape == (2, 8, 8, 3)
    # class ids are densified to 0..K-1 (consistently across splits): the
    # test image is class "5", same as train image golden_img_a
    assert sorted(ds.client_y[0].tolist()) == [0, 1]
    assert ds.test_x.shape == (1, 8, 8, 3)
    a_label = ds.client_y[0][0]  # golden_img_a, class "5"
    assert ds.test_y[0] == a_label
