"""Round-2 data loaders: ImageNet, Landmarks, UCI
streaming, NUS-WIDE + Lending Club vertical. Each gets a tiny fixture in the
real on-disk format, same pattern as tests/test_data_loaders.py."""

import csv
import os

import numpy as np
import pytest


def _png(path, size=8, seed=0):
    from PIL import Image

    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 255, size=(size, size, 3), dtype=np.uint8)
    Image.fromarray(arr).save(path)


# --- ImageNet -------------------------------------------------------------


def _write_imagenet(root, n_classes=3, per_class=4, size=8):
    for split in ("train", "val"):
        for c in range(n_classes):
            d = os.path.join(root, split, f"n{c:08d}")
            os.makedirs(d, exist_ok=True)
            n = per_class if split == "train" else 2
            for i in range(n):
                _png(os.path.join(d, f"img_{i}.png"), size=size, seed=c * 100 + i)


def test_imagenet_loader(tmp_path):
    from fedml_tpu.data.imagenet import load_imagenet

    _write_imagenet(str(tmp_path))
    data = load_imagenet(str(tmp_path), num_clients=3, image_size=8)
    assert data.num_clients == 3
    assert data.num_classes == 3
    assert sum(len(y) for y in data.client_y) == 12
    assert data.client_x[0].shape[1:] == (8, 8, 3)
    assert len(data.test_y) == 6
    # normalized with ImageNet stats: roughly centered
    assert abs(float(np.mean(data.test_x))) < 3.0


def test_imagenet_lda_partition(tmp_path):
    from fedml_tpu.data.imagenet import load_imagenet

    _write_imagenet(str(tmp_path), per_class=8)
    data = load_imagenet(
        str(tmp_path), num_clients=4, image_size=8,
        partition_method="hetero", partition_alpha=0.2,
    )
    sizes = [len(y) for y in data.client_y]
    assert sum(sizes) == 24 and data.num_clients == 4


def test_imagenet_registry(tmp_path):
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig
    from fedml_tpu.data import registry

    _write_imagenet(str(tmp_path))
    cfg = RunConfig(
        data=DataConfig(dataset="imagenet", data_dir=str(tmp_path)),
        fed=FedConfig(client_num_in_total=3),
    )
    # registry path: image_size default 224 would blow up 8x8 fixtures;
    # loader signature keeps data_dir first so direct use covers that —
    # registry smoke just confirms dispatch works
    data = registry.load(cfg)
    assert data.name == "imagenet"


# --- Landmarks ------------------------------------------------------------


def _write_landmarks(root, users=3, per_user=3, n_classes=2):
    img_dir = os.path.join(root, "images")
    os.makedirs(img_dir, exist_ok=True)
    rows = []
    k = 0
    for u in range(users):
        for i in range(per_user):
            iid = f"im{k:04d}"
            _png(os.path.join(img_dir, iid + ".png"), size=8, seed=k)
            rows.append({"user_id": str(u), "image_id": iid, "class": f"c{k % n_classes}"})
            k += 1
    with open(os.path.join(root, "mini_gld_train_split.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["user_id", "image_id", "class"])
        w.writeheader()
        w.writerows(rows)
    test_rows = []
    for i in range(3):
        iid = f"te{i:04d}"
        _png(os.path.join(img_dir, iid + ".png"), size=8, seed=1000 + i)
        test_rows.append({"image_id": iid, "class": f"c{i % n_classes}"})
    with open(os.path.join(root, "mini_gld_test.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["image_id", "class"])
        w.writeheader()
        w.writerows(test_rows)


def test_landmarks_loader(tmp_path):
    from fedml_tpu.data.landmarks import load_landmarks

    _write_landmarks(str(tmp_path))
    data = load_landmarks(str(tmp_path), image_size=8)
    assert data.num_clients == 3  # one shard per user_id: natural federation
    assert all(len(y) == 3 for y in data.client_y)
    assert data.num_classes == 2
    assert data.test_x.shape == (3, 8, 8, 3)


def test_landmarks_bad_mapping_raises(tmp_path):
    from fedml_tpu.data.landmarks import load_landmarks

    os.makedirs(tmp_path / "images", exist_ok=True)
    with open(tmp_path / "mini_gld_train_split.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["wrong", "cols"])
        w.writeheader()
        w.writerow({"wrong": "1", "cols": "2"})
    with open(tmp_path / "mini_gld_test.csv", "w") as f:
        f.write("image_id,class\n")
    with pytest.raises(ValueError, match="image_id and class"):
        load_landmarks(str(tmp_path), image_size=8)


# --- UCI streaming --------------------------------------------------------


def _write_susy(path, n=200, d=4, seed=3):
    rng = np.random.default_rng(seed)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        for _ in range(n):
            y = rng.integers(0, 2)
            # two feature regimes so k-means has something to find
            x = rng.normal(3.0 * y, 1.0, size=d)
            w.writerow([float(y)] + [round(float(v), 4) for v in x])


def test_uci_streaming_shapes_and_regimes(tmp_path):
    from fedml_tpu.data.uci import load_uci_streaming

    p = str(tmp_path / "susy.csv")
    _write_susy(p)
    xs, ys = load_uci_streaming(p, num_clients=4, samples_per_client=20, beta=0.5)
    assert xs.shape == (4, 20, 4) and ys.shape == (4, 20)
    assert set(np.unique(ys)) <= {0, 1}


def test_uci_streaming_feeds_decentralized(tmp_path):
    from fedml_tpu.algorithms.decentralized import DecentralizedAPI
    from fedml_tpu.data.uci import load_uci_streaming
    from fedml_tpu.models import ModelDef
    from fedml_tpu.models.linear import LogisticRegression
    from fedml_tpu.partition.topology import SymmetricTopologyManager

    p = str(tmp_path / "susy.csv")
    _write_susy(p)
    xs, ys = load_uci_streaming(p, num_clients=4, samples_per_client=30, beta=0.3)
    topo = SymmetricTopologyManager(4, neighbor_num=2)
    topo.generate_topology()
    model = ModelDef(LogisticRegression(num_classes=1), (4,), 1, name="lr")
    api = DecentralizedAPI(model, topo, lr=0.2, variant="dsgd")
    out = api.run(xs, ys.astype(np.float32))
    assert np.isfinite(out["regret"]).all()
    # separable regimes: online loss should drop
    assert out["regret"][-1] < out["regret"][2]


def test_uci_insufficient_samples_raises(tmp_path):
    from fedml_tpu.data.uci import load_uci_streaming

    p = str(tmp_path / "susy.csv")
    _write_susy(p, n=10)
    with pytest.raises(ValueError, match="need"):
        load_uci_streaming(p, num_clients=4, samples_per_client=20)


# --- NUS-WIDE -------------------------------------------------------------


def _write_nus(root, labels=("grass", "water"), n=24, d_feat=6, d_tags=8, seed=5):
    rng = np.random.default_rng(seed)
    for dtype, nn in (("Train", n), ("Test", max(8, n // 3))):
        lab_dir = os.path.join(root, "Groundtruth", "TrainTestLabels")
        os.makedirs(lab_dir, exist_ok=True)
        which = rng.integers(0, len(labels), size=nn)
        for li, lab in enumerate(labels):
            col = (which == li).astype(int)
            with open(os.path.join(lab_dir, f"Labels_{lab}_{dtype}.txt"), "w") as f:
                f.write("\n".join(str(v) for v in col))
        feat_dir = os.path.join(root, "Low_Level_Features")
        os.makedirs(feat_dir, exist_ok=True)
        feats = rng.normal(which[:, None], 0.3, size=(nn, d_feat))
        with open(os.path.join(feat_dir, f"{dtype}_Normalized_CH.dat"), "w") as f:
            for row in feats:
                f.write(" ".join(f"{v:.4f}" for v in row) + " \n")
        tag_dir = os.path.join(root, "NUS_WID_Tags")
        os.makedirs(tag_dir, exist_ok=True)
        tags = rng.integers(0, 2, size=(nn, d_tags))
        with open(os.path.join(tag_dir, f"{dtype}_Tags1k.dat"), "w") as f:
            for row in tags:
                f.write("\t".join(str(v) for v in row) + "\n")


def test_nus_wide_two_and_three_party(tmp_path):
    from fedml_tpu.data.vertical import load_nus_wide

    _write_nus(str(tmp_path))
    data2 = load_nus_wide(str(tmp_path), selected_labels=("grass", "water"), parties=2)
    assert len(data2.train_xs) == 2
    assert data2.train_xs[0].shape[1] == 6 and data2.train_xs[1].shape[1] == 8
    assert data2.train_xs[0].shape[0] == len(data2.train_y)
    assert set(np.unique(data2.train_y)) <= {0.0, 1.0}

    data3 = load_nus_wide(str(tmp_path), selected_labels=("grass", "water"), parties=3)
    assert len(data3.train_xs) == 3
    assert data3.train_xs[1].shape[1] + data3.train_xs[2].shape[1] == 8


def test_nus_wide_vfl_learns(tmp_path):
    from fedml_tpu.data.vertical import load_nus_wide, run_vfl

    _write_nus(str(tmp_path), n=64)
    data = load_nus_wide(str(tmp_path), selected_labels=("grass", "water"))
    _, stats = run_vfl(data, epochs=15, lr=0.1, batch_size=16)
    assert stats["acc"] > 0.8  # party A's features carry the label signal


# --- Lending Club ---------------------------------------------------------


def _write_lending_club(path, n=60, seed=6):
    rng = np.random.default_rng(seed)
    cols = [
        "annual_inc", "emp_length", "home_ownership", "verification_status",
        "grade", "loan_amnt", "int_rate", "installment", "term", "purpose",
        "dti", "total_pymnt", "total_rec_int", "total_rec_prncp",
        "last_pymnt_amnt", "loan_status",
    ]
    grades = list("ABCDEFG")
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        for _ in range(n):
            bad = rng.random() < 0.4
            w.writerow({
                "annual_inc": round(float(rng.uniform(2e4, 2e5)), 2),
                "emp_length": rng.choice(["< 1 year", "5 years", "10+ years", ""]),
                "home_ownership": rng.choice(["RENT", "OWN", "MORTGAGE"]),
                "verification_status": rng.choice(["Verified", "Not Verified"]),
                "grade": grades[int(rng.integers(0, 7))],
                "loan_amnt": round(float(rng.uniform(1e3, 4e4)), 2),
                "int_rate": round(float(rng.uniform(5, 30)), 2),
                "installment": round(float(rng.uniform(30, 1500)), 2),
                "term": " 36 months",
                "purpose": rng.choice(["credit_card", "car", "small_business"]),
                "dti": round(float(rng.uniform(0, 40)), 2),
                "total_pymnt": round(float(rng.uniform(0, 5e4)), 2),
                "total_rec_int": round(float(rng.uniform(0, 1e4)), 2),
                "total_rec_prncp": round(float(rng.uniform(0, 4e4)), 2),
                "last_pymnt_amnt": round(float(rng.uniform(0, 2e3)), 2),
                "loan_status": "Charged Off" if bad else "Fully Paid",
            })


def test_lending_club_three_party_split(tmp_path):
    from fedml_tpu.data.vertical import (
        QUALIFICATION_FEATURES, LOAN_FEATURES, REPAYMENT_FEATURES,
        load_lending_club,
    )

    p = str(tmp_path / "loans.csv")
    _write_lending_club(p)
    data = load_lending_club(p)
    assert [x.shape[1] for x in data.train_xs] == [
        len(QUALIFICATION_FEATURES), len(LOAN_FEATURES), len(REPAYMENT_FEATURES)
    ]
    assert len(data.train_y) + len(data.test_y) == 60
    assert 0.0 < float(data.train_y.mean()) < 1.0  # both classes present
    # z-scored features
    assert abs(float(data.train_xs[0].mean())) < 0.5


def test_lending_club_vfl_runs(tmp_path):
    from fedml_tpu.data.vertical import load_lending_club, run_vfl

    p = str(tmp_path / "loans.csv")
    _write_lending_club(p, n=80)
    data = load_lending_club(p)
    _, stats = run_vfl(data, epochs=5, lr=0.05, batch_size=16)
    assert np.isfinite(stats["loss"])


def test_synthetic_shakespeare_geometry():
    """shakespeare_synth: leaf-shakespeare shapes (80-char int windows,
    vocab 90), ragged shards, deterministic under seed, and the y label is
    the chain's next char (x windows stride by one)."""
    from fedml_tpu.data.synthetic import synthetic_shakespeare

    d1 = synthetic_shakespeare(num_clients=6, samples_per_client=20, seed=3)
    d2 = synthetic_shakespeare(num_clients=6, samples_per_client=20, seed=3)
    assert d1.num_clients == 6
    sizes = {len(y) for y in d1.client_y}
    assert len(sizes) > 1  # ragged
    for cx, cy in zip(d1.client_x, d1.client_y):
        assert cx.shape[1:] == (80,) and cx.dtype == np.int32
        assert cx.min() >= 0 and cx.max() < 90
        assert cy.min() >= 0 and cy.max() < 90
        # windows stride one char over one chain: next window starts with
        # this window shifted left, and y is the char that completes it
        np.testing.assert_array_equal(cx[1, :-1], cx[0, 1:])
        assert cy[0] == cx[1, -1]
    np.testing.assert_array_equal(d1.client_x[0], d2.client_x[0])
    np.testing.assert_array_equal(d1.test_y, d2.test_y)
