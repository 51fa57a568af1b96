"""Spilled client-state store (algorithms/state_store.py) — SCAFFOLD and
Ditto past the HBM budget ride the disk tier the data layer already uses
(round 3 refused at 8 GiB while the repo's own scale
story ran 100k clients on the mmap data store)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.ditto import DittoAPI
from fedml_tpu.algorithms.scaffold import ScaffoldAPI
from fedml_tpu.algorithms.state_store import MmapClientState, resolve_state_store
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model


def _cfg(rounds=3, per_round=4, total=8, state_store="auto", budget=8 << 30):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=total, client_num_per_round=per_round,
            comm_round=rounds, epochs=1, frequency_of_the_test=10_000,
            state_store=state_store, state_budget_bytes=budget,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )


def _data_model(total=8):
    data = synthetic_classification(
        num_clients=total, num_classes=3, feat_shape=(6,),
        samples_per_client=16, partition_method="homo", ragged=False, seed=0,
    )
    return data, create_model("lr", "synthetic", (6,), 3)


# ------------------------------------------------------------------- store
def test_mmap_state_lazy_init_and_roundtrip(tmp_path):
    init = {"a": np.full((3,), 7.0, np.float32), "b": np.zeros((2, 2), np.float32)}
    st = MmapClientState(init, n_clients=100, path=str(tmp_path / "s"))
    # untouched rows gather as the initial state — no write happened
    got = st.gather([5, 50])
    np.testing.assert_array_equal(got["a"], np.tile(init["a"], (2, 1)))
    assert st.initialized_count() == 0
    rows = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.ones((2, 2, 2), np.float32)}
    st.scatter([5, 50], rows)
    assert st.initialized_count() == 2
    back = st.gather([50, 5, 7])
    np.testing.assert_array_equal(back["a"][0], rows["a"][1])
    np.testing.assert_array_equal(back["a"][1], rows["a"][0])
    np.testing.assert_array_equal(back["a"][2], init["a"])  # still lazy
    # reopen (resume) — schema-checked, rows survive
    st.flush()
    st2 = MmapClientState(init, n_clients=100, path=str(tmp_path / "s"))
    np.testing.assert_array_equal(st2.gather([5])["a"][0], rows["a"][0])
    assert st2.initialized_count() == 2
    # schema mismatch refuses
    with pytest.raises(ValueError):
        MmapClientState(init, n_clients=99, path=str(tmp_path / "s"))


def test_resolve_state_store_modes():
    fed = FedConfig(state_store="auto", state_budget_bytes=1000)
    assert resolve_state_store(fed, 999) == "device"
    assert resolve_state_store(fed, 1001) == "mmap"
    assert resolve_state_store(FedConfig(state_store="mmap"), 1) == "mmap"
    with pytest.raises(ValueError):
        resolve_state_store(FedConfig(state_store="hbm"), 1)


# ---------------------------------------------------- bit-identical oracles
def test_scaffold_spilled_bitmatches_device_store():
    """The spilled run and the in-HBM run are the SAME math: gather and
    scatter are exact row copies, the in-program compute is the same
    code. Exact equality, not allclose."""
    data, model = _data_model()
    dev = ScaffoldAPI(_cfg(state_store="device"), data, model)
    spill = ScaffoldAPI(_cfg(state_store="mmap"), data, model)
    assert dev._state_mode == "device" and spill._state_mode == "mmap"
    for r in range(3):
        dev.train_round(r)
        spill.train_round(r)
    for a, b in zip(
        jax.tree_util.tree_leaves(dev.global_vars),
        jax.tree_util.tree_leaves(spill.global_vars),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(dev.c_server),
        jax.tree_util.tree_leaves(spill.c_server),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-client control rows match too
    sampled_all = sorted(
        {int(i) for r in range(3) for i in dev._round_plan(r)[0]}
    )
    rows = spill._c_store.gather(sampled_all)
    for a, b in zip(
        jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(
                lambda s: s[np.asarray(sampled_all)], dev.c_stack
            )
        ),
        jax.tree_util.tree_leaves(rows),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ditto_spilled_bitmatches_device_store():
    data, model = _data_model()
    dev = DittoAPI(_cfg(state_store="device"), data, model, lam=0.1)
    spill = DittoAPI(_cfg(state_store="mmap"), data, model, lam=0.1)
    for r in range(3):
        dev.train_round(r)
        spill.train_round(r)
    for a, b in zip(
        jax.tree_util.tree_leaves(dev.global_vars),
        jax.tree_util.tree_leaves(spill.global_vars),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for i in range(8):
        for a, b in zip(
            jax.tree_util.tree_leaves(dev._personal_row(i)),
            jax.tree_util.tree_leaves(spill._personal_row(i)),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # personalized eval runs off the spilled store
    row = spill.personalized_test_on_clients()
    assert np.isfinite(row["Personalized/Acc"])


def test_spilled_checkpoint_resume_exact():
    """Kill-and-resume with the spilled store: the store directory is the
    durable state; a resumed run continues bit-identically."""
    data, model = _data_model()
    a = ScaffoldAPI(_cfg(rounds=6, state_store="mmap"), data, model)
    for r in range(3):
        a.train_round(r)
    state = a.checkpoint_state()
    gv = jax.device_get(a.global_vars)
    b = ScaffoldAPI(
        _cfg(rounds=6, state_store="mmap"), data, model
    )
    b.global_vars = jax.tree_util.tree_map(jnp.asarray, gv)
    b.restore_state(state)
    for r in range(3, 6):
        a.train_round(r)
        b.train_round(r)
    for x, y in zip(
        jax.tree_util.tree_leaves(a.global_vars),
        jax.tree_util.tree_leaves(b.global_vars),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------- 10k scale
@pytest.mark.parametrize("api_cls,kw", [(ScaffoldAPI, {}), (DittoAPI, {"lam": 0.1})])
def test_stateful_10k_clients_spilled(api_cls, kw):
    """10k-client SCAFFOLD and Ditto in CI at
    reduced shape — a 1-byte budget forces the spill; rounds run, rows
    update, and nothing materializes the [N, ...] stack in RAM."""
    n = 10_000
    data = synthetic_classification(
        num_clients=64, num_classes=3, feat_shape=(6,),
        samples_per_client=8, partition_method="homo", ragged=False, seed=1,
    )
    # a 10k-client federation over 64 distinct shards (shared data keeps
    # the fixture small; the STATE store sees all 10k client ids)
    data = dataclasses.replace(
        data,
        client_x=[data.client_x[i % 64] for i in range(n)],
        client_y=[data.client_y[i % 64] for i in range(n)],
    )
    model = create_model("lr", "synthetic", (6,), 3)
    cfg = _cfg(rounds=2, per_round=16, total=n, state_store="auto", budget=1)
    api = api_cls(cfg, data, model, **kw)
    assert api._state_mode == "mmap"
    touched = set()
    for r in range(2):
        sampled, metrics = api.train_round(r)
        touched.update(int(i) for i in sampled)
        assert np.isfinite(float(metrics["loss_sum"]))
    store = api._c_store if api_cls is ScaffoldAPI else api._v_store
    assert store.n == n
    assert store.initialized_count() == len(touched)


def test_self_created_temp_store_dir_is_cleaned_up():
    """Advisor r4: a store spilling into a self-created temp dir must not
    leak N x |params| bytes of disk per run — the dir is removed when the
    store is garbage-collected. A user-supplied path is never removed."""
    import gc
    import os
    import tempfile

    import numpy as np

    from fedml_tpu.algorithms.state_store import MmapClientState

    init = {"w": np.zeros((4, 3), np.float32)}
    store = MmapClientState(init, n_clients=16)
    tmp_path = store.path
    store.scatter([1, 2], {"w": np.ones((2, 4, 3), np.float32)})
    assert os.path.isdir(tmp_path)
    del store
    gc.collect()
    assert not os.path.exists(tmp_path), "self-created temp dir leaked"

    user_dir = tempfile.mkdtemp(prefix="fedml_tpu_user_state_")
    store = MmapClientState(init, n_clients=16, path=user_dir)
    store.scatter([0], {"w": np.ones((1, 4, 3), np.float32)})
    del store
    gc.collect()
    assert os.path.isdir(user_dir), "user-supplied dir must survive"
    # and a fresh store resumes from it
    store2 = MmapClientState(init, n_clients=16, path=user_dir)
    assert store2.initialized_ids().tolist() == [0]


def test_empty_string_path_is_treated_as_unset():
    """FedConfig.state_dir defaults to "" — a store built with path=""
    must behave exactly like path=None: temp dir, cleaned up at gc."""
    import gc
    import os

    import numpy as np

    from fedml_tpu.algorithms.state_store import MmapClientState

    store = MmapClientState({"w": np.zeros((2,), np.float32)}, 4, path="")
    p = store.path
    assert p and os.path.isdir(p)
    del store
    gc.collect()
    assert not os.path.exists(p)


# ------------------------------------------------- spill x mesh composition
def test_scaffold_spilled_mesh_matches_single_chip():
    """The two scale stories COMPOSE: 100k-on-disk
    state AND the multi-chip mesh. The sharded cohort round at the same
    seed matches the single-chip spilled run to float tolerance, including
    cohorts that don't divide the mesh (dummy-padded rows)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from fedml_tpu.parallel import DistributedScaffoldAPI

    data, model = _data_model(total=12)
    cfg = _cfg(rounds=4, per_round=5, total=12, state_store="mmap")
    sim = ScaffoldAPI(cfg, data, model)
    mesh_api = DistributedScaffoldAPI(cfg, data, model)
    assert sim._state_mode == mesh_api._state_mode == "mmap"
    saw_nondivisible = False
    for r in range(4):
        sampled, m_sim = sim.train_round(r)
        _, m_mesh = mesh_api.train_round(r)
        saw_nondivisible |= len(sampled) % mesh_api.n_shards != 0
        np.testing.assert_allclose(
            float(m_sim["loss_sum"]), float(m_mesh["loss_sum"]), rtol=1e-5
        )
    assert saw_nondivisible  # 5 % 8 != 0 — padding actually exercised
    for name, a, b in (
        ("params", sim.global_vars, mesh_api.global_vars),
        ("c_server", sim.c_server, mesh_api.c_server),
        (
            "store_rows",
            sim._c_store.gather(np.arange(12)),
            mesh_api._c_store.gather(np.arange(12)),
        ),
    ):
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6,
                err_msg=name,
            )
    assert sim._c_store.initialized_ids().tolist() == \
        mesh_api._c_store.initialized_ids().tolist()


def test_ditto_spilled_mesh_matches_single_chip():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from fedml_tpu.parallel import DistributedDittoAPI

    data, model = _data_model(total=12)
    cfg = _cfg(rounds=3, per_round=5, total=12, state_store="mmap")
    sim = DittoAPI(cfg, data, model, lam=0.1)
    mesh_api = DistributedDittoAPI(cfg, data, model, lam=0.1)
    assert sim._state_mode == mesh_api._state_mode == "mmap"
    for r in range(3):
        sim.train_round(r)
        mesh_api.train_round(r)
    for x, y in zip(
        jax.tree_util.tree_leaves(sim.global_vars),
        jax.tree_util.tree_leaves(mesh_api.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
        )
    for x, y in zip(
        jax.tree_util.tree_leaves(sim._v_store.gather(np.arange(12))),
        jax.tree_util.tree_leaves(mesh_api._v_store.gather(np.arange(12))),
    ):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-6
        )


# ------------------------------------------------------- cohort prefetcher
def test_cohort_prefetcher_excludes_in_flight_rows(tmp_path):
    """The overlap contract: rows being scattered are excluded from the
    background read and re-fetched at take() AFTER the scatter landed —
    the prefetched cohort must reflect the post-scatter store exactly."""
    from fedml_tpu.algorithms.state_store import CohortPrefetcher

    init = {"w": np.zeros((2,), np.float32)}
    st = MmapClientState(init, n_clients=10, path=str(tmp_path / "s"))
    pf = CohortPrefetcher(st)
    # round r writes rows {1, 2}; round r+1 wants {2, 3} (overlap: 2)
    pf.launch(1, [2, 3], exclude={1, 2})
    pf._thread.join()  # background read done BEFORE the scatter below
    st.scatter([1, 2], {"w": np.asarray([[10, 10], [20, 20]], np.float32)})
    got = pf.take(1, [2, 3])
    np.testing.assert_array_equal(got["w"][0], [20, 20])  # post-scatter!
    np.testing.assert_array_equal(got["w"][1], [0, 0])
    # mismatched take falls back to a plain gather
    pf.launch(2, [4], exclude=set())
    got = pf.take(3, [5, 6])
    np.testing.assert_array_equal(got["w"], np.zeros((2, 2)))
    pf.cancel()
