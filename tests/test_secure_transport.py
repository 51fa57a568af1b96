"""Secure aggregation in the transport round loop (ref distributed
turboaggregate): masked uploads, exact-weighted-average reconstruction,
and dropout mask recovery on the quorum path."""

import jax
import numpy as np

from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation
from fedml_tpu.config import (
    CommConfig,
    DataConfig,
    FedConfig,
    RunConfig,
    TrainConfig,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.secagg.secure_aggregation import (
    flatten_tree,
    mask_round_update,
    round_aggregator,
    unflatten_like,
    unmask_round_average,
)


def _fixture(secure):
    data = synthetic_classification(
        num_clients=4, num_classes=3, feat_shape=(5,), samples_per_client=12,
        partition_method="homo", seed=9,
    )
    model_def = lambda: ModelDef(
        module=LogisticRegression(num_classes=3), input_shape=(5,),
        num_classes=3, name="lr",
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=-1),
        fed=FedConfig(
            client_num_in_total=4, client_num_per_round=4, comm_round=3,
            epochs=1, frequency_of_the_test=3,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        comm=CommConfig(secure_agg=secure),
        seed=0,
    )
    return cfg, data, model_def


def test_secure_loopback_matches_plain():
    """The server never sees a raw update, yet the trained model equals the
    plain transport run up to the 2^-16 fixed-point grid."""
    from fedml_tpu.algorithms import FedAvgAPI

    cfg, data, model_def = _fixture(secure=True)
    sim = FedAvgAPI(cfg.replace(comm=CommConfig()), data, model_def())
    sim.train()
    server = run_loopback_federation(cfg, data, model_def())
    assert server.round_idx == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(sim.global_vars),
        jax.tree_util.tree_leaves(server.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3
        )


def test_secure_round_dropout_recovery():
    """A party that vanishes AFTER masking: survivors' masks toward it are
    unwound and the result is exactly the survivors' weighted average."""
    rng = np.random.default_rng(0)
    w_round = {"w": rng.normal(size=(6, 3)).astype(np.float32),
               "b": rng.normal(size=(3,)).astype(np.float32)}
    locals_ = [
        jax.tree_util.tree_map(
            lambda a, s=s: a + rng.normal(scale=0.01, size=a.shape).astype(a.dtype),
            w_round,
        )
        for s in range(4)
    ]
    ns = {0: 10.0, 1: 20.0, 2: 30.0, 3: 40.0}
    dim = sum(a.size for a in jax.tree_util.tree_leaves(w_round))
    agg = round_aggregator(4, dim, seed=3, round_idx=5)
    uploads = {
        i: mask_round_update(agg, i, locals_[i], w_round, ns[i])
        for i in range(4)
    }
    uploads.pop(2)  # party 2 drops after masking
    got = unmask_round_average(agg, uploads, ns, w_round)
    # expected: weighted average over survivors only
    flat_round, spec = flatten_tree(w_round)
    num = np.zeros_like(flat_round)
    for i in (0, 1, 3):
        fl, _ = flatten_tree(locals_[i])
        num += ns[i] * (fl - flat_round)
    expect = unflatten_like(spec, flat_round + num / (10 + 20 + 40))
    for k in w_round:
        np.testing.assert_allclose(got[k], expect[k], atol=5e-4)


def test_masked_upload_hides_update():
    """A single masked upload is statistically unrelated to the raw update
    (the mask is a full-range field element per coordinate)."""
    w_round = {"w": np.zeros((4, 4), np.float32)}
    w_local = {"w": np.full((4, 4), 0.01, np.float32)}
    agg = round_aggregator(3, 16, seed=1, round_idx=0)
    masked = mask_round_update(agg, 0, w_local, w_round, 5.0)
    from fedml_tpu.secagg.secure_aggregation import encode_fixed

    raw = encode_fixed(5.0 * 0.01 * np.ones(16))
    # masked differs from raw in (essentially) every coordinate
    assert np.mean(masked == raw) < 0.2


def test_mask_round_update_rejects_field_overflow():
    """Magnitudes that would wrap the fixed-point field raise at encode
    instead of silently corrupting the aggregate."""
    import pytest

    w_round = {"w": np.zeros((4,), np.float32)}
    w_local = {"w": np.full((4,), 10.0, np.float32)}
    agg = round_aggregator(4, 4, seed=0, round_idx=0)
    with pytest.raises(ValueError, match="field bound"):
        mask_round_update(agg, 0, w_local, w_round, 10_000.0)
    # in-range magnitudes pass
    mask_round_update(agg, 0, w_local, w_round, 12.0)


def test_dh_group_and_secret_space():
    """The key agreement is a 2048-bit MODP
    group (RFC 3526 group 14) with >= 128-bit secret space — nothing
    about the masks is brute-forceable."""
    from fedml_tpu.secagg import mpc

    p = mpc.MODP_2048_P
    assert p.bit_length() == 2048 and p % 2 == 1
    # RFC 3526 structure: top and bottom 64 bits are all-ones
    assert p >> (2048 - 64) == (1 << 64) - 1
    assert p & ((1 << 64) - 1) == (1 << 64) - 1
    # Fermat base-2 — catches any transcription error in the constant
    assert pow(2, p - 1, p) == 1
    # safe prime: q = (p-1)/2 is also prime (Miller-Rabin, fixed bases)
    q = (p - 1) // 2
    d, r = q - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for a in (2, 3, 5, 7, 11, 13, 17):
        x = pow(a, d, q)
        if x in (1, q - 1):
            continue
        for _ in range(r - 1):
            x = pow(x, 2, q)
            if x == q - 1:
                break
        else:
            raise AssertionError(f"(p-1)/2 failed Miller-Rabin base {a}")

    assert mpc.DH_SECRET_BITS >= 128
    sk = mpc.dh_secret()
    # the top bit is pinned: secret space is exactly 2^255
    assert 1 << (mpc.DH_SECRET_BITS - 1) <= sk < 1 << mpc.DH_SECRET_BITS
    assert mpc.dh_secret() != mpc.dh_secret()  # OS entropy, not a constant

    # key agreement symmetry + degenerate-pk rejection
    a, b = mpc.dh_secret(), mpc.dh_secret()
    assert mpc.dh_shared(a, mpc.dh_public(b)) == mpc.dh_shared(b, mpc.dh_public(a))
    import pytest

    for bad in (0, 1, p - 1, p, p + 1):
        with pytest.raises(ValueError):
            mpc.dh_shared(a, bad)


def test_pair_mask_kdf_properties():
    """Mask expansion: deterministic per (key, pair), distinct across
    pairs and keys, full-field-range uniform-ish."""
    from fedml_tpu.secagg import mpc
    from fedml_tpu.secagg.mpc import FIELD_PRIME

    k1 = mpc.dh_shared(mpc.dh_secret(), mpc.dh_public(mpc.dh_secret()))
    m = mpc.derive_pair_mask(k1, 0, 1, 4096)
    np.testing.assert_array_equal(m, mpc.derive_pair_mask(k1, 0, 1, 4096))
    assert np.any(m != mpc.derive_pair_mask(k1, 0, 2, 4096))
    assert np.any(m != mpc.derive_pair_mask(k1 + 1, 0, 1, 4096))
    assert np.all((0 <= m) & (m < FIELD_PRIME))
    # rough uniformity: mean of U[0, p) is p/2 within a few stddevs
    assert abs(m.mean() / FIELD_PRIME - 0.5) < 0.05


def _party_exchange(n_parties, dim, rngs=None):
    """Full client-held-key exchange: parties generate local keypairs, the
    'server' relays the pk registry (public material only)."""
    from fedml_tpu.secagg.secure_aggregation import ClientParty

    parties = [
        ClientParty(i, dim, rng=(rngs[i] if rngs else None))
        for i in range(n_parties)
    ]
    registry = {p.party: p.pk for p in parties}
    for p in parties:
        p.set_registry(registry)
    return parties


def test_client_held_keys_not_derivable_from_config_seed():
    """Round 2 derived all secret keys from
    config.seed, so the server could recompute every mask. Now two
    executions of the SAME configured round produce different masks
    (client-local entropy), while both decode to the same average."""
    from fedml_tpu.secagg.secure_aggregation import ServerAggregator

    w_round = {"w": np.zeros((8,), np.float32)}
    w_local = {"w": np.full((8,), 0.02, np.float32)}
    uploads = []
    for _ in range(2):
        parties = _party_exchange(3, 8)
        uploads.append(
            {p.party: p.masked_update(w_local, w_round, 4.0) for p in parties}
        )
    # masks differ run to run — nothing about them is derivable from any
    # shared configuration
    assert np.mean(uploads[0][0] == uploads[1][0]) < 0.2
    srv = ServerAggregator(8)
    for up in uploads:
        avg = srv.decode_average(
            srv.masked_sum(up), {0: 4.0, 1: 4.0, 2: 4.0}, w_round
        )
        np.testing.assert_allclose(avg["w"], 0.02, atol=5e-4)


def test_server_cannot_reconstruct_individual_update():
    """Give the server EVERYTHING it observes in a dropout-free round —
    the pk registry and every masked upload — and check an individual
    update is not recoverable while the sum is exact."""
    from fedml_tpu.secagg.secure_aggregation import (
        ServerAggregator,
        decode_fixed,
        encode_fixed,
    )

    dim = 16
    rng = np.random.default_rng(7)
    w_round = {"w": np.zeros((dim,), np.float32)}
    locals_ = [
        {"w": rng.normal(scale=0.01, size=(dim,)).astype(np.float32)}
        for _ in range(4)
    ]
    parties = _party_exchange(4, dim)
    ns = {i: 1.0 for i in range(4)}
    uploads = {
        p.party: p.masked_update(locals_[p.party], w_round, 1.0)
        for p in parties
    }
    srv = ServerAggregator(dim)
    # the sum is exact (fixed-point grid)
    avg = srv.decode_average(srv.masked_sum(uploads), ns, w_round)
    expect = np.mean([l["w"] for l in locals_], axis=0)
    np.testing.assert_allclose(avg["w"], expect, atol=5e-4)
    # ...but any single observed upload decodes to mask noise, nowhere
    # near the raw update: the best the server can do with its observations
    # is the sum. (The true update is ~0.01-scale; the masked decode is
    # uniform over the +-16k fixed-point range.)
    for i in range(4):
        single = decode_fixed(uploads[i], 1)
        err = np.abs(single - locals_[i]["w"])
        assert np.median(err) > 1.0, "masked upload leaked the raw update"
    # and the server object itself never held a secret
    assert not hasattr(srv, "sks") and not hasattr(srv, "pair_keys")


def test_client_party_dropout_recovery_exchange():
    """Registry party drops before uploading: survivors' recovery masks
    restore the survivors-only weighted average."""
    from fedml_tpu.secagg.secure_aggregation import ServerAggregator

    dim = 12
    rng = np.random.default_rng(3)
    w_round = {"w": rng.normal(size=(dim,)).astype(np.float32)}
    locals_ = [
        jax.tree_util.tree_map(
            lambda a: a + rng.normal(scale=0.01, size=a.shape).astype(a.dtype),
            w_round,
        )
        for _ in range(4)
    ]
    ns = {0: 10.0, 1: 20.0, 3: 40.0}
    parties = _party_exchange(4, dim)
    uploads = {
        i: parties[i].masked_update(locals_[i], w_round, n)
        for i, n in ns.items()
    }  # party 2 never uploads
    recovery = {i: parties[i].recovery_mask([2]) for i in uploads}
    srv = ServerAggregator(dim)
    total = srv.remove_dropout_masks(srv.masked_sum(uploads), recovery)
    got = srv.decode_average(total, ns, w_round)
    num = np.zeros(dim)
    for i, n in ns.items():
        num += n * (locals_[i]["w"] - w_round["w"])
    expect = w_round["w"] + num / sum(ns.values())
    np.testing.assert_allclose(got["w"], expect, atol=5e-4)


def test_secure_quorum_deadline_recovers_dropout():
    """End-to-end: a deadline quorum round with a straggler exercises the
    recovery path inside the server FSM (finite, reasonable model out)."""
    import fedml_tpu.algorithms.fedavg_transport as T

    cfg, data, model_def = _fixture(secure=True)
    # straggler delay (1.8s) > deadline (1.0s) but < 2 rounds' deadlines:
    # its round-r upload lands while round r+1 is still open, so the
    # server is alive to count the drop
    cfg = cfg.replace(
        fed=FedConfig(
            client_num_in_total=4, client_num_per_round=4, comm_round=3,
            epochs=1, frequency_of_the_test=3, deadline_s=1.0, min_clients=2,
        )
    )
    orig_train = T.LocalTrainer.train

    def slow_train(self, round_idx, variables):
        if self.client_index == 3:  # one straggler every round
            import time

            time.sleep(1.8)
        return orig_train(self, round_idx, variables)

    T.LocalTrainer.train = slow_train
    try:
        server = run_loopback_federation(cfg, data, model_def())
    finally:
        T.LocalTrainer.train = orig_train
    assert server.round_idx == 3
    assert server.dropped_uploads >= 1  # the straggler was dropped
    assert np.isfinite(server.history[-1]["Test/Loss"])


def test_secure_client_dead_before_pubkey_completes_on_quorum():
    """A client that dies BEFORE advertising its round key must not
    deadlock the key phase: after the deadline the server broadcasts the
    registry of parties heard so far and the round completes on quorum."""
    import fedml_tpu.algorithms.fedavg_transport as T

    cfg, data, model_def = _fixture(secure=True)
    cfg = cfg.replace(
        fed=FedConfig(
            client_num_in_total=4, client_num_per_round=4, comm_round=2,
            epochs=1, frequency_of_the_test=2, deadline_s=1.0, min_clients=2,
        )
    )
    orig = T.FedAvgClientManager._on_sync

    def dying_on_sync(self, msg):
        # rank 4 "dies" (stops responding entirely) from round 1 on
        if self.rank == 4 and msg.get("round_idx") >= 1:
            return
        return orig(self, msg)

    T.FedAvgClientManager._on_sync = dying_on_sync
    try:
        server = run_loopback_federation(cfg, data, model_def())
    finally:
        T.FedAvgClientManager._on_sync = orig
    assert server.round_idx == 2
    assert np.isfinite(server.history[-1]["Test/Loss"])
