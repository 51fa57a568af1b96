"""Client-level DP-FedAvg + RDP accountant (fedml_tpu/privacy/) — the
accounted upgrade over the reference's ad-hoc weak-DP noise
(robust_aggregation.py:38-55, which never reports an epsilon)."""

import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.privacy import (
    DpConfig,
    DPFedAvgAPI,
    RdpAccountant,
    rdp_subsampled_gaussian,
)
from fedml_tpu.privacy.dp_fedavg import clip_update_tree


# ---------------------------------------------------------------- accountant
def test_rdp_reduces_to_plain_gaussian_at_q1():
    """Internal consistency: at q=1 the subsampled bound must equal the
    analytic Gaussian RDP alpha/(2 sigma^2) exactly."""
    for sigma in (0.5, 1.0, 4.0):
        for alpha in (2, 8, 64):
            assert rdp_subsampled_gaussian(1.0, sigma, alpha) == pytest.approx(
                alpha / (2 * sigma**2)
            )


def test_rdp_monotonicity():
    """More rounds, more sampling, or less noise => more epsilon."""
    def eps(q, z, rounds):
        a = RdpAccountant()
        a.step(q, z, rounds=rounds)
        return a.epsilon(1e-5)[0]

    assert eps(0.1, 1.0, 10) < eps(0.1, 1.0, 100) < eps(0.1, 1.0, 1000)
    assert eps(0.01, 1.0, 100) < eps(0.1, 1.0, 100) < eps(0.5, 1.0, 100)
    assert eps(0.1, 4.0, 100) < eps(0.1, 1.0, 100) < eps(0.1, 0.6, 100)


def test_rdp_subsampling_amplifies():
    """Privacy amplification: q < 1 must beat the unsampled mechanism."""
    a_sub, a_full = RdpAccountant(), RdpAccountant()
    a_sub.step(0.05, 1.0, rounds=100)
    a_full.step(1.0, 1.0, rounds=100)
    assert a_sub.epsilon(1e-5)[0] < a_full.epsilon(1e-5)[0] / 3


def test_rdp_input_validation():
    with pytest.raises(ValueError):
        rdp_subsampled_gaussian(1.5, 1.0, 2)
    with pytest.raises(ValueError):
        rdp_subsampled_gaussian(0.5, 0.0, 2)
    with pytest.raises(ValueError):
        rdp_subsampled_gaussian(0.5, 1.0, 1)
    with pytest.raises(ValueError):
        RdpAccountant().epsilon(0.0)


# ---------------------------------------------------------------- clipping
def test_clip_update_tree_bounds_full_norm():
    g = {"a": jnp.zeros((3,)), "b": jnp.zeros((2, 2))}
    l = {"a": jnp.full((3,), 10.0), "b": jnp.full((2, 2), -10.0)}
    c = clip_update_tree(l, g, clip_norm=1.0)
    total = math.sqrt(
        sum(float(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(c))
    )
    assert total == pytest.approx(1.0, rel=1e-5)
    # a small update passes through unchanged
    s = {"a": jnp.full((3,), 0.01), "b": jnp.full((2, 2), 0.01)}
    c2 = clip_update_tree(s, g, clip_norm=1.0)
    for x, y in zip(
        jax.tree_util.tree_leaves(c2), jax.tree_util.tree_leaves(s)
    ):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


# ---------------------------------------------------------------- round/API
def _cfg(rounds=3, per_round=4, total=8):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=total, client_num_per_round=per_round,
            comm_round=rounds, epochs=1, frequency_of_the_test=10_000,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )


def _data_model():
    data = synthetic_classification(
        num_clients=8, num_classes=3, feat_shape=(6,), samples_per_client=16,
        partition_method="homo", ragged=False, seed=0,
    )
    return data, create_model("lr", "synthetic", (6,), 3)


def test_zero_noise_huge_clip_equals_uniform_mean_fedavg():
    """Degenerate-config oracle: z->0, S->inf and q=1 (per_round == total,
    so the Poisson draw includes everyone surely) turn DP-FedAvg into
    plain FedAvg with UNIFORM weights — with equal shard sizes that is
    exactly the sample-weighted FedAvg round."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    data, model = _data_model()
    # clip far above any real update norm (but not so large that the
    # noise stddev z*S/m becomes visible even at tiny z)
    dp_api = DPFedAvgAPI(
        _cfg(per_round=8), data, model,
        dp=DpConfig(clip_norm=1e4, noise_multiplier=1e-15),
    )
    plain = FedAvgAPI(_cfg(per_round=8), data, model)
    for r in range(3):
        dp_api.train_round(r)
        plain.train_round(r)
    for a, b in zip(
        jax.tree_util.tree_leaves(dp_api.global_vars),
        jax.tree_util.tree_leaves(plain.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


_TEST_SECRET = 0xDEADBEEF_CAFEBABE_0123456789ABCDEF  # 125-bit repro secret


def test_noise_is_applied_and_seeded():
    data, model = _data_model()
    mk = lambda: DPFedAvgAPI(
        _cfg(rounds=1), data, model,
        dp=DpConfig(
            clip_norm=0.5, noise_multiplier=1.0, sample_secret=_TEST_SECRET
        ),
    )
    a, b = mk(), mk()
    a.train_round(0)
    b.train_round(0)
    # same seed => identical noised result (reproducible)
    for x, y in zip(
        jax.tree_util.tree_leaves(a.global_vars),
        jax.tree_util.tree_leaves(b.global_vars),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and it differs from the noiseless run
    c = DPFedAvgAPI(
        _cfg(rounds=1), data, model,
        dp=DpConfig(
            clip_norm=0.5, noise_multiplier=1e-12, sample_secret=_TEST_SECRET
        ),
    )
    c.train_round(0)
    diffs = [
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(
            jax.tree_util.tree_leaves(a.global_vars),
            jax.tree_util.tree_leaves(c.global_vars),
        )
    ]
    assert max(diffs) > 1e-4


def test_dp_run_learns_and_reports_epsilon():
    data, model = _data_model()
    api = DPFedAvgAPI(
        _cfg(rounds=20, per_round=8), data, model,
        dp=DpConfig(clip_norm=2.0, noise_multiplier=0.3, delta=1e-5),
    )
    final = api.train()
    assert final["DP/epsilon"] > 0
    assert final["DP/rounds_accounted"] == 20
    _, acc = api.evaluate_global()
    assert acc > 0.8, f"DP run failed to learn: acc={acc}"
    # accounting matches a hand-composed ledger
    ref = RdpAccountant()
    ref.step(1.0, 0.3, rounds=20)
    assert final["DP/epsilon"] == pytest.approx(ref.epsilon(1e-5)[0], rel=1e-6)


def test_ledger_survives_checkpoint_roundtrip():
    """A resumed DP run must carry the PRE-crash privacy spend — a reset
    ledger would under-report epsilon for updates already released."""
    data, model = _data_model()
    dp = DpConfig(clip_norm=1.0, noise_multiplier=0.8)
    a = DPFedAvgAPI(_cfg(rounds=6), data, model, dp=dp)
    for r in range(6):
        a.train_round(r)
    state = a.checkpoint_state()
    b = DPFedAvgAPI(_cfg(rounds=6), data, model, dp=dp)
    b.restore_state(state)
    assert b.accountant.rounds == 6
    assert b.privacy_spent()["DP/epsilon"] == a.privacy_spent()["DP/epsilon"]
    # the sampling secret rides with the ledger: the resumed run continues
    # the SAME participation stream (a re-draw would fork the mechanism
    # away from the accounted one mid-run)
    assert b._sample_secret == a._sample_secret
    for r in range(6, 10):
        assert b._sample_clients(r).tolist() == a._sample_clients(r).tolist()


def test_restore_of_another_secret_redraws_planned_cohorts():
    """A plan memoised before the restore (``round_program``, a warm-up's
    stash) drew its cohort from the construction-time secret; after a
    checkpoint brings another secret the same rounds must be drawn again
    from it, or the executed cohorts leave the accounted stream."""
    data, model = _data_model()
    dp = DpConfig(clip_norm=1.0, noise_multiplier=0.8)
    a = DPFedAvgAPI(_cfg(rounds=40), data, model, dp=dp)
    b = DPFedAvgAPI(_cfg(rounds=40), data, model, dp=dp)
    assert a._sample_secret != b._sample_secret
    rounds = range(40)
    stale = [b._round_plan(r)[0].tolist() for r in rounds]
    b._round_may_pad(0)
    b.warmup()  # stashes round 0's batch, placed for the stale cohort
    assert 0 in b._warm_placed
    want = [a._sample_clients(r).tolist() for r in rounds]
    assert stale != want  # 40 Poisson draws of 8 clients from two secrets
    b.restore_state(a.checkpoint_state())
    assert not b._warm_placed and not b._may_pad_cache
    assert [b._round_plan(r)[0].tolist() for r in rounds] == want
    # the same secret again invalidates nothing
    plans = dict(b._round_plans)
    b.restore_state(a.checkpoint_state())
    assert all(b._round_plans[r] is plans[r] for r in rounds)


def test_dp_sampling_secret_is_os_entropy_not_config_seed():
    """Advisor r4 (medium): config.seed defaults to 0 and is public/reused
    (data shuffling, broadcast init), so the participation stream must
    come from OS entropy by default — two default-constructed APIs at the
    same config.seed draw DIFFERENT cohorts — and an explicit low-entropy
    secret must warn that amplification is void."""
    data, model = _data_model()
    a = DPFedAvgAPI(_cfg(), data, model)
    b = DPFedAvgAPI(_cfg(), data, model)
    assert a._sample_secret != b._sample_secret
    assert a._sample_secret.bit_length() > 64  # 128-bit draw
    cohorts_a = [a._sample_clients(r).tolist() for r in range(30)]
    cohorts_b = [b._sample_clients(r).tolist() for r in range(30)]
    assert cohorts_a != cohorts_b
    with pytest.warns(UserWarning, match="entropy"):
        DPFedAvgAPI(
            _cfg(), data, model,
            dp=DpConfig(sample_secret=0),  # the old config.seed default
        )
    # a high-entropy explicit secret (tests/repro/resume) does not warn
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        DPFedAvgAPI(
            _cfg(), data, model, dp=DpConfig(sample_secret=_TEST_SECRET)
        )


def test_cli_rejects_degenerate_dp_flags():
    from click.testing import CliRunner

    from fedml_tpu.cli import main

    base = ["--algorithm", "dp_fedavg", "--dataset", "synthetic",
            "--model", "lr", "--comm_round", "1"]
    for bad in (["--dp_noise_multiplier", "0"], ["--dp_clip", "-1"],
                ["--dp_delta", "0"]):
        result = CliRunner().invoke(main, base + bad)
        assert result.exit_code != 0, bad
        assert "dp_" in result.output, bad


def test_mesh_dp_matches_vmap():
    """DistributedDPFedAvgAPI (psum uniform mean + the same clip/noise
    hooks) == the single-chip DPFedAvgAPI at the same seed — the noise
    rng chain is identical, so results agree to float tolerance."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from fedml_tpu.parallel import DistributedDPFedAvgAPI

    data, model = _data_model()
    dp = DpConfig(clip_norm=0.5, noise_multiplier=0.7)
    sim = DPFedAvgAPI(_cfg(rounds=3, per_round=8), data, model, dp=dp)
    mesh = DistributedDPFedAvgAPI(
        _cfg(rounds=3, per_round=8), data, model, dp=dp
    )
    for r in range(3):
        sim.train_round(r)
        mesh.train_round(r)
    assert mesh.accountant.rounds == sim.accountant.rounds == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(sim.global_vars),
        jax.tree_util.tree_leaves(mesh.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_mesh_dp_poisson_cohort_matches_vmap():
    """q < 1: realized Poisson cohorts vary per round and need NOT divide
    the mesh — padding rows are excluded by the aggregate's inclusion
    mask, so the mesh run still bit-matches the single-chip simulator."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from fedml_tpu.parallel import DistributedDPFedAvgAPI

    data, model = _data_model()
    # the two runtimes must draw the SAME Poisson cohorts to be comparable
    # — share an explicit repro secret (each would otherwise draw its own
    # OS-entropy stream)
    dp = DpConfig(
        clip_norm=0.5, noise_multiplier=0.7, sample_secret=_TEST_SECRET
    )
    sim = DPFedAvgAPI(_cfg(rounds=4, per_round=5), data, model, dp=dp)
    mesh = DistributedDPFedAvgAPI(
        _cfg(rounds=4, per_round=5), data, model, dp=dp
    )
    saw_nondivisible = False
    for r in range(4):
        sampled, _ = sim.train_round(r)
        mesh.train_round(r)
        saw_nondivisible |= len(sampled) % mesh.n_shards != 0
    for a, b in zip(
        jax.tree_util.tree_leaves(sim.global_vars),
        jax.tree_util.tree_leaves(mesh.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
    # the run must actually have exercised a cohort that doesn't divide
    # the mesh — otherwise this test silently degrades to the q=1 one
    assert saw_nondivisible


# ------------------------------------------------------------ Poisson sampler
def test_poisson_sampling_matches_accounted_q():
    """The executed inclusion frequency is the accounted q (LLN check),
    and the API's sampler and accountant share the same q object."""
    from fedml_tpu.privacy.dp_fedavg import poisson_client_sampling

    N, q = 64, 0.25
    hits = np.zeros(N)
    rounds = 400
    for r in range(rounds):
        hits[poisson_client_sampling(0, r, N, q)] += 1
    freq = hits / rounds
    # per-client binomial stddev ~ sqrt(q(1-q)/rounds) ~ 0.022
    assert abs(freq.mean() - q) < 0.01
    assert np.all(np.abs(freq - q) < 0.1)

    data, model = _data_model()
    api = DPFedAvgAPI(_cfg(), data, model)
    assert api.sampling == "poisson"
    assert api._q == pytest.approx(4 / 8)
    cohorts = [set(api._sample_clients(r).tolist()) for r in range(50)]
    sizes = [len(c) for c in cohorts]
    assert min(sizes) < 4 < max(sizes), "cohort sizes should vary (Poisson)"


def test_poisson_sampling_is_run_dependent_not_public():
    """The ADVICE-high fix: cohort draws must depend on the run seed, not
    the round index alone (a round-only seed is publicly predictable,
    voiding amplification), and must not touch numpy's global PRNG."""
    from fedml_tpu.privacy.dp_fedavg import poisson_client_sampling

    a = [poisson_client_sampling(0, r, 32, 0.3).tolist() for r in range(20)]
    b = [poisson_client_sampling(1, r, 32, 0.3).tolist() for r in range(20)]
    assert a != b, "different run seeds must draw different cohorts"
    # deterministic per (seed, round) — reproducibility/resume contract
    assert a == [
        poisson_client_sampling(0, r, 32, 0.3).tolist() for r in range(20)
    ]
    # global numpy stream untouched (np.random.seed would be the old sin)
    np.random.seed(123)
    before = np.random.get_state()[1].copy()
    poisson_client_sampling(7, 3, 32, 0.3)
    np.random.seed(123)
    assert np.array_equal(before, np.random.get_state()[1])

    with pytest.raises(ValueError):
        poisson_client_sampling(0, 0, 8, 0.0)
    with pytest.raises(ValueError):
        poisson_client_sampling(0, 0, 8, 1.5)


def test_dp_padding_invariance():
    """Padding the cohort axis further must not change the mechanism: the
    fixed-denominator aggregate excludes dummy rows exactly."""
    import fedml_tpu.privacy.dp_fedavg as dpmod

    data, model = _data_model()
    dp = DpConfig(
        clip_norm=0.5, noise_multiplier=0.9, sample_secret=_TEST_SECRET
    )
    a = DPFedAvgAPI(_cfg(rounds=2), data, model, dp=dp)
    b = DPFedAvgAPI(_cfg(rounds=2), data, model, dp=dp)
    orig = dpmod.bucket_cohort
    try:
        dpmod.bucket_cohort = lambda m: orig(m) * 2  # double the padding
        for r in range(2):
            b.train_round(r)
    finally:
        dpmod.bucket_cohort = orig
    for r in range(2):
        a.train_round(r)
    for x, y in zip(
        jax.tree_util.tree_leaves(a.global_vars),
        jax.tree_util.tree_leaves(b.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7
        )


def test_dp_empty_cohort_round_is_noise_only():
    """An empty Poisson draw is a legal round: w moves by noise only, and
    with z ~ 0 the model is unchanged."""
    data, model = _data_model()
    api = DPFedAvgAPI(
        _cfg(rounds=1), data, model,
        dp=DpConfig(clip_norm=1.0, noise_multiplier=1e-15),
    )
    api._sample_clients = lambda r: np.array([], dtype=np.int64)
    before = jax.tree_util.tree_map(np.asarray, api.global_vars)
    api.train_round(0)
    assert api.accountant.rounds == 1
    for x, y in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(api.global_vars),
    ):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_cli_dp_fedavg_reachable():
    import json

    from click.testing import CliRunner

    from fedml_tpu.cli import main

    result = CliRunner().invoke(
        main,
        [
            "--algorithm", "dp_fedavg", "--dataset", "synthetic",
            "--model", "lr", "--client_num_in_total", "8",
            "--client_num_per_round", "4", "--comm_round", "3",
            "--batch_size", "8", "--lr", "0.1",
            "--dp_clip", "1.0", "--dp_noise_multiplier", "0.8",
        ],
    )
    assert result.exit_code == 0, result.output
    row = json.loads(result.output.strip().splitlines()[-1])
    assert row["DP/epsilon"] > 0 and row["DP/delta"] == 1e-5


def test_dp_secret_validation_and_legacy_checkpoint_warning():
    data, model = _data_model()
    with pytest.raises(ValueError, match="non-negative"):
        DPFedAvgAPI(_cfg(), data, model, dp=DpConfig(sample_secret=-1))
    with pytest.raises(ValueError, match="256 bits"):
        DPFedAvgAPI(_cfg(), data, model, dp=DpConfig(sample_secret=1 << 300))
    # a legacy checkpoint (no dp_sample_secret) resumes with a loud
    # warning that the participation stream forks here
    api = DPFedAvgAPI(_cfg(), data, model, dp=DpConfig(sample_secret=_TEST_SECRET))
    api.train_round(0)
    state = api.checkpoint_state()
    state.pop("dp_sample_secret")
    b = DPFedAvgAPI(_cfg(), data, model)
    with pytest.warns(UserWarning, match="forks"):
        b.restore_state(state)
    assert b.accountant.rounds == 1


def test_secret_word_encoding_roundtrips_and_is_jax_safe():
    """The secret<->words encoding must survive a pass through jnp (the
    multi-host broadcast path): uint32 words are immune to the silent
    64->32-bit truncation jax applies with x64 disabled."""
    from fedml_tpu.privacy.dp_fedavg import (
        _secret_to_words,
        _words_to_secret,
    )

    for sec in (0, 1, _TEST_SECRET, (1 << 128) - 1):
        words = _secret_to_words(sec)
        assert words.dtype == np.uint32
        assert _words_to_secret(words) == sec
        # through jnp and back (broadcast_one_to_all's transport)
        assert _words_to_secret(np.asarray(jnp.asarray(words))) == sec
    # decode follows the array's actual word width (defensive tolerance
    # for checkpoints touched by other tooling)
    wide = np.asarray([0xDEADBEEF_CAFEBABE, 0x1234], np.uint64)
    assert _words_to_secret(wide) == (0x1234 << 64) | 0xDEADBEEF_CAFEBABE
    with pytest.raises(ValueError, match="exceeds"):
        _secret_to_words(1 << 300)
