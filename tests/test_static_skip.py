"""Static cond-skip selection (resolve_skip_empty_steps).

The per-step lax.cond that skips all-padding local steps costs real time
even when every step has data (measured +50% per step on the cross-silo
ResNet-56 round), so whether to emit it is decided per cohort from
host-side sample counts. These tests pin:
- the host-side predicate (_cohort_may_pad) against the bucket contract;
- that the dispatcher compiles the cond-less variant for pad-free
  cohorts and the cond variant for padded ones;
- that both variants produce identical round math on the SAME padded
  batch (the where-gated no-skip path and the cond-skip path must agree
  bitwise-closely, or the variant choice would change results).
"""

import jax
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, client_sampling
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model


def _api(samples_per_client, partition="homo", batch_size=4, momentum=0.9):
    num_clients = 4
    data = synthetic_classification(
        num_clients=num_clients,
        num_classes=3,
        feat_shape=(6,),
        samples_per_client=samples_per_client,
        partition_method=partition,
        ragged=(partition != "homo"),
        seed=0,
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=batch_size, pad_bucket=1),
        fed=FedConfig(
            client_num_in_total=num_clients,
            client_num_per_round=num_clients,
            comm_round=2,
            epochs=2,
            client_parallelism="scan",
            frequency_of_the_test=10_000,
        ),
        # momentum makes a skipped-vs-computed padding step observable if
        # the gating were wrong (momentum state must not move on padding)
        train=TrainConfig(client_optimizer="sgd", lr=0.1, momentum=momentum),
        model="lr",
    )
    model = create_model("lr", "synthetic", (6,), 3)
    return FedAvgAPI(cfg, data, model)


def test_cohort_may_pad_predicate():
    api = _api(samples_per_client=8, batch_size=4)  # 8 = 2 full steps, pow2
    sampled = client_sampling(0, 4, 4)
    assert api._cohort_may_pad(sampled) is False

    ragged = _api(samples_per_client=8, partition="hetero", batch_size=4)
    sampled = client_sampling(0, 4, 4)
    counts = ragged._client_counts(sampled)
    from fedml_tpu.data.base import bucket_steps

    steps, bs, _ = bucket_steps(counts, 4, 1)
    expect = any(-(-n // bs) < steps for n in counts)
    assert ragged._cohort_may_pad(sampled) is expect


def test_dispatcher_compiles_matching_variant():
    api = _api(samples_per_client=8, batch_size=4)
    assert api.round_fn.supports_may_pad
    api.train_round(0)
    assert set(api.round_fn._variants) == {False}

    # a ragged cohort with an all-padding step picks the cond variant
    ragged = _api(samples_per_client=9, batch_size=4)  # 3 steps -> pow2 4
    sampled = client_sampling(0, 4, 4)
    assert ragged._cohort_may_pad(sampled) is True
    ragged.train_round(0)
    assert set(ragged.round_fn._variants) == {True}


def test_variants_identical_math_on_padded_batch():
    """Run the SAME padded round through both variants: cond-skip and
    where-gated must agree (incl. momentum state effects across 2 epochs)."""
    api = _api(samples_per_client=9, batch_size=4)
    sampled = client_sampling(0, 4, 4)
    batch = api._round_batch(sampled, 0)
    rng = jax.random.fold_in(api.rng, 1)
    placed = api._place_batch(batch, rng)

    gv0 = jax.tree_util.tree_map(lambda a: a.copy(), api.global_vars)
    out_skip, met_skip = api.round_fn(gv0, *placed, may_pad=True)
    gv1 = jax.tree_util.tree_map(lambda a: a.copy(), api.global_vars)
    out_gate, met_gate = api.round_fn(gv1, *placed, may_pad=False)

    assert set(api.round_fn._variants) == {True, False}
    for a, b in zip(
        jax.tree_util.tree_leaves(out_skip), jax.tree_util.tree_leaves(out_gate)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
    for k in met_skip:
        np.testing.assert_allclose(
            float(met_skip[k]), float(met_gate[k]), rtol=1e-6
        )
