"""Backdoor attack vs defense: the eval the reference runs with
FedAvgRobustAggregator.py:14-60 + edge_case_examples — round 1's gap was
that the defense was never shown defeating an attack."""

import numpy as np
import pytest

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.edge_cases import (
    PoisonSpec,
    apply_trigger,
    attack_success_rate,
    backdoor_test_set,
    poison_clients,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.robustness import RobustConfig
from fedml_tpu.robustness.backdoor import AttackConfig, BackdoorFedAvgAPI

SPEC = PoisonSpec(target_label=0, poison_frac=0.5, trigger_size=3, trigger_value=2.5)


def _clean_data():
    return synthetic_classification(
        num_clients=8,
        num_classes=4,
        feat_shape=(10, 10, 1),
        samples_per_client=48,
        partition_method="homo",
        ragged=False,
        seed=7,
    )


def test_poison_clients_only_touches_attackers():
    data = _clean_data()
    poisoned = poison_clients(data, attacker_ids=[1, 5], spec=SPEC, seed=0)
    for c in range(data.num_clients):
        same = np.array_equal(poisoned.client_x[c], data.client_x[c])
        assert same == (c not in (1, 5))
    # poisoned samples carry the target label and the trigger patch
    changed = poisoned.client_x[1][..., :3, :3, :] != data.client_x[1][..., :3, :3, :]
    assert changed.any()
    n_target = int(np.sum(poisoned.client_y[1] == SPEC.target_label))
    assert n_target >= int(0.5 * len(poisoned.client_y[1]))


def test_backdoor_test_set_excludes_target_class():
    data = _clean_data()
    x, y = backdoor_test_set(data, SPEC)
    assert (y == SPEC.target_label).all()
    assert len(x) == int(np.sum(np.asarray(data.test_y) != SPEC.target_label))
    assert float(x[:, :3, :3].min()) == SPEC.trigger_value


def _run(defense: RobustConfig, rounds: int = 4):
    # Few rounds: norm clipping defends against model REPLACEMENT (the
    # boosted upload); a persistent poisoned-data attack trickles the
    # backdoor in "honestly" over many rounds regardless of clipping — at
    # 12 rounds both arms reach ASR 1.0 and the comparison is meaningless.
    data = poison_clients(_clean_data(), attacker_ids=[1, 5], spec=SPEC, seed=0)
    model = ModelDef(LogisticRegression(num_classes=4), (10, 10, 1), 4, name="lr")
    cfg = RunConfig(
        data=DataConfig(batch_size=16),
        fed=FedConfig(
            client_num_in_total=8,
            client_num_per_round=8,
            comm_round=rounds,
            epochs=1,
            frequency_of_the_test=10_000,
        ),
        train=TrainConfig(lr=0.1),
    )
    api = BackdoorFedAvgAPI(
        cfg,
        data,
        model,
        robust=defense,
        attack=AttackConfig(attacker_ids=(1, 5), boost=8.0),
    )
    for r in range(rounds):
        api.train_round(r)
    _, main_acc = api.evaluate_global()
    asr = attack_success_rate(model, api.global_vars, data, SPEC, eval_fn=api.eval_fn)
    return main_acc, asr


def test_defense_reduces_attack_success_rate():
    """The defense contract: ASR(defense) < ASR(no defense) at comparable
    main-task accuracy — the defense measurably defeats a boosted backdoor."""
    main_nodef, asr_nodef = _run(RobustConfig(defense_type="no_defense"))
    main_def, asr_def = _run(
        RobustConfig(defense_type="norm_diff_clipping", norm_bound=0.3)
    )
    # the boosted attack installs the backdoor without a defense
    assert asr_nodef > 0.5, f"attack too weak to test the defense (ASR={asr_nodef})"
    # clipping defeats it while keeping the main task working
    assert asr_def < 0.5 * asr_nodef, (asr_def, asr_nodef)
    assert main_def > 0.7, f"defense destroyed main-task accuracy ({main_def})"
    assert main_def >= main_nodef - 0.15
