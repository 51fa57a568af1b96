"""Train the committed ResNet-56 pretrained artifact: the reference ships
real trained resnet56 checkpoints
(fedml_api/model/cv/pretrained/CIFAR10/resnet56/, loaded via
resnet56(pretrained=True, path=...)); this repo shipped only the
import/export mechanism. This script trains ResNet-56 on the synthetic
cross-silo CIFAR-10 regime (the same generator the bench's
bf16_cross_silo row uses — real downloads are unavailable in this
environment) to a pinned accuracy target and saves the npz the test
suite loads with create_model(..., pretrained=...).

Run on the TPU:  python examples/train_pretrained_resnet56.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.models.pretrained import save_pretrained

OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "fedml_tpu", "models", "pretrained_weights", "resnet56_cifar10_synth.npz",
)
TARGET = 0.80  # pooled-train accuracy target the artifact must carry

data = synthetic_classification(
    num_clients=10, num_classes=10, feat_shape=(32, 32, 3),
    samples_per_client=512, partition_method="homo", ragged=False, seed=0,
)
model = create_model("resnet56", "cifar10", (32, 32, 3), 10)
cfg = RunConfig(
    data=DataConfig(batch_size=64),
    fed=FedConfig(client_num_in_total=10, client_num_per_round=10,
                  comm_round=200, epochs=1, frequency_of_the_test=10_000),
    train=TrainConfig(client_optimizer="sgd", lr=0.1, momentum=0.9),
    model="resnet56",
    seed=0,
)
api = FedAvgAPI(cfg, data, model)
t0 = time.perf_counter()
best = 0.0
for r in range(cfg.fed.comm_round):
    api.train_round(r)
    if (r + 1) % 10 == 0:
        pool = api.local_test_on_all_clients(r)
        acc = float(pool["Train/Acc"])
        _, test_acc = api.evaluate_global()
        best = max(best, acc)
        print(f"round {r+1}: pooled_train_acc={acc:.4f} test_acc={float(test_acc):.4f} "
              f"elapsed={time.perf_counter()-t0:.0f}s", flush=True)
        if acc >= TARGET:
            break
assert acc >= TARGET, f"did not reach {TARGET}: {acc}"
os.makedirs(os.path.dirname(OUT), exist_ok=True)
save_pretrained(OUT, api.global_vars)
meta = {
    "regime": "synthetic cross-silo CIFAR-10 geometry (synthetic_classification "
              "num_clients=10 homo samples_per_client=512 seed=0)",
    "algo": "fedavg sgd lr=0.1 momentum=0.9 batch=64 E=1 fp32",
    "rounds_trained": r + 1,
    "pooled_train_acc": round(acc, 4),
    "test_acc": round(float(test_acc), 4),
    "ref": "fedml_api/model/cv/resnet.py:200-222 + pretrained/CIFAR10/resnet56/",
}
with open(OUT.replace(".npz", ".json"), "w") as f:
    json.dump(meta, f, indent=1)
print(json.dumps(meta), flush=True)
print("saved:", OUT, os.path.getsize(OUT), "bytes", flush=True)
