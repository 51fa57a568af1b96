"""Calibrate the flagship bf16 gated bench row:
ResNet-18-GN, synthetic fed-CIFAR-100 geometry, bf16 — find the
accuracy-vs-rounds curve and per-round cost so bench.py can pin a
target/horizon with a stable 'expected: reach'."""
import sys
import time

sys.path.insert(0, "/root/repo")

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model

data = synthetic_classification(
    num_clients=8, num_classes=100, feat_shape=(32, 32, 3),
    samples_per_client=1024, partition_method="hetero", partition_alpha=0.5,
    ragged=False, seed=0,
)
model = create_model("resnet18_gn", "cifar100", (32, 32, 3), 100)
cfg = RunConfig(
    data=DataConfig(batch_size=256, pad_bucket=1),
    fed=FedConfig(
        client_num_in_total=8, client_num_per_round=8, comm_round=100,
        epochs=1, frequency_of_the_test=10_000,
    ),
    train=TrainConfig(client_optimizer="sgd", lr=0.05, momentum=0.9, compute_dtype="bfloat16"),
    seed=0,
)
api = FedAvgAPI(cfg, data, model)
t0 = time.perf_counter()
for r in range(100):
    api.train_round(r)
    if (r + 1) % 5 == 0:
        loss, acc = api.evaluate_global()
        print(f"round {r+1}: loss={loss:.3f} acc={acc:.4f} elapsed={time.perf_counter()-t0:.0f}s", flush=True)
