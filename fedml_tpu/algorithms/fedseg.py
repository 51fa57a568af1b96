"""FedSeg — federated semantic segmentation (ref: fedml_api/distributed/
fedseg/{FedSegAggregator.py:10-41 per-client mIoU tracking,
MyModelTrainer.py:95-128 eval, utils.py:161-197 Saver, :239+ Evaluator}).

FedAvg over an encoder-decoder with the per-pixel ignore-index CE task
("segmentation" in train/client.py) plus confusion-matrix mIoU/FWIoU
evaluation and best-mIoU checkpoint promotion (the Saver's contract)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.utils.checkpoint import save_checkpoint
from fedml_tpu.utils.seg_metrics import Evaluator


class FedSegAPI(FedAvgAPI):
    def __init__(self, config, data, model, checkpoint_path: Optional[str] = None, **kw):
        kw.setdefault("task", "segmentation")
        super().__init__(config, data, model, **kw)
        self.checkpoint_path = checkpoint_path
        self.best_miou = -1.0
        self._predict = jax.jit(  # fedlint: disable=uncached-jit -- per-API-instance argmax-predict closure over self.model; eval-only long-tail path
            lambda v, x: jnp.argmax(self.model.apply(v, x, train=False)[0], -1)
        )

    def evaluate_seg(self, batch_size: int = 16) -> dict:
        """mIoU/FWIoU/pixel-acc on the global test set (ref Evaluator usage,
        MyModelTrainer.py:95-128)."""
        ev = Evaluator(self.data.num_classes)
        x, y = self.data.test_x, self.data.test_y
        for s in range(0, len(y), batch_size):
            pred = self._predict(self.global_vars, jnp.asarray(x[s : s + batch_size]))
            ev.add_batch(np.asarray(y[s : s + batch_size]), np.asarray(pred))
        return {
            "Test/mIoU": ev.Mean_Intersection_over_Union(),
            "Test/FWIoU": ev.Frequency_Weighted_Intersection_over_Union(),
            "Test/Acc": ev.Pixel_Accuracy(),
            "Test/Acc_class": ev.Pixel_Accuracy_Class(),
        }

    def train(self):
        cfg = self.config
        final = {}
        for round_idx in range(cfg.fed.comm_round):
            _, metrics = self.train_round(round_idx)
            count = float(metrics["count"])
            row = {
                "round": round_idx,
                "Train/Loss": float(metrics["loss_sum"]) / max(count, 1e-9),
                "Train/Acc": float(metrics["correct"]) / max(count, 1e-9),
            }
            if (
                round_idx % cfg.fed.frequency_of_the_test == 0
                or round_idx == cfg.fed.comm_round - 1
            ):
                row.update(self.evaluate_seg())
                # best-mIoU promotion (ref Saver.save_checkpoint,
                # fedseg/utils.py:161-197)
                if self.checkpoint_path and row["Test/mIoU"] > self.best_miou:
                    self.best_miou = row["Test/mIoU"]
                    save_checkpoint(
                        self.checkpoint_path,
                        self.global_vars,
                        round_idx=round_idx,
                        extra_meta={"best_miou": self.best_miou},
                    )
            self.history.append(row)
            self.log_fn(row)
            final = row
        return final
