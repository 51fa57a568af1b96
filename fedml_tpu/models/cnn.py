"""FedAvg CNNs (ref: fedml_api/model/cv/cnn.py:5 CNNOriginalFedAvg,
:74 CNNDropOut).

Layout is NHWC (TPU-native; XLA tiles conv+matmul onto the MXU best in NHWC),
vs the reference's NCHW torch layout. Architecture parity: 2× [conv 5×5 →
maxpool 2×2] → dense 512 → dense classes, matching the original FedAvg paper
CNN the reference reproduces (cnn.py:10-31 docstring + layers at :33-47)."""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.ops.pooling import max_pool


class CNNOriginalFedAvg(nn.Module):
    """conv32(5×5) → pool → conv64(5×5) → pool → fc512 → fc#classes
    (ref cnn.py:33-47; `only_digits` selects 10 vs 62 classes at :33)."""

    num_classes: int = 62

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(32, (5, 5), padding="SAME", name="conv2d_1")(x)
        x = nn.relu(x)
        x = max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(64, (5, 5), padding="SAME", name="conv2d_2")(x)
        x = nn.relu(x)
        x = max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(512, name="linear_1")(x))
        return nn.Dense(self.num_classes, name="linear_2")(x)


class CNNDropOut(nn.Module):
    """Dropout variant (ref cnn.py:74-131: conv32/conv64 3×3, dropout .25/.5,
    fc128)."""

    num_classes: int = 62
    dropout1: float = 0.25
    dropout2: float = 0.5

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Conv(32, (3, 3), padding="VALID", name="conv2d_1")(x))
        x = nn.relu(nn.Conv(64, (3, 3), padding="VALID", name="conv2d_2")(x))
        x = max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Dropout(self.dropout1, deterministic=not train)(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(128, name="linear_1")(x))
        x = nn.Dropout(self.dropout2, deterministic=not train)(x)
        return nn.Dense(self.num_classes, name="linear_2")(x)
