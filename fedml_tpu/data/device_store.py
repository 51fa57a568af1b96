"""HBM-resident federated data store.

The reference re-materialises every sampled client's tensors on the training
device each round (fedavg_api.py:59-63 re-points Client objects;
my_model_trainer_classification.py:22 `.to(device)` per local train). On TPU
— especially through a remote-device transport, where host→device bandwidth
can be O(10 MB/s) — shipping the stacked batch every round dominates the
round (measured: 1.3 s transfer vs 74 ms compute for the north-star CNN
round). The TPU-native design: upload the *flat concatenation* of all client
shards to HBM once, and per round send only a [C, S·B] int32 index matrix
(tens of KB); the sampled clients' samples are gathered on-device.

This also pins compiled shapes: the index matrix is bucketed exactly like
:func:`fedml_tpu.data.base.stack_clients`, so rounds reuse the same small
set of jitted shapes, and the per-round host work is building a few KB of
indices instead of copying the batch.

What is resident: each sample as ONE 2-D row, zero-padded on the host to
whole 128-lane tiles (``flat_x: [N, lanes]``; FEMNIST's 784 floats sit in
896 lanes, 2.95 GB for 824 019 samples), with the feature shape restored on
the cohort-sized result. Why: a TPU holds ``[N, 896]`` sample-major, the
layout a row gather reads, but puts ``N`` minor-most in ``[N, 28, 28]`` or
``[N, 784]``, and a gather program handed either first copies the whole
population into padded rows, in every round.
"""

from __future__ import annotations

import math
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.data.base import ClientBatch, FederatedDataset, bucket_steps

# HBM budget guard: datasets larger than this stay on host (override with
# env FEDML_TPU_DEVICE_CACHE_MAX_BYTES; v5e has 16 GB per chip).
_DEFAULT_MAX_BYTES = 4_000_000_000


_LANES = 128  # a TPU tile's minor dimension: rows are held in whole tiles


def _row_lanes(shape) -> int:
    """Elements one sample's row takes on the device: its trailing
    dimensions flattened and padded to whole 128-lane tiles (a scalar
    sample, in a 1-D population, has no row to pad)."""
    return -(-math.prod(shape) // _LANES) * _LANES if shape else 1


def fits_on_device(data: FederatedDataset) -> bool:
    """Whether the population, AS THE DEVICE WILL HOLD IT (lane-padded
    rows, not the host's ``nbytes``), is under the cap."""
    cap = int(
        os.environ.get("FEDML_TPU_DEVICE_CACHE_MAX_BYTES", _DEFAULT_MAX_BYTES)
    )
    # one client's row shape and the sample count: summing nbytes over
    # 100k lazy mmap views would walk the whole store
    per_row = sum(
        _row_lanes(a.shape[1:]) * a.dtype.itemsize
        for a in (data.client_x[0], data.client_y[0])
    )
    return data.total_train_samples() * per_row <= cap


def _host_rows(shards):
    """The clients' shards concatenated as lane-padded 2-D rows
    ``[N, lanes]``, in one pass; scalar samples stay a 1-D ``[N]``.
    Ragged per-client shapes raise ValueError, as concatenating does."""
    feat = shards[0].shape[1:]
    if not feat:
        return np.concatenate(shards, axis=0)
    rows = np.zeros((sum(len(s) for s in shards), _row_lanes(feat)), shards[0].dtype)
    np.concatenate(
        [s.reshape(len(s), -1) for s in shards], axis=0,
        out=rows[:, : math.prod(feat)],
    )
    return rows


def _restore(rows, lead, shape):
    """Gathered rows ``[C, cap(, lanes)]`` as ``[*lead, *shape]``: the
    lanes' padding goes and the sample's shape returns."""
    if shape:
        rows = rows[..., : math.prod(shape)]
    return rows.reshape(lead + tuple(shape))


def gather_batch(flat_x, flat_y, idx, mask, steps, bs, feat_shape, label_shape):
    """One cohort's ``[C, steps, bs, ...]`` batch from the store's rows: take
    them, zero the padded slots (padded indices point at row 0; zeroing
    keeps the result bit-identical to host stack_clients, which zero-pads),
    and only then restore the feature shape, on the cohort-sized result.
    The body of :meth:`DeviceDataStore.gather_program`."""
    with jax.named_scope("gather"):
        x = jnp.take(flat_x, idx, axis=0)
        y = jnp.take(flat_y, idx, axis=0)
    with jax.named_scope("mask_pad"):
        mx = mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))
        my = mask.reshape(mask.shape + (1,) * (y.ndim - mask.ndim))
        x, y = x * mx.astype(x.dtype), y * my.astype(y.dtype)
    lead = (idx.shape[0], steps, bs)
    return (
        _restore(x, lead, feat_shape),
        _restore(y, lead, label_shape),
        mask.reshape(lead),
    )


class DeviceDataStore:
    """Upload-once, gather-per-round client data store. ``flat_x`` and
    ``flat_y`` are lane-padded 2-D rows (1-D where a sample is a scalar);
    readers take the per-sample shapes from ``feat_shape`` /
    ``label_shape``."""

    def __init__(self, data: FederatedDataset):
        counts = data.train_sample_counts
        self.offsets = np.concatenate([[0], np.cumsum(counts)])
        self.counts = counts
        self.feat_shape = data.client_x[0].shape[1:]
        self.label_shape = data.client_y[0].shape[1:]
        self.flat_x = jnp.asarray(_host_rows(data.client_x))
        self.flat_y = jnp.asarray(_host_rows(data.client_y))
        # what the device holds: one sample's row, and everything
        self.row_bytes = _row_lanes(self.feat_shape) * self.flat_x.dtype.itemsize
        self.resident_bytes = (
            self.flat_x.on_device_size_in_bytes()
            + self.flat_y.on_device_size_in_bytes()
        )

    def gather_program(self, steps: int, bs: int):
        """The round-batch program for one (steps, bs) shape class:
        :func:`gather_batch` as ONE ProgramCache-routed jit, so that (a)
        the AOT warmup pre-enumeration can compile it per class up front
        and (b) it persists through the executable cache like every other
        round program (zero-cold-start)."""
        from fedml_tpu.compile import get_program_cache

        feat, lab = self.feat_shape, self.label_shape

        def builder():
            # the function's name is the XLA module's: jit_device_store_gather
            def device_store_gather(flat_x, flat_y, idx, mask):
                return gather_batch(flat_x, flat_y, idx, mask, steps, bs, feat, lab)

            return jax.jit(device_store_gather)

        return get_program_cache().get_or_build(
            "device_store_gather",
            {
                "kind": "device_store_gather", "steps": steps, "bs": bs,
                "feat": feat, "lab": lab,
            },
            builder,
        )

    def round_indices(
        self,
        client_indices: Sequence[int],
        batch_size: int,
        seed: int = 0,
        pad_bucket: int = 1,
        shuffle: bool = True,
    ):
        """Host-side index/mask matrices for one round's gather:
        (idx [C, cap] int32, mask [C, cap] float32, steps, bs, ns).
        ``ns`` is the per-client true sample count — the single source for
        aggregation weights."""
        ns = [int(self.counts[i]) for i in client_indices]
        steps, bs, cap = bucket_steps(ns, batch_size, pad_bucket)

        rng = np.random.default_rng(seed)
        C = len(client_indices)
        idx = np.zeros((C, cap), dtype=np.int32)
        mask = np.zeros((C, cap), dtype=np.float32)
        for j, ci in enumerate(client_indices):
            n = ns[j]
            order = rng.permutation(n) if shuffle else np.arange(n)
            idx[j, :n] = self.offsets[ci] + order
            mask[j, :n] = 1.0
        return idx, mask, steps, bs, ns

    def round_batch(
        self,
        client_indices: Sequence[int],
        batch_size: int,
        seed: int = 0,
        pad_bucket: int = 1,
        shuffle: bool = True,
    ) -> ClientBatch:
        """Device-array ClientBatch for the sampled clients. Same bucketed
        shape contract as :func:`stack_clients`; padded slots index row 0
        and are mask-0."""
        idx, mask, steps, bs, ns = self.round_indices(
            client_indices, batch_size, seed=seed, pad_bucket=pad_bucket,
            shuffle=shuffle,
        )
        x, y, mask_dev = self.gather_program(steps, bs)(
            self.flat_x, self.flat_y, jnp.asarray(idx), jnp.asarray(mask)
        )
        return ClientBatch(
            x=x,
            y=y,
            mask=mask_dev,
            num_samples=np.array(ns, dtype=np.float32),
        )
