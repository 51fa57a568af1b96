"""Disk-backed per-client ALGORITHM state — the spill tier for stateful
federated algorithms (SCAFFOLD control variates, Ditto personal models).

The round-3 stateful algorithms pinned their N × |params| state as one
stacked pytree in HBM and hard-refused past 8 GiB while the data layer
already scaled to 100k clients on disk. This module
closes that asymmetry with the data layer's own tiering
(data/mmap_store.py):

    disk (np.memmap, all N clients' state rows)
        -> host RAM (sampled cohort's rows only)
        -> HBM (cohort rows enter the jitted cohort-form round)

Layout on disk (one directory): ``leaf_{i}.npy`` — one np.lib.format
array per pytree leaf, shape [N, *leaf_shape] — plus ``init_mask.npy``
and ``meta.json``. Rows are LAZILY initialized: ``open_memmap`` creates
sparse zero files instantly (no 100k-row write at construction), and a
per-client bitmap records which rows have ever been scattered; a gather
of an untouched row returns the algorithm's initial state (zeros for
SCAFFOLD's c_i, the broadcast w_0 for Ditto's v_k) without any disk
write having happened. Per round, only the cohort's rows are read and
written — O(|S| · params) IO, independent of N.

Math contract: gather/scatter are exact row copies (float32 in, float32
out), so a spilled run is BIT-IDENTICAL to the in-HBM run at the same
seed — pinned by tests/test_state_spill.py against ScaffoldAPI/DittoAPI
with the device store.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import weakref
from typing import Optional, Sequence

import jax
import numpy as np


class MmapClientState:
    """[N, ...] per-client state pytree spilled to one memmap per leaf.

    ``init_tree`` is ONE client's initial state (no leading N axis); its
    tree structure, shapes, and dtypes define the store's schema.
    """

    def __init__(self, init_tree, n_clients: int, path: Optional[str] = None):
        self.n = int(n_clients)
        leaves, self._treedef = jax.tree_util.tree_flatten(init_tree)
        self._init_leaves = [np.asarray(l) for l in leaves]
        path = path or None  # "" (FedConfig.state_dir default) == unset
        self.path = path or tempfile.mkdtemp(prefix="fedml_tpu_state_")
        if path is None:
            # a self-created temp spill dir is scratch, not a deliverable:
            # without cleanup every 100k-client run leaks N x |params|
            # bytes of /tmp (advisor r4). User-supplied paths are THEIRS
            # (resume target) and are never removed.
            self._cleanup = weakref.finalize(
                self, shutil.rmtree, self.path, ignore_errors=True
            )
        else:
            self._cleanup = None
        os.makedirs(self.path, exist_ok=True)
        meta_path = os.path.join(self.path, "meta.json")
        schema = [
            {"shape": list(l.shape), "dtype": str(l.dtype)}
            for l in self._init_leaves
        ]
        if os.path.exists(meta_path):
            # resume: reopen an existing store — schema must match exactly
            # (a silent mismatch would scatter rows into the wrong layout)
            with open(meta_path) as f:
                meta = json.load(f)
            if meta["n"] != self.n or meta["leaves"] != schema:
                raise ValueError(
                    f"existing state store at {self.path} has schema "
                    f"{meta}, expected n={self.n}, leaves={schema}"
                )
            self._mms = [
                np.load(
                    os.path.join(self.path, f"leaf_{i}.npy"), mmap_mode="r+"
                )
                for i in range(len(self._init_leaves))
            ]
            self._init_mask = np.load(
                os.path.join(self.path, "init_mask.npy"), mmap_mode="r+"
            )
            self._advise_random()
        else:
            # open_memmap w+ creates SPARSE zero-filled files — O(1) in
            # data written, whatever N is
            self._mms = [
                np.lib.format.open_memmap(
                    os.path.join(self.path, f"leaf_{i}.npy"),
                    mode="w+",
                    dtype=l.dtype,
                    shape=(self.n,) + l.shape,
                )
                for i, l in enumerate(self._init_leaves)
            ]
            self._init_mask = np.lib.format.open_memmap(
                os.path.join(self.path, "init_mask.npy"),
                mode="w+",
                dtype=np.bool_,
                shape=(self.n,),
            )
            with open(meta_path, "w") as f:
                json.dump({"n": self.n, "leaves": schema}, f)
            self._advise_random()

    def _advise_random(self) -> None:
        # cohort rows are random by construction: kernel readahead on
        # the sparse [N, ...] files amplifies every row fault into a
        # full readahead window (measured 280x on the sharded tier at
        # 1M clients — see data.mmap_store.advise_random)
        from fedml_tpu.data.mmap_store import advise_random

        for mm in self._mms:
            advise_random(mm)
        advise_random(self._init_mask)

    @property
    def state_bytes_total(self) -> int:
        """Logical size of the full store (the number the HBM path would
        have to pin) — for logging; actual disk use is cohort-sparse."""
        return self.n * sum(l.nbytes for l in self._init_leaves)

    def gather(self, idx: Sequence[int]):
        """Cohort rows as a HOST pytree [C, ...] (copies — safe to ship to
        device). Untouched rows come back as the initial state."""
        idx = np.asarray(idx, np.int64)
        inited = np.asarray(self._init_mask[idx])
        out = []
        for mm, base in zip(self._mms, self._init_leaves):
            rows = np.array(mm[idx])  # fancy-index copy off the mmap
            if not inited.all():
                rows[~inited] = base
            out.append(rows)
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def scatter(self, idx: Sequence[int], rows_tree) -> None:
        """Write the cohort's updated rows back (host arrays in)."""
        idx = np.asarray(idx, np.int64)
        leaves = jax.tree_util.tree_leaves(rows_tree)
        for mm, r in zip(self._mms, leaves):
            mm[idx] = np.asarray(r, dtype=mm.dtype)
        self._init_mask[idx] = True

    def flush(self) -> None:
        for mm in self._mms:
            mm.flush()
        self._init_mask.flush()

    def initialized_ids(self) -> np.ndarray:
        """Client ids whose rows have ever been scattered — together with
        :meth:`gather` of those ids this is the store's ENTIRE information
        content (every other row is the initial state), which is what
        checkpoint_state embeds so checkpoints are self-contained (a
        checkpoint that merely recorded the live directory's path would
        roll forward as training continues, and would dangle after a
        tmp-cleaner pass)."""
        return np.flatnonzero(np.asarray(self._init_mask))

    def reset_to(self, idx: Sequence[int], rows_tree) -> None:
        """Roll the store back to exactly {initial state everywhere except
        ``idx``, which holds ``rows_tree``} — the restore side of the
        self-contained checkpoint."""
        inited = self.initialized_ids()
        if len(inited):
            # rows touched after the checkpoint revert to the initial state
            for mm, base in zip(self._mms, self._init_leaves):
                mm[inited] = base
            self._init_mask[inited] = False
        if len(np.asarray(idx)):
            self.scatter(idx, rows_tree)

    def initialized_count(self) -> int:
        return int(np.count_nonzero(self._init_mask))


class CohortPrefetcher:
    """Overlap the next round's cohort gather (disk read) with the current
    round's device compute.

    Correctness contract: rows the caller is about to scatter THIS round
    must be passed in ``exclude`` — the background thread never reads
    them, and :meth:`take` re-fetches them synchronously after the scatter
    has landed, so a prefetched cohort can never contain torn or stale
    rows. A take() whose (round, ids) doesn't match the pending prefetch
    falls back to a plain synchronous gather."""

    def __init__(self, store: MmapClientState):
        self.store = store
        self._pending = None  # (round_idx, ids_bytes, safe_mask, result)
        self._thread = None

    def launch(self, round_idx: int, ids, exclude=()) -> None:
        import threading

        self.cancel()
        ids = np.asarray(ids, np.int64)
        excl = set(int(i) for i in exclude)
        safe_mask = np.fromiter(
            (int(i) not in excl for i in ids), bool, count=len(ids)
        )
        safe_ids = ids[safe_mask]
        result = {}

        def work():
            try:
                result["rows"] = self.store.gather(safe_ids)
            except Exception as e:  # noqa: BLE001 — surface at take()
                result["err"] = e

        t = threading.Thread(target=work, daemon=True)
        t.start()
        self._pending = (int(round_idx), ids.tobytes(), safe_mask, result)
        self._thread = t

    def take(self, round_idx: int, ids):
        ids = np.asarray(ids, np.int64)
        if (
            self._pending is None
            or self._pending[0] != int(round_idx)
            or self._pending[1] != ids.tobytes()
        ):
            self.cancel()
            return self.store.gather(ids)
        _, _, safe_mask, result = self._pending
        self._thread.join()
        self._pending, self._thread = None, None
        if "rows" not in result:
            # the background gather died (disk error, dir removed): retry
            # synchronously — a persistent failure re-raises HERE with the
            # true error, attributed to the caller's round
            return self.store.gather(ids)
        pre = result["rows"]
        if safe_mask.all():
            return pre
        missing = self.store.gather(ids[~safe_mask])

        def merge(p, m):
            out = np.empty((len(ids),) + p.shape[1:], p.dtype)
            out[safe_mask] = p
            out[~safe_mask] = m
            return out

        return jax.tree_util.tree_map(merge, pre, missing)

    def cancel(self) -> None:
        if self._thread is not None:
            self._thread.join()
        self._pending, self._thread = None, None


def resolve_state_store(
    config_fed, state_bytes: int, n_clients: int = 0, population=None
) -> str:
    """"device" | "mmap" | "sharded" from FedConfig.state_store, the
    state size, and the population. ``auto`` keeps the stack in HBM
    while it fits the budget; past it, spill goes to the per-leaf mmap
    tier — or, at/above the population threshold
    (PopulationConfig.ocohort_threshold), to the record-major sharded
    tier (population/state_tier.py: one contiguous record per client
    instead of one scattered row per pytree leaf)."""
    mode = config_fed.state_store
    if mode == "auto":
        if state_bytes <= config_fed.state_budget_bytes:
            return "device"
        threshold = (
            population.ocohort_threshold if population is not None else 65536
        )
        return "sharded" if n_clients and n_clients >= threshold else "mmap"
    if mode not in ("device", "mmap", "sharded"):
        raise ValueError(
            f"FedConfig.state_store must be 'auto', 'device', 'mmap' or "
            f"'sharded'; got {mode!r}"
        )
    return mode


def make_spill_store(
    mode: str, init_tree, n_clients: int, path=None, population=None
):
    """Construct the spill tier named by a resolved non-device mode —
    the ONE mapping from mode string to store class, shared by SCAFFOLD
    and Ditto (and any future stateful algorithm), so the two can never
    wire the tiers differently."""
    if mode == "sharded":
        from fedml_tpu.population.state_tier import ShardedClientState

        return ShardedClientState(
            init_tree,
            n_clients,
            path,
            shard_bits=(
                population.state_shard_bits if population is not None else 16
            ),
        )
    if mode == "mmap":
        return MmapClientState(init_tree, n_clients, path)
    raise ValueError(f"not a spill-store mode: {mode!r}")
