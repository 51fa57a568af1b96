"""Canonical program digests — the ProgramCache keying contract.

A compiled XLA program is determined by (a) the round/eval/train *code
path* chosen by static configuration, (b) the model architecture, and
(c) the abstract shapes/dtypes/shardings of its inputs. Everything else
(dataset values, RNG values, round indices) is runtime data. The digest
here canonicalizes exactly those determinants into a stable sha256 so
that two independently constructed factories producing structurally
identical programs land on ONE jit object (and therefore ONE compile)
per process.

Canonicalization rules:

- dataclasses (TrainConfig, RobustConfig, ...) → qualname + field map
- dicts → sorted (key, value) pairs; lists/tuples → element lists
- anything with ``.shape``/``.dtype`` (np/jnp arrays, ShapeDtypeStruct)
  → its abstract signature only (shape, dtype, and sharding when
  present) — concrete values NEVER enter a digest
- callables → (module, qualname). This is an identity marker, not a
  semantic hash: factories must only cache programs whose closures are
  fully described by the digested fields, and must bypass the cache
  (``ProgramCache.wrap_uncached``) when handed opaque callables.

Digests of plain fields (configs, shapes, strings) are stable across
processes and runs — pinned by tests/test_compile.py. ``repr`` fallbacks
(e.g. flax module reprs in :func:`model_fingerprint`) are only
guaranteed stable within a process, which is all the in-process dedup
needs."""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict

import numpy as np


def canonical(obj: Any):
    """Reduce ``obj`` to a JSON-able canonical form (see module doc)."""
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return float(obj)
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if isinstance(obj, np.dtype):
        return {"__dtype__": str(obj)}
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            "__dataclass__": type(obj).__qualname__,
            "fields": {
                f.name: canonical(getattr(obj, f.name))
                for f in dataclasses.fields(obj)
            },
        }
    if isinstance(obj, dict):
        return {
            "__dict__": sorted(
                (str(k), canonical(v)) for k, v in obj.items()
            )
        }
    if isinstance(obj, (list, tuple)):
        return [canonical(v) for v in obj]
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        aval: Dict[str, Any] = {
            "__aval__": [list(map(int, shape)), str(dtype)]
        }
        sharding = getattr(obj, "sharding", None)
        if sharding is not None:
            aval["sharding"] = str(sharding)
        return aval
    if callable(obj):
        return {
            "__callable__": [
                getattr(obj, "__module__", "?"),
                getattr(obj, "__qualname__", repr(type(obj))),
            ]
        }
    return {"__repr__": repr(obj)}


def program_digest(fields: Dict[str, Any]) -> str:
    """sha256 hex digest of the canonical form of ``fields``."""
    doc = json.dumps(
        canonical(fields), sort_keys=True, separators=(",", ":")
    )
    return hashlib.sha256(doc.encode("utf-8")).hexdigest()


def mesh_fingerprint(mesh) -> Dict[str, Any]:
    """Canonical identity of a device mesh: axis names/sizes plus the
    flat device list (id + platform + kind). Two meshes over the same
    devices in the same topology produce identical sharded programs."""
    devices = [
        {
            "id": int(d.id),
            "platform": str(getattr(d, "platform", "?")),
            "kind": str(getattr(d, "device_kind", "?")),
        }
        for d in np.asarray(mesh.devices).ravel()
    ]
    return {
        "axes": {str(k): int(v) for k, v in mesh.shape.items()},
        "devices": devices,
    }


def model_fingerprint(model) -> Dict[str, Any]:
    """Canonical identity of a :class:`fedml_tpu.models.ModelDef`.

    flax linen modules are frozen dataclasses whose ``repr`` prints every
    hyperparameter, so (module class, repr) pins the architecture; the
    ModelDef adapter fields (input shape/dtype, dropout/batch-stats
    switches) pin the adapter behavior that also shapes the traced
    program. NOT stable across processes for arbitrary modules — the
    ProgramCache is in-process by design."""
    module = getattr(model, "module", None)
    try:
        input_dtype = str(np.dtype(getattr(model, "input_dtype", np.float32)))
    except TypeError:
        input_dtype = repr(getattr(model, "input_dtype", None))  # fedlint: disable=repr-in-digest -- non-dtype fallback; in-process stability is the documented ProgramCache contract
    return {
        "name": getattr(model, "name", type(model).__name__),
        "module": (
            [
                type(module).__module__,
                type(module).__qualname__,
                repr(module),  # fedlint: disable=repr-in-digest -- flax frozen-dataclass repr pins hyperparams; in-process-only stability is documented above
            ]
            if module is not None
            else None
        ),
        "input_shape": [int(s) for s in getattr(model, "input_shape", ())],
        "num_classes": getattr(model, "num_classes", None),
        "input_dtype": input_dtype,
        "has_dropout": bool(getattr(model, "has_dropout", False)),
        "has_batch_stats": bool(getattr(model, "has_batch_stats", False)),
        "counters": list(getattr(model, "counters", ())),
    }


def call_signature(args) -> tuple:
    """Hashable abstract signature of a concrete argument tuple: the
    pytree structure plus (shape, dtype) per leaf. This is the key the
    AOT-dispatch path uses to decide whether a warmed executable matches
    a call — shardings are deliberately NOT part of it (a sharding
    mismatch is caught by the executable itself and falls back to jit)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            sig.append(("py", repr(type(leaf)), repr(leaf)))
        else:
            sig.append((tuple(map(int, shape)), str(dtype)))
    return (str(treedef), tuple(sig))
