"""Flax model zoo (ref: fedml_api/model/, re-exported at model/__init__.py:1-15).

Every model is wrapped in a :class:`ModelDef` adapter giving the framework a
uniform functional surface: ``init(rng) -> variables`` and
``apply(variables, x, train, rng) -> (outputs, updated_variables)``. The
variables pytree may contain non-param collections (e.g. ``batch_stats`` for
BatchNorm models) — FedAvg averages those with the same sample weights the
reference uses for BN running stats (ref FedAVGAggregator.py:66-71 averages the
full state_dict, which includes BN stats)."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax.core import FrozenDict


def no_flush_attrs(batch: int) -> dict:
    """``ModelDef.flush_attrs`` of a model without kernel sites."""
    return {}


@dataclasses.dataclass
class ModelDef:
    module: Any  # flax linen Module
    input_shape: Tuple[int, ...]  # per-example shape (no batch dim)
    num_classes: int
    input_dtype: Any = jnp.float32
    has_dropout: bool = False
    has_batch_stats: bool = False
    name: str = "model"
    # Device counters the module reports in training: it sows one float32
    # vector (an entry per name here) into the "counters" collection wherever
    # it counts, and ``apply(..., counters=True)`` hands back their sum. The
    # local-train loop carries them with its metrics.
    counters: Tuple[str, ...] = ()
    # From the samples a local step trains on, the model's host constants
    # that every ``flush`` span carries for the per-layer metrics: how many
    # calls of each kernel-backed op take its kernel, by the op's own
    # ``takes_kernel`` (``*_kernel_sites`` of ``*_sites``), and the widths and
    # counts those metrics' FLOPs and bytes follow from. The model builds
    # them (``DecoderLM.flush_attrs``, ``TransformerLM.flush_attrs``).
    flush_attrs: Callable[[int], dict] = no_flush_attrs

    def init(self, rng) -> dict:
        dummy = jnp.zeros((1,) + tuple(self.input_shape), dtype=self.input_dtype)
        rngs = {"params": rng}
        if self.has_dropout:
            rngs["dropout"] = jax.random.fold_in(rng, 1)
        variables = dict(self.module.init(rngs, dummy, train=False))
        variables.pop("counters", None)  # what init's own pass sowed
        return jax.tree_util.tree_map(lambda a: a, variables)

    def apply(self, variables, x, train: bool, rng=None, counters: bool = False):
        """Returns (outputs, updated_variables), and with ``counters=True``
        a third item: the module's counters summed into one vector (see
        ``counters`` above; a model that reports none gives an empty one)."""
        rngs = {}
        if self.has_dropout and train:
            rngs["dropout"] = rng if rng is not None else jax.random.PRNGKey(0)
        if counters:
            if self.has_batch_stats:
                raise NotImplementedError("no model reports counters beside batch statistics")
            out, sown = self.module.apply(
                variables, x, train=train, rngs=rngs, mutable=["counters"]
            )
            counted = sum(
                jax.tree_util.tree_leaves(sown),
                jnp.zeros((len(self.counters),), jnp.float32),
            )
            return out, variables, counted
        if self.has_batch_stats and train:
            out, mutated = self.module.apply(
                variables, x, train=train, rngs=rngs, mutable=["batch_stats"]
            )
            new_vars = dict(variables)
            new_vars["batch_stats"] = mutated["batch_stats"]
            return out, new_vars
        out = self.module.apply(variables, x, train=train, rngs=rngs)
        return out, variables


def create_model(model_name: str, dataset_name: str, input_shape, num_classes, **kw) -> ModelDef:
    """Model-name × dataset → ModelDef dispatch
    (ref fedml_experiments/base.py:103-140 create_model)."""
    from fedml_tpu.models import registry

    return registry.create(model_name, dataset_name, input_shape, num_classes, **kw)
