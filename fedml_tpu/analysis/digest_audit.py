"""Digest-completeness fuzzer — the mechanized form of PR 4's manual
factory audit.

The ProgramCache's correctness contract is one implication: **if two
configs produce different traced programs, their digests must differ.**
(The converse — digest splits on irrelevant fields — only costs a
duplicate compile, never numerics, and is allowed.) PR 4 verified the
implication by hand and found SCAFFOLD baking ``eta_g`` and ``1/N`` into
the traced round as constants while the digest ignored them: any
full-suite run mixing two scaffold configs silently reused the wrong
program. This module proves the implication per factory, on every tree:

for each registered factory spec
    build the base config's program          (in a FRESH ProgramCache)
    for each single-field perturbation
        build the perturbed program          (its own fresh cache)
        if the digests differ             -> fine ("distinct")
        else lower BOTH with abstract inputs
            identical module text         -> fine ("merged-identical")
            different module text         -> VIOLATION

Everything stays abstract — ``jit(...).lower()`` over
``jax.ShapeDtypeStruct`` trees traces but never compiles or executes,
so the full audit over every factory runs in seconds on CPU.

The fresh-cache-per-build discipline (``use_program_cache``) matters:
built through the shared global cache, a digest collision would hand the
perturbed build the BASE program object and there would be nothing left
to compare — the collision is exactly what must be observed.

``drop_digest_fields`` re-keys programs with named digest fields
removed (via the ``CachedProgram.key_fields`` introspection hook):
dropping ``server`` from the scaffold digest MUST make the audit fail
on the ``server.server_lr`` perturbation — tests/test_analysis.py pins
that the fuzzer really detects its target hazard class.

Perturbation lists are AUTO-DERIVED from the RunConfig dataclass tree
(:func:`auto_perturbations`): every leaf is perturbed with a
type-appropriate changed value, so a newly added config knob — a
CompileConfig field, a new TrainConfig hyperparameter — is audited by
default, against every registered factory, without anyone editing a
list. Leaves classified in :data:`KNOWN_BENIGN` (run structure, host
bucketing, transport wire, the compile-runtime knobs themselves) are
still audited every run, but against one representative spec instead of
the full factory fan-out, which keeps the audit's runtime bounded.

Collision comparisons hold the abstract input shapes FIXED at the base
config's: two configs whose digests collide share one jit object, and
the jit layer already compiles per input shape, so a field that only
changes which shapes get dispatched is harmless — the hazard is a
config value baked into the trace as a CONSTANT, which same-shape
lowering exposes."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Tuple

from fedml_tpu.config import RunConfig

# Shared abstract-shape vocabulary: C clients, S local steps, B batch,
# FEAT per-example features, NCLS classes, NTOT population size (kept in
# sync with the base config below).
S, B = 2, 8
FEAT = (10,)
NCLS = 3


@dataclasses.dataclass(frozen=True)
class Perturbation:
    """One single-field config change. ``field`` is a dotted RunConfig
    path ('train.lr', 'fed.epochs'); a leading '@' targets a factory
    kwarg instead ('@lam', '@q'), and '@kwarg.field' replaces one FIELD
    of a dataclass-valued kwarg ('@robust.num_byzantine' →
    dataclasses.replace on the RobustConfig) — the fan-out form that
    proves per-leaf digest coverage for config objects passed to
    factories outside the RunConfig tree."""

    field: str
    value: Any


@dataclasses.dataclass
class PerturbResult:
    field: str
    status: str  # distinct | merged-identical | rejected | unlowerable | VIOLATION
    detail: str = ""


@dataclasses.dataclass
class FactoryAudit:
    name: str
    results: List[PerturbResult]

    @property
    def violations(self) -> List[PerturbResult]:
        return [r for r in self.results if r.status == "VIOLATION"]

    def render(self) -> str:
        counts: Dict[str, int] = {}
        for r in self.results:
            counts[r.status] = counts.get(r.status, 0) + 1
        summary = ", ".join(f"{k}:{v}" for k, v in sorted(counts.items()))
        lines = [f"digest-audit {self.name}: {summary or 'no perturbations'}"]
        lines.extend(
            f"  VIOLATION {r.field}: {r.detail}" for r in self.violations
        )
        return "\n".join(lines)


class DigestAuditError(AssertionError):
    """At least one perturbation changed the lowered program without
    changing the digest — the silent-wrong-numerics hazard."""


@dataclasses.dataclass
class FactorySpec:
    """One registered program factory: how to build its CachedProgram
    from a config and how to make abstract lower() inputs for it."""

    name: str
    build: Callable[[RunConfig, dict, Dict[str, Any]], Any]
    args: Callable[[RunConfig, dict, Dict[str, Any]], tuple]
    perturbations: List[Perturbation]
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    needs_mesh: bool = False


def base_config() -> RunConfig:
    """Tiny, CPU-lowerable base point in config space. client_parallelism
    is pinned (not 'auto') so perturbing it is a pure one-field change."""
    from fedml_tpu.config import DataConfig, FedConfig

    return RunConfig(
        data=DataConfig(batch_size=B),
        fed=FedConfig(
            client_num_in_total=6,
            client_num_per_round=4,
            epochs=1,
            client_parallelism="vmap",
        ),
        model="lr",
    )


def config_replace(cfg: RunConfig, field: str, value: Any) -> RunConfig:
    """Nested one-field dataclasses.replace ('train.lr' -> new value)."""
    parts = field.split(".")
    if len(parts) == 1:
        return dataclasses.replace(cfg, **{parts[0]: value})
    if len(parts) == 2:
        section = getattr(cfg, parts[0])
        return dataclasses.replace(
            cfg, **{parts[0]: dataclasses.replace(section, **{parts[1]: value})}
        )
    raise ValueError(f"unsupported perturbation path {field!r}")


# --------------------------------------------------------------------------
# abstract input builders
# --------------------------------------------------------------------------


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _gv_shapes(model):
    import jax

    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def _cohort(cfg: RunConfig, C: int):
    """(x, y, mask, num_samples, rngs) abstract round inputs."""
    import numpy as np

    return (
        _sds((C, S, B) + FEAT, np.float32),
        _sds((C, S, B), np.int32),
        _sds((C, S, B), np.float32),
        _sds((C,), np.float32),
        _sds((C, 2), np.uint32),
    )


def _params_like(tree, lead=(), dtype=None):
    import jax
    import numpy as np

    return jax.tree_util.tree_map(
        lambda s: _sds(tuple(lead) + tuple(s.shape), dtype or np.dtype(s.dtype)),
        tree,
    )


def _model(ctx: dict):
    if "model" not in ctx:
        from fedml_tpu.models import create_model

        ctx["model"] = create_model("lr", "synthetic", FEAT, NCLS)
    return ctx["model"]


def _split_models(ctx: dict, width: int = 32):
    """Bottom/top ModelDef pair for one SplitNN cut width — the '@width'
    kwarg perturbation moves the CUT LAYER (a wider bottom emits a wider
    activation), which must split the digest (splitnn_cut_spec's model
    fingerprints)."""
    key = f"split_models_w{width}"
    if key not in ctx:
        from fedml_tpu.algorithms.split_nn import default_split_models

        ctx[key] = default_split_models(FEAT, NCLS, width=width)
    return ctx[key]


def _vfl_party_shapes(feature_dim: int, hidden_dim: int, out_dim: int,
                      has_labels: bool):
    """Abstract param shapes for one VFL party (extractor + dense head),
    matching algorithms/vertical_fl.py VFLParty.params."""
    import jax
    import numpy as np

    from fedml_tpu.models.vfl import VFLClassifier, VFLFeatureExtractor

    ex = VFLFeatureExtractor(output_dim=hidden_dim)
    de = VFLClassifier(output_dim=out_dim, use_bias=has_labels)
    k = jax.random.PRNGKey(0)
    return {
        "extractor": jax.eval_shape(
            ex.init, k, _sds((1, feature_dim), np.float32)
        ),
        "dense": jax.eval_shape(
            de.init, k, _sds((1, hidden_dim), np.float32)
        ),
    }


def _mesh(ctx: dict):
    if "mesh" not in ctx:
        from fedml_tpu.parallel.mesh import make_mesh

        ctx["mesh"] = make_mesh()
    return ctx["mesh"]


def _mesh_cohort_size(ctx: dict) -> int:
    mesh = _mesh(ctx)
    return max(int(mesh.size), 1) * 1


# --------------------------------------------------------------------------
# auto-derived perturbations (the RunConfig dataclass tree IS the list)
# --------------------------------------------------------------------------
#
# The lists used to be hand-curated per factory, which meant a NEW config
# knob was only audited if someone remembered to add it. Now every leaf
# of the RunConfig tree is perturbed by default; a field is only excluded
# from the full per-factory fan-out by being classified below — and the
# classified-benign leaves are still audited every run, on one
# representative spec, to prove the classification stays true.

# Choice-typed leaves where "default + noise" is not a legal value — the
# perturbed value must be a DIFFERENT member of the field's choice set.
_CHOICE_VALUES: Dict[str, Any] = {
    "data.partition_method": "homo",
    "train.client_optimizer": "adam",
    "train.compute_dtype": "bfloat16",
    "train.augment": "crop_flip",
    "fed.client_parallelism": "scan",
    "fed.selection": "weighted",
    "fed.state_store": "mmap",
    "server.server_optimizer": "adam",
    "comm.compression": "int8",
    "comm.activation_compression": "int8",
    "model": "mlp",
}

# Leaves that cannot change any REGISTERED factory's program: run
# structure, host-side data/bucketing knobs, transport wire options,
# scheduler/fault plumbing, and the compile-runtime knobs themselves
# (cache dirs, budgets — they steer WHEN programs compile, never what
# they compute). "model" is here for a harness reason, not a semantic
# one: every spec builds from the fixture's FIXED ModelDef (_model), so
# the cfg.model string cannot reach a factory in this harness either
# way — model-identity completeness is covered separately by
# model_fingerprint entering every factory digest (pinned by
# test_model_fingerprint_distinguishes_architectures and the factory
# dedup tests), not by this leaf. Audited on the representative spec
# each run (expected
# status: merged-identical/rejected, never VIOLATION) instead of fanning
# out over every registered factory, which bounds audit time. A leaf absent
# from BOTH this set and the tree is impossible; a NEW unclassified leaf
# — e.g. the next CompileConfig knob — fans out over every factory by
# default, which is the point.
KNOWN_BENIGN = frozenset({
    "model", "seed",
    "data.dataset", "data.data_dir", "data.partition_method",
    "data.partition_alpha", "data.batch_size", "data.pad_bucket",
    "data.device_cache",
    "fed.client_num_per_round", "fed.comm_round",
    "fed.frequency_of_the_test", "fed.ci", "fed.group_num",
    "fed.group_comm_round", "fed.selection", "fed.overprovision_factor",
    "fed.fault_plan", "fed.deadline_s", "fed.min_clients",
    "fed.eval_on_clients", "fed.async_buffer_k",
    "fed.async_staleness_exp", "fed.async_server_lr", "fed.state_store",
    "fed.state_budget_bytes", "fed.state_dir",
    "comm.compression", "comm.topk_frac", "comm.error_feedback",
    # activation-wire compression (fedml_tpu/splitfed/codec.py): encode/
    # decode run HOST-SIDE on the boundary payloads between dispatches —
    # the traced forward/server-step/backward programs see plain float32
    # arrays either way, so neither leaf can reach a program
    "comm.activation_compression", "comm.activation_error_feedback",
    "comm.secure_agg", "comm.send_retries", "comm.send_backoff_s",
    "comm.send_backoff_max_s", "comm.send_retry_deadline_s",
    "comm.send_timeout_s", "comm.send_fault_p", "comm.beacons",
    # connection-scaling knobs (fedml_tpu/fleet/): executor sizing, stream
    # budgets, and broker caps steer transport-side threads/queues only —
    # nothing here can reach a traced program
    "comm.grpc_max_workers", "comm.grpc_stream_budget",
    "comm.grpc_max_message_mb", "comm.grpc_keepalive_s",
    "comm.mqtt_max_connections",
    "mesh.client_shards", "mesh.axis_name",
    "compile.warmup", "compile.cache_dir", "compile.min_compile_time_s",
    "compile.executable_cache", "compile.recompile_budget",
    # PopulationConfig (fedml_tpu/population/): every leaf steers HOST-
    # SIDE structures — which sampler implementation draws the cohort,
    # where the packed index / sharded state records live on disk, and
    # the telemetry/checkpoint bounds. None can reach a traced program:
    # the cohort a policy draws is a program INPUT (ids/shapes), the
    # state tiers are exact byte stores outside jit, and the health/
    # loss-map bounds only affect bookkeeping. A leaf here changing a
    # lowered program would be a population-layer bug, not a digest gap.
    "population.ocohort_threshold", "population.index_mmap_bytes",
    "population.index_dir", "population.state_shard_bits",
    "population.loss_map_capacity", "population.selection_memo_rounds",
    "population.health_active_clients",
    "population.health_trace_budget_bytes",
    "population.flight_rounds", "population.flight_budget_bytes",
    # AdminConfig (fedml_tpu/serve/: admission.py, placement.py): pure
    # service control-plane policy — WHERE the serve layer schedules a
    # tenant (the device-slice pin changes which device dispatches, and
    # the compile layer already keys per-device via the pinned-signature
    # token in program_cache.py) and what the admission door requires
    # (headroom/flops thresholds that decide WHETHER a tenant builds at
    # all). None of it enters a factory's traced program.
    "admin.device_slice", "admin.admit_min_headroom_mb",
    "admin.admit_cost_cap_gflops",
})


def runconfig_leaves(cfg: Optional[RunConfig] = None) -> List[Tuple[str, Any]]:
    """Every (dotted path, current value) leaf of the RunConfig tree —
    one nesting level, matching the config's section.field shape."""
    cfg = cfg or base_config()
    out: List[Tuple[str, Any]] = []
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if dataclasses.is_dataclass(v):
            for sf in dataclasses.fields(v):
                out.append((f"{f.name}.{sf.name}", getattr(v, sf.name)))
        else:
            out.append((f.name, v))
    return out


def perturbed_value(path: str, value: Any) -> Any:
    """A type-appropriate SINGLE-field change for a leaf: choice members
    for enum-ish strings, flipped bools, nudged numbers. Any change
    works — the audit only needs the perturbed program to differ when
    the field matters."""
    if path in _CHOICE_VALUES:
        return _CHOICE_VALUES[path]
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 3
    if isinstance(value, float):
        return value * 2 + 0.015625
    if isinstance(value, str):
        return value + "_x"
    if value is None:  # Optional[...] leaves (recompile_budget, shards)
        return 7
    raise TypeError(f"unperturbable RunConfig leaf {path!r}: {value!r}")


def auto_perturbations(
    cfg: Optional[RunConfig] = None,
) -> Tuple[List[Perturbation], List[Perturbation]]:
    """Derive the audit's perturbation lists from the RunConfig tree:
    ``(fanout, benign)`` — ``fanout`` (every unclassified leaf) runs
    against EVERY registered factory; ``benign`` (the KNOWN_BENIGN
    classification) runs against the representative spec only."""
    fanout: List[Perturbation] = []
    benign: List[Perturbation] = []
    for path, value in runconfig_leaves(cfg):
        pert = Perturbation(path, perturbed_value(path, value))
        (benign if path in KNOWN_BENIGN else fanout).append(pert)
    return fanout, benign


# --------------------------------------------------------------------------
# the factory registry
# --------------------------------------------------------------------------

_AUTO_FANOUT, _AUTO_BENIGN = auto_perturbations()
_TRAIN_PERTURBS = [
    p for p in _AUTO_FANOUT
    if p.field.startswith("train.") or p.field == "fed.epochs"
]
_MODE_PERTURB = [
    p for p in _AUTO_FANOUT if p.field == "fed.client_parallelism"
]
_SERVER_PERTURBS = [p for p in _AUTO_FANOUT if p.field.startswith("server.")]
# classified-benign leaves — the representative spec re-proves every run
# that they merge identically (the audit tolerates benign digest merges
# instead of demanding splits)
_BENIGN_PERTURBS = list(_AUTO_BENIGN)


def _robust_config(**kw):
    from fedml_tpu.robustness import RobustConfig

    return RobustConfig(**kw)


def default_specs() -> List[FactorySpec]:
    import numpy as np

    C = 4

    def fedavg_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.fedavg import make_fedavg_round

        return make_fedavg_round(_model(ctx), cfg).variant_for(None)

    def fedavg_args(cfg, ctx, kw):
        return (_gv_shapes(_model(ctx)),) + _cohort(cfg, C)

    def fednova_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.fednova import make_fednova_round

        return make_fednova_round(_model(ctx), cfg)

    def qfedavg_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.qfedavg import make_qfedavg_round

        return make_qfedavg_round(_model(ctx), cfg, q=kw.get("q", 1.0))

    def scaffold_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.scaffold import make_scaffold_round

        return make_scaffold_round(_model(ctx), cfg)

    def scaffold_args(cfg, ctx, kw):
        import numpy as np

        gv = _gv_shapes(_model(ctx))
        params = gv["params"]
        N = cfg.fed.client_num_in_total
        return (
            gv,
            _params_like(params, dtype=np.float32),
            _params_like(params, lead=(N,), dtype=np.float32),
            _sds((C,), np.int32),
        ) + _cohort(cfg, C)

    def scaffold_cohort_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.scaffold import make_scaffold_cohort_round

        return make_scaffold_cohort_round(_model(ctx), cfg)

    def scaffold_cohort_args(cfg, ctx, kw):
        import numpy as np

        gv = _gv_shapes(_model(ctx))
        params = gv["params"]
        return (
            gv,
            _params_like(params, dtype=np.float32),
            _params_like(params, lead=(C,), dtype=np.float32),
        ) + _cohort(cfg, C)

    def ditto_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.ditto import make_ditto_round

        return make_ditto_round(_model(ctx), cfg, lam=kw.get("lam", 0.1))

    def ditto_args(cfg, ctx, kw):
        import numpy as np

        gv = _gv_shapes(_model(ctx))
        N = cfg.fed.client_num_in_total
        return (
            gv,
            _params_like(gv, lead=(N,)),
            _sds((C,), np.int32),
        ) + _cohort(cfg, C)

    def ditto_cohort_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.ditto import make_ditto_cohort_round

        return make_ditto_cohort_round(_model(ctx), cfg, lam=kw.get("lam", 0.1))

    def ditto_cohort_args(cfg, ctx, kw):
        gv = _gv_shapes(_model(ctx))
        return (gv, _params_like(gv, lead=(C,))) + _cohort(cfg, C)

    def server_step_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.fedopt import make_cached_server_step

        prog, _opt = make_cached_server_step(cfg)
        return prog

    def server_step_args(cfg, ctx, kw):
        import jax

        from fedml_tpu.algorithms.fedopt import make_server_optimizer

        gv = _gv_shapes(_model(ctx))
        opt_state = jax.eval_shape(
            make_server_optimizer(cfg.server).init, gv["params"]
        )
        return (gv, gv, opt_state)

    def eval_build(cfg, ctx, kw):
        from fedml_tpu.train.evaluate import make_eval_fn

        return make_eval_fn(_model(ctx))

    def eval_args(cfg, ctx, kw):
        import numpy as np

        return (
            _gv_shapes(_model(ctx)),
            _sds((S, B) + FEAT, np.float32),
            _sds((S, B), np.int32),
            _sds((S, B), np.float32),
        )

    def local_train_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.fedavg_transport import shared_local_train

        return shared_local_train(_model(ctx), cfg, "classification")

    def local_train_args(cfg, ctx, kw):
        import numpy as np

        return (
            _gv_shapes(_model(ctx)),
            _sds((S, B) + FEAT, np.float32),
            _sds((S, B), np.int32),
            _sds((S, B), np.float32),
            _sds((2,), np.uint32),
        )

    def robust_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.fedavg_robust import make_robust_fedavg_round

        return make_robust_fedavg_round(
            _model(ctx), cfg, kw["robust"]
        ).variant_for(None)

    def robust_args(cfg, ctx, kw):
        import numpy as np

        # the defense hooks take one extra arg: the weak-DP noise rng
        return (
            (_gv_shapes(_model(ctx)),)
            + _cohort(cfg, C)
            + (_sds((2,), np.uint32),)
        )

    def sharded_fedavg_build(cfg, ctx, kw):
        from fedml_tpu.parallel.fedavg_sharded import make_sharded_fedavg_round

        return make_sharded_fedavg_round(_model(ctx), cfg, _mesh(ctx))

    def sharded_args(cfg, ctx, kw):
        return (_gv_shapes(_model(ctx)),) + _cohort(cfg, _mesh_cohort_size(ctx))

    def sharded_fednova_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.fednova import make_sharded_fednova_round

        return make_sharded_fednova_round(_model(ctx), cfg, _mesh(ctx))

    def sharded_scaffold_build(cfg, ctx, kw):
        from fedml_tpu.algorithms.scaffold import make_sharded_scaffold_round

        return make_sharded_scaffold_round(_model(ctx), cfg, _mesh(ctx))

    def sharded_scaffold_args(cfg, ctx, kw):
        import numpy as np

        gv = _gv_shapes(_model(ctx))
        params = gv["params"]
        N = cfg.fed.client_num_in_total
        Cm = _mesh_cohort_size(ctx)
        return (
            gv,
            _params_like(params, dtype=np.float32),
            _params_like(params, lead=(N,), dtype=np.float32),
            _sds((Cm,), np.int32),
        ) + _cohort(cfg, Cm)

    def splitnn_fused_build(cfg, ctx, kw):
        from fedml_tpu.splitfed.programs import make_splitnn_fused_step

        bottom, top = _split_models(ctx, kw.get("width", 32))
        return make_splitnn_fused_step(
            bottom, top, lr=cfg.train.lr, momentum=cfg.train.momentum,
            wd=cfg.train.wd,
        )

    def splitnn_fused_args(cfg, ctx, kw):
        import jax
        import numpy as np

        from fedml_tpu.splitfed.programs import make_split_optimizer

        bottom, top = _split_models(ctx, kw.get("width", 32))
        params = {
            "bottom": _gv_shapes(bottom)["params"],
            "top": _gv_shapes(top)["params"],
        }
        opt = make_split_optimizer(
            cfg.train.lr, cfg.train.momentum, cfg.train.wd
        )
        return (
            params,
            jax.eval_shape(opt.init, params),
            _sds((B,) + FEAT, np.float32),
            _sds((B,), np.int32),
        )

    def splitnn_server_build(cfg, ctx, kw):
        from fedml_tpu.splitfed.programs import make_splitnn_server_step

        _bottom, top = _split_models(ctx, kw.get("width", 32))
        return make_splitnn_server_step(
            top, cfg.train.lr, cfg.train.momentum, cfg.train.wd
        )

    def splitnn_server_args(cfg, ctx, kw):
        import jax
        import numpy as np

        from fedml_tpu.splitfed.programs import make_split_optimizer

        width = kw.get("width", 32)
        _bottom, top = _split_models(ctx, width)
        tp = _gv_shapes(top)["params"]
        opt = make_split_optimizer(
            cfg.train.lr, cfg.train.momentum, cfg.train.wd
        )
        return (
            tp,
            jax.eval_shape(opt.init, tp),
            _sds((B, width), np.float32),
            _sds((B,), np.int32),
        )

    def vfl_fused_build(cfg, ctx, kw):
        from fedml_tpu.splitfed.programs import make_vfl_fused_step

        return make_vfl_fused_step(
            kw["feature_splits"], hidden_dim=kw.get("hidden_dim", 16),
            out_dim=1, lr=cfg.train.lr,
        )

    def vfl_fused_args(cfg, ctx, kw):
        import jax
        import numpy as np
        import optax

        splits = kw["feature_splits"]
        hd = kw.get("hidden_dim", 16)
        all_params = [
            _vfl_party_shapes(d, hd, 1, i == 0)
            for i, d in enumerate(splits)
        ]
        opt = optax.sgd(cfg.train.lr, momentum=0.9)
        return (
            all_params,
            jax.eval_shape(opt.init, all_params),
            [_sds((B, d), np.float32) for d in splits],
            _sds((B,), np.float32),
        )

    # Every spec audits the FULL auto-derived fan-out (every unclassified
    # RunConfig leaf) — the hand-curated per-factory subsets this
    # replaces silently exempted new knobs. Factory-kwarg perturbations
    # (@q, @lam) ride along where the factory takes them; the
    # representative fedavg_round spec additionally re-proves the
    # KNOWN_BENIGN classification each run.
    return [
        FactorySpec(
            "fedavg_round", fedavg_build, fedavg_args,
            _AUTO_FANOUT + _BENIGN_PERTURBS,
        ),
        FactorySpec("fednova_round", fednova_build, fedavg_args, _AUTO_FANOUT),
        FactorySpec(
            "qfedavg_round", qfedavg_build, fedavg_args,
            _AUTO_FANOUT + [Perturbation("@q", 2.0)],
        ),
        FactorySpec(
            "scaffold_round", scaffold_build, scaffold_args, _AUTO_FANOUT,
        ),
        FactorySpec(
            "scaffold_cohort_round", scaffold_cohort_build,
            scaffold_cohort_args, _AUTO_FANOUT,
        ),
        FactorySpec(
            "ditto_round", ditto_build, ditto_args,
            _AUTO_FANOUT + [Perturbation("@lam", 0.5)],
        ),
        FactorySpec(
            "ditto_cohort_round", ditto_cohort_build, ditto_cohort_args,
            _AUTO_FANOUT + [Perturbation("@lam", 0.5)],
        ),
        FactorySpec(
            "fedopt_server_step", server_step_build, server_step_args,
            _AUTO_FANOUT,
        ),
        # The Byzantine-robust round (ISSUE 14): cached with the whole
        # RobustConfig in its digest instead of the historical
        # wrap_uncached bypass. Two bases so every RobustConfig leaf
        # reaches a trace somewhere: the order-statistics base exercises
        # defense_type/num_byzantine (trim_k)/multi_krum_m, the weak_dp
        # base exercises norm_bound (clip) and stddev (noise). Dropping
        # the 'robust' digest field must fail on exactly these leaves —
        # the scaffold eta_g pin's analog, tests/test_robust_compile.py.
        FactorySpec(
            "robust_fedavg_round", robust_build, robust_args,
            _AUTO_FANOUT + [
                Perturbation("@robust.defense_type", "median"),
                Perturbation("@robust.defense_type", "multi_krum"),
                Perturbation("@robust.num_byzantine", 0),
                Perturbation("@robust.multi_krum_m", 2),
                Perturbation("@robust.norm_bound", 1.5),
                Perturbation("@robust.stddev", 0.5),
            ],
            kwargs={
                "robust": _robust_config(
                    defense_type="trimmed_mean", num_byzantine=1
                )
            },
        ),
        FactorySpec(
            "robust_clip_round", robust_build, robust_args,
            [
                Perturbation("@robust.defense_type", "norm_diff_clipping"),
                Perturbation("@robust.norm_bound", 1.5),
                Perturbation("@robust.stddev", 0.5),
            ],
            kwargs={"robust": _robust_config(defense_type="weak_dp")},
        ),
        # The split/vertical factories (PR 19, fedml_tpu/splitfed/): the
        # cut spec is the hazard surface — '@width' moves the SplitNN cut
        # layer (both model fingerprints change), '@feature_splits' /
        # '@hidden_dim' move the VFL party layout; lr/momentum/wd ride
        # the auto fan-out (train.*) and are baked into the traced
        # updates exactly like scaffold's eta_g.
        FactorySpec(
            "splitnn_fused_step", splitnn_fused_build, splitnn_fused_args,
            _AUTO_FANOUT + [Perturbation("@width", 48)],
        ),
        FactorySpec(
            "splitnn_server_step", splitnn_server_build, splitnn_server_args,
            _AUTO_FANOUT + [Perturbation("@width", 48)],
        ),
        FactorySpec(
            "vfl_fused_step", vfl_fused_build, vfl_fused_args,
            _AUTO_FANOUT + [
                Perturbation("@feature_splits", (4, 3, 2, 1)),
                Perturbation("@feature_splits", (5, 5)),
                Perturbation("@hidden_dim", 8),
            ],
            kwargs={"feature_splits": (4, 3, 3)},
        ),
        FactorySpec("eval", eval_build, eval_args, _AUTO_FANOUT),
        FactorySpec(
            "local_train", local_train_build, local_train_args, _AUTO_FANOUT
        ),
        FactorySpec(
            "sharded_fedavg_round", sharded_fedavg_build, sharded_args,
            _AUTO_FANOUT, needs_mesh=True,
        ),
        FactorySpec(
            "sharded_fednova_round", sharded_fednova_build, sharded_args,
            _AUTO_FANOUT, needs_mesh=True,
        ),
        FactorySpec(
            "sharded_scaffold_round", sharded_scaffold_build,
            sharded_scaffold_args, _AUTO_FANOUT, needs_mesh=True,
        ),
    ]


# --------------------------------------------------------------------------
# the audit itself
# --------------------------------------------------------------------------


def _build_fresh(spec: FactorySpec, cfg: RunConfig, ctx: dict, kw: Dict[str, Any]):
    """Build the spec's program in a fresh ProgramCache (see module doc)."""
    from fedml_tpu.compile import ProgramCache, use_program_cache

    with use_program_cache(ProgramCache()):
        return spec.build(cfg, ctx, kw)


def _digest_of(prog, drop: FrozenSet[str]) -> Optional[str]:
    if not drop or not getattr(prog, "key_fields", None):
        return getattr(prog, "digest", None)
    from fedml_tpu.compile import program_digest

    return program_digest(
        {k: v for k, v in prog.key_fields.items() if k not in drop}
    )


def _lowered_text(prog, args) -> str:
    low = prog.lower(*args)
    try:
        text = low.as_text()
    except Exception:  # pragma: no cover — very old jax
        text = str(low.compiler_ir())
    # strip location metadata — it can differ between two otherwise
    # identical traces (closure line numbers)
    return "\n".join(
        ln for ln in text.splitlines() if not ln.lstrip().startswith("loc(")
    )


def audit_factory(
    spec: FactorySpec,
    cfg: Optional[RunConfig] = None,
    ctx: Optional[dict] = None,
    drop_digest_fields: FrozenSet[str] = frozenset(),
) -> FactoryAudit:
    """Run the completeness audit for one factory. Raises nothing —
    returns the per-perturbation verdicts (callers decide severity)."""
    cfg = cfg or base_config()
    ctx = ctx if ctx is not None else {}
    drop = frozenset(drop_digest_fields)
    base_prog = _build_fresh(spec, cfg, ctx, dict(spec.kwargs))
    base_digest = _digest_of(base_prog, drop)
    base_text: Optional[str] = None
    results: List[PerturbResult] = []
    for pert in spec.perturbations:
        kw = dict(spec.kwargs)
        if pert.field.startswith("@"):
            name = pert.field[1:]
            if "." in name:
                # '@kwarg.field': one-field dataclasses.replace on a
                # dataclass-valued kwarg (e.g. '@robust.num_byzantine')
                obj_name, attr = name.split(".", 1)
                kw[obj_name] = dataclasses.replace(
                    kw[obj_name], **{attr: pert.value}
                )
            else:
                kw[name] = pert.value
            cfg2 = cfg
        else:
            cfg2 = config_replace(cfg, pert.field, pert.value)
        try:
            prog2 = _build_fresh(spec, cfg2, ctx, kw)
        except Exception as e:  # noqa: BLE001 — guards ARE the protection
            results.append(
                PerturbResult(pert.field, "rejected", f"{type(e).__name__}: {e}")
            )
            continue
        d2 = _digest_of(prog2, drop)
        if base_digest is None or d2 is None:
            results.append(
                PerturbResult(
                    pert.field, "VIOLATION",
                    "program has no digest (bypassed factory?) — the audit "
                    "cannot prove completeness",
                )
            )
            continue
        if d2 != base_digest:
            results.append(PerturbResult(pert.field, "distinct"))
            continue
        # digest collision: the programs MUST be identical — compared at
        # the BASE config's abstract shapes. A collision means both
        # configs share ONE jit object, and the jit layer compiles per
        # input shape anyway, so a field that only changes which shapes
        # get dispatched (a lead-axis count sourcing an argument shape)
        # is harmless; lowering the perturbed program at the perturbed
        # shapes would flag exactly that and drown the real hazard —
        # config values baked into the trace as CONSTANTS (the scaffold
        # eta_g / 1/N class), which same-shape lowering still exposes.
        try:
            if base_text is None:
                base_text = _lowered_text(base_prog, spec.args(cfg, ctx, dict(spec.kwargs)))
            text2 = _lowered_text(prog2, spec.args(cfg, ctx, dict(spec.kwargs)))
        except Exception as e:  # noqa: BLE001 — backend can't lower this combo
            results.append(
                PerturbResult(
                    pert.field, "unlowerable", f"{type(e).__name__}: {e}"
                )
            )
            continue
        if text2 == base_text:
            results.append(PerturbResult(pert.field, "merged-identical"))
        else:
            results.append(
                PerturbResult(
                    pert.field, "VIOLATION",
                    f"perturbing {pert.field} -> {pert.value!r} changed the "
                    "lowered program but not the digest "
                    f"({(base_digest or '')[:12]}) — two configs would share "
                    "one wrong executable",
                )
            )
    return FactoryAudit(spec.name, results)


def audit_all(
    specs: Optional[List[FactorySpec]] = None,
    cfg: Optional[RunConfig] = None,
) -> Tuple[List[FactoryAudit], List[PerturbResult]]:
    """Audit every registered factory; returns (audits, violations).

    A fan-out field whose perturbation is REJECTED by every factory is
    itself a violation: it means the derived value is illegal everywhere
    (typically a new choice-typed leaf missing from ``_CHOICE_VALUES``),
    so the leaf is silently unaudited — the exact failure mode
    auto-derivation exists to prevent."""
    specs = specs if specs is not None else default_specs()
    cfg = cfg or base_config()
    ctx: dict = {}
    audits = [audit_factory(s, cfg=cfg, ctx=ctx) for s in specs]
    violations = [v for a in audits for v in a.violations]
    by_field: Dict[str, set] = {}
    for a in audits:
        for r in a.results:
            by_field.setdefault(r.field, set()).add(r.status)
    for field, statuses in sorted(by_field.items()):
        if statuses == {"rejected"}:
            violations.append(
                PerturbResult(
                    field, "VIOLATION",
                    "perturbation rejected by EVERY factory — the leaf is "
                    "effectively unaudited; give it a legal alternative "
                    "value in _CHOICE_VALUES (or classify it KNOWN_BENIGN "
                    "with justification)",
                )
            )
    return audits, violations


def assert_digests_complete(specs=None) -> List[FactoryAudit]:
    """Raise :class:`DigestAuditError` on any violation (pytest entry)."""
    audits, violations = audit_all(specs)
    if violations:
        per_factory = {id(v) for a in audits for v in a.violations}
        lines = [a.render() for a in audits if a.violations]
        lines.extend(
            f"digest-audit GLOBAL: VIOLATION {v.field}: {v.detail}"
            for v in violations
            if id(v) not in per_factory
        )
        raise DigestAuditError("\n".join(lines))
    return audits
