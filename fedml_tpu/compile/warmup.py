"""AOT warmup — compile the run's programs before round 0.

Round 0 of a cold run silently includes XLA compilation: the first round
dispatch blocks on a compile that can take orders of magnitude longer
than the round itself, which skews round-0 wall-clock metrics and forced
the transport deadline/quorum machinery to special-case "arbitrarily
long cold compiles" (PR 3). ``--warmup`` moves that cost to an explicit,
observable phase: every program the run will dispatch at round 0 is
``jit(...).lower(...).compile()``d up front (through
:meth:`CachedProgram.warmup`, which keeps the executable for dispatch —
so the warmup compile IS the run's compile, not a duplicate), under
``compile`` telemetry spans, with per-program XLA cost analysis
(flops/bytes) and compile seconds forwarded into summary.json.

Warm and cold runs are numerically identical by construction: warmup
only lowers and compiles — it executes nothing, consumes no RNG, and
touches no training state (pinned by tests/test_compile.py).

Covered programs, matching what ``FedAvgAPI.train`` dispatches first:

- the round program — the round-fn variant for round
  ``start_round``'s cohort shapes;
- **every other (steps, bs) shape class the partition can produce**
  (:func:`fedml_tpu.data.base.partition_shape_classes` — the cohort
  bucket only reads the max member's count, so the reachable classes
  are exactly the per-client singleton buckets), including both
  ``may_pad`` round variants where the partition makes both reachable —
  so rounds 1..R never hit a lazy shape-bucket compile, no matter which
  cohorts the scheduler draws;
- the eval program at the cached test-batch shapes;
- the server-optimizer step (FedOpt family), when present.

With a persistent executable cache installed
(compile/executable_cache.py), every warmed program is additionally
serialized to disk — so the NEXT process deserializes its whole warmup
set instead of compiling it (zero-cold-start serving).

Remaining lazy compiles: shape classes past ``_MAX_WARM_CLASSES``, and
cohorts reshaped mid-run by participation faults (a fault-shrunk cohort
is a different client-axis size)."""

from __future__ import annotations

import logging
import time
from typing import Callable, Optional

from fedml_tpu.telemetry import get_tracer


def _warm_one(rows: dict, label: str, fn, args, tracer) -> None:
    """AOT-compile one program; record per-program stats; never crash the
    run (a backend without AOT support degrades to lazy compilation)."""
    if not hasattr(fn, "warmup"):
        from fedml_tpu.compile.program_cache import get_program_cache

        # the AOT executable lives on this throwaway wrapper, so only the
        # persistent compile cache (when installed) carries the benefit to
        # the run's lazy dispatch — route the factory through the
        # ProgramCache instead of relying on this fallback
        logging.warning(
            "warmup program %r is a bare jit object (no ProgramCache "
            "wrapper): the warmed executable cannot serve its dispatches "
            "directly", label,
        )
        fn = get_program_cache().wrap_uncached(label, fn)
    try:
        st = fn.warmup(*args, tracer=tracer)
    except Exception as e:  # noqa: BLE001 — warmup must not kill the run
        logging.warning("warmup of program %r failed: %s", label, e)
        rows[f"compile/{label}_error"] = f"{type(e).__name__}: {e}"
        return
    rows[f"compile/{label}_compile_s"] = st["compile_s"]
    rows[f"compile/{label}_aot_cache_hit"] = bool(st.get("aot_cache_hit"))
    if st.get("deserialized"):
        # the program came from the persistent executable store — nothing
        # compiled (compile_s is 0 by contract); the row says so, so a
        # warm-from-disk run's summary is distinguishable from a hit
        rows[f"compile/{label}_deserialized"] = True
        rows[f"compile/{label}_deserialize_s"] = st.get("deserialize_s", 0.0)
    if st.get("flops"):
        rows[f"compile/{label}_flops"] = st["flops"]
    if st.get("bytes"):
        rows[f"compile/{label}_bytes"] = st["bytes"]


# Pre-enumeration cap: full-batch mode (batch_size=-1) makes bs the
# cohort max, so a ragged partition can yield one class per DISTINCT
# client size — compiling them all would turn warmup into a multi-hour
# stall over shapes most runs never dispatch. Classes are warmed
# most-populous first and the skip is LOGGED (never silent).
_MAX_WARM_CLASSES = 32


def _classes_by_population(
    counts, batch_size: int, pad_bucket: int, cohort: int = 1
):
    """Partition shape classes ordered by member count (descending) —
    under the warm cap, the classes that cover the most clients (and so
    the most future cohorts) compile first.

    ``cohort`` filters UNREACHABLE classes: a class is a cohort's shape
    only when its defining client is the cohort MAX, which needs at
    least ``cohort`` clients at-or-below that size to draw from (without
    replacement). With counts=[8,100,100,100] and cohort=4 every cohort
    contains a 100-sample client, so the size-8 singleton class can
    never be dispatched — warming it would waste compile time and cache
    entries. Callers with fault-shrinkable cohorts pass cohort=1 (a
    shrunk cohort CAN make small classes reachable)."""
    from fedml_tpu.data.base import bucket_steps, partition_shape_classes

    classes = partition_shape_classes(counts, batch_size, pad_bucket)
    population: dict = {}
    class_max: dict = {}
    for n in counts:
        k = bucket_steps([int(n)], batch_size, pad_bucket)[:2]
        population[k] = population.get(k, 0) + 1
        class_max[k] = max(class_max.get(k, 0), int(n))
    sorted_counts = sorted(int(n) for n in counts)
    import bisect

    def reachable(k) -> bool:
        return bisect.bisect_right(sorted_counts, class_max[k]) >= cohort

    ordered = sorted(
        ((k, v) for k, v in classes.items() if reachable(k)),
        key=lambda kv: (-population[kv[0]], kv[0]),
    )
    skipped = max(0, len(ordered) - _MAX_WARM_CLASSES)
    if skipped:
        logging.warning(
            "shape-class pre-enumeration capped: warming the %d most-"
            "populous of %d classes (%d skipped — they will compile "
            "lazily on first dispatch). A class count this high usually "
            "means batch_size=-1 (full-batch mode) over a ragged "
            "partition; consider pad_bucket to collapse classes.",
            _MAX_WARM_CLASSES, len(ordered), skipped,
        )
    return ordered[:_MAX_WARM_CLASSES], skipped


def _class_may_pad_variants(fulls, st: int, bs: int, cohort: int):
    """Which ``may_pad`` round variants are reachable for shape class
    ``(st, bs)`` given the partition's per-client full-step counts
    (``ceil(n/bs)`` per client). A client can join an (st, bs) cohort iff
    its full-step count <= st; the cohort pads iff any member underfills
    the bucketed step count. ``may_pad=False`` needs a whole cohort of
    exact fills; ``True`` needs one underfill (its own bucket rounding,
    or a smaller ride-along client)."""
    members = [f for f in fulls if f <= st]
    exact = sum(1 for f in members if f == st)
    variants = []
    if exact >= cohort:
        variants.append(False)
    if any(f < st for f in members):
        variants.append(True)
    return variants or [None]


def _warm_partition_classes(api, rows: dict, tracer, r0: int) -> None:
    """Pre-enumerate and AOT-compile the round program for EVERY
    (steps, bs) shape class the partition can produce — not just round
    ``r0``'s — so later rounds whose cohorts bucket differently dispatch
    a warmed executable instead of paying a lazy compile (ROADMAP item 1:
    every later-round shape bucket used to compile lazily at dispatch).

    Synthetic all-zero batches drive the lowering (only shapes/dtypes
    enter ``lower()``); they pass through ``api._place_batch`` so mesh
    runtimes warm against the exact shardings their dispatches carry.
    Round ``r0``'s class was already warmed from its real batch — the
    re-warm here is a free per-signature hit that only labels the row."""
    import jax
    import numpy as np

    from fedml_tpu.data.base import ClientBatch

    cfg = api.config
    data = api.data
    counts = [int(n) for n in api._client_counts(range(data.num_clients))]
    cohort = len(api._round_plan(r0)[0])
    # participation faults shrink cohorts mid-run, which can make classes
    # reachable that full cohorts never produce — enumerate as if cohorts
    # could be singletons then
    faults = getattr(api, "faults", None)
    reach_cohort = (
        1
        if faults is not None and faults.plan.has_participation_faults()
        else cohort
    )
    classes, skipped = _classes_by_population(
        counts, cfg.data.batch_size, cfg.data.pad_bucket,
        cohort=reach_cohort,
    )
    if skipped:
        rows["compile/warm_classes_skipped"] = skipped
    feat = tuple(data.client_x[0].shape[1:])
    lab = tuple(data.client_y[0].shape[1:])
    xdt, ydt = data.client_x[0].dtype, data.client_y[0].dtype
    fn = api.round_fn
    variant_for = getattr(fn, "variant_for", None)
    can_vary = bool(getattr(fn, "supports_may_pad", False))
    rng = jax.random.fold_in(api.rng, r0 + 1)  # shape-only: (2,) uint32
    store = getattr(api, "_store", None)
    for (st, bs), _rep in classes:
        if store is not None:
            # the HBM-store round-batch program (gather + reshape) is a
            # per-class dispatch too — warm it, or round 1..R's first
            # cohort in this class pays ITS lazy compile instead
            _warm_one(
                rows,
                f"gather_s{st}b{bs}",
                store.gather_program(st, bs),
                (
                    store.flat_x,
                    store.flat_y,
                    np.zeros((cohort, st * bs), np.int32),
                    np.zeros((cohort, st * bs), np.float32),
                ),
                tracer,
            )
        batch = ClientBatch(
            x=np.zeros((cohort, st, bs) + feat, xdt),
            y=np.zeros((cohort, st, bs) + lab, ydt),
            mask=np.ones((cohort, st, bs), np.float32),
            num_samples=np.ones((cohort,), np.float32),
        )
        placed = api._place_batch(batch, rng)
        if can_vary:
            fulls = [-(-n // bs) for n in counts]
            variants = _class_may_pad_variants(fulls, st, bs, cohort)
        else:
            variants = [None]
        for mp in variants:
            f = variant_for(mp) if variant_for is not None else fn
            suffix = {False: "_nopad", True: "_pad"}.get(mp, "")
            _warm_one(
                rows,
                f"round_s{st}b{bs}{suffix}",
                f,
                (api.global_vars, *placed),
                tracer,
            )


def warmup_api(api, log_fn: Optional[Callable[[dict], None]] = None) -> dict:
    """Warm a FedAvgAPI-family simulator (vmap or mesh): round + eval +
    server-optimizer programs for ``api.start_round``'s shapes. Returns
    the compile-stats row (also forwarded through ``log_fn``)."""
    import jax

    tracer = getattr(api, "_tracer", None) or get_tracer()
    rows: dict = {}
    t0 = time.perf_counter()
    with tracer.span("warmup"):
        r0 = int(getattr(api, "start_round", 0))
        mesh = getattr(api, "mesh", None)
        if mesh is not None:
            # mesh runtime: round outputs carry NamedSharding(mesh, P()),
            # so from round r0+1 on the round INPUT does too. Replicate
            # global_vars onto the mesh now (values unchanged) so ONE
            # warmed executable serves every round, instead of matching
            # only round r0's single-device placement.
            from jax.sharding import NamedSharding, PartitionSpec

            api.global_vars = jax.device_put(
                api.global_vars, NamedSharding(mesh, PartitionSpec())
            )
        # -- round program: the variant for round r0's cohort --
        sampled = api._round_plan(r0)[0]
        batch = api._round_batch(sampled, r0)
        rng = jax.random.fold_in(api.rng, r0 + 1)
        placed = api._place_batch(batch, rng)
        if hasattr(api, "_warm_placed"):
            # hand the placed batch to train_round(r0) so the stack +
            # host->device transfer is paid once, not twice
            api._warm_placed[r0] = placed
        fn = api.round_fn
        variant_for = getattr(fn, "variant_for", None)
        if variant_for is not None:
            fn = variant_for(api._round_may_pad(r0))
        _warm_one(rows, "round", fn, (api.global_vars, *placed), tracer)
        # every OTHER shape class the partition can produce — rounds
        # 1..R must never pay a lazy shape-bucket compile
        try:
            _warm_partition_classes(api, rows, tracer, r0)
        except Exception as e:  # noqa: BLE001 — enumeration must not
            logging.warning(  # kill the run; r0 is already warm
                "shape-class pre-enumeration failed: %s", e
            )
            rows["compile/class_enum_error"] = f"{type(e).__name__}: {e}"
        # -- eval program at the cached test-batch shapes --
        if getattr(api, "eval_fn", None) is not None and hasattr(
            api, "_eval_batches"
        ):
            batches = api._eval_batches()
            _warm_one(
                rows, "eval", api.eval_fn, (api.global_vars, *batches), tracer
            )
        # -- server optimizer step (FedOpt family) --
        server_step = getattr(api, "_server_step", None)
        opt_state = getattr(api, "server_opt_state", None)
        if server_step is not None and opt_state is not None:
            _warm_one(
                rows,
                "server_opt",
                server_step,
                (api.global_vars, api.global_vars, opt_state),
                tracer,
            )
    rows["compile/warmup_s"] = time.perf_counter() - t0
    if log_fn is not None:
        log_fn(dict(rows))
    return rows


def warmup_splitnn(
    bottom,
    top,
    config,
    data,
    log_fn: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Warm every program a split federation dispatches — the boundary-cut
    triple (client forward, server top-step, client backward), the fused
    simulator step they must stay byte-parity with, and the eval program —
    for the run's one activation shape class (``batch_size`` × the cut
    width, derived via ``jax.eval_shape`` so no real forward runs).

    Split rounds are a RELAY: a cold boundary compile stalls not just one
    client but every later ring slot behind it, so the warmup barrier
    matters more here than in the horizontal family. All five factories
    route through the ProgramCache, so with a persistent executable store
    installed the warmed set deserializes on the next process start."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.splitfed.programs import (
        make_split_optimizer,
        make_splitnn_client_backward,
        make_splitnn_client_forward,
        make_splitnn_eval,
        make_splitnn_fused_step,
        make_splitnn_server_step,
        merge_opt_state,
    )

    tracer = get_tracer()
    rows: dict = {}
    t0 = time.perf_counter()
    cfg = config
    lr = cfg.train.lr
    momentum = cfg.train.momentum
    wd = cfg.train.wd
    bs = int(cfg.data.batch_size)
    feat = tuple(np.asarray(data.client_x[0]).shape[1:])
    xdt = np.asarray(data.client_x[0]).dtype
    ydt = np.asarray(data.client_y[0]).dtype
    # params only drive shapes here — same init path as the transport
    k1, k2 = jax.random.split(jax.random.PRNGKey(cfg.seed))
    x0 = jnp.zeros((1,) + feat, jnp.float32)
    bp = jax.device_get(bottom.module.init(k1, x0)["params"])
    acts_sds = jax.eval_shape(
        lambda v, x: bottom.module.apply({"params": v}, x, train=False),
        bp,
        jax.ShapeDtypeStruct((bs,) + feat, jnp.float32),
    )
    tp = jax.device_get(
        top.module.init(k2, jnp.zeros((1,) + acts_sds.shape[1:]))["params"]
    )
    opt = make_split_optimizer(lr, momentum, wd)
    b_opt = jax.device_get(opt.init(bp))
    t_opt = jax.device_get(opt.init(tp))
    xb = np.zeros((bs,) + feat, xdt)
    yb = np.zeros((bs,), ydt)
    acts = np.zeros(acts_sds.shape, np.float32)
    with tracer.span("warmup", programs="splitfed"):
        _warm_one(
            rows,
            "split_forward",
            make_splitnn_client_forward(bottom),
            (bp, xb),
            tracer,
        )
        _warm_one(
            rows,
            "split_server_step",
            make_splitnn_server_step(top, lr, momentum, wd),
            (tp, t_opt, acts, yb),
            tracer,
        )
        _warm_one(
            rows,
            "split_backward",
            make_splitnn_client_backward(bottom, lr, momentum, wd),
            (bp, b_opt, xb, acts),
            tracer,
        )
        _warm_one(
            rows,
            "split_fused",
            make_splitnn_fused_step(bottom, top, lr=lr, momentum=momentum, wd=wd),
            (
                {"bottom": bp, "top": tp},
                merge_opt_state(opt, b_opt, t_opt, bp, tp),
                xb,
                yb,
            ),
            tracer,
        )
        _warm_one(
            rows,
            "split_eval",
            make_splitnn_eval(bottom, top),
            (bp, tp, xb, yb),
            tracer,
        )
    rows["compile/warmup_s"] = time.perf_counter() - t0
    if log_fn is not None:
        log_fn(dict(rows))
    return rows


def warmup_local_train(
    shared_train,
    config,
    data,
    global_vars,
    client_ids=None,
    log_fn: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Warm a transport federation's shared local-train program for every
    distinct shape class in the partition — the warmup *barrier* that
    lets ``deadline_s`` rounds start with compilation already paid
    instead of racing a cold compile, for EVERY round's cohort (the
    pre-PR-8 version only covered round 0's, so a later round whose
    client bucketed differently still raced a lazy compile against the
    deadline). ``client_ids`` restricts the enumeration (legacy round-0
    behavior); None — the default — derives the warmup set from the
    whole partition via :func:`partition_shape_classes`.

    Shape classes are derived exactly the way ``LocalTrainer._train``
    derives them (``stack_clients`` of one client at the configured
    batch/bucket settings), so the warmed signature matches the training
    dispatch byte-for-byte."""
    import jax
    import numpy as np

    from fedml_tpu.data.base import stack_clients

    tracer = get_tracer()
    rows: dict = {}
    t0 = time.perf_counter()
    if client_ids is None:
        client_ids = range(data.num_clients)
    client_ids = list(client_ids)
    counts = [len(data.client_y[int(cid)]) for cid in client_ids]
    classes, skipped = _classes_by_population(
        counts, config.data.batch_size, config.data.pad_bucket
    )
    if skipped:
        rows["compile/warm_classes_skipped"] = skipped
    with tracer.span("warmup", programs="local_train"):
        for (steps, bs), rep in classes:
            cid = int(client_ids[rep])
            batch = stack_clients(
                data,
                [cid],
                config.data.batch_size,
                seed=0,  # values are irrelevant — only shapes enter lower()
                pad_bucket=config.data.pad_bucket,
            )
            rng = jax.random.PRNGKey(0)
            _warm_one(
                rows,
                f"local_train_s{steps}b{bs}",
                shared_train,
                (
                    global_vars,
                    np.asarray(batch.x[0]),
                    np.asarray(batch.y[0]),
                    np.asarray(batch.mask[0]),
                    rng,
                ),
                tracer,
            )
    rows["compile/warmup_s"] = time.perf_counter() - t0
    if log_fn is not None:
        log_fn(dict(rows))
    return rows
