"""SCAFFOLD — stochastic controlled averaging (Karimireddy et al. 2020).

BEYOND the reference's inventory (it ships FedAvg/FedProx/FedOpt/FedNova;
SURVEY §2b) — included because it is the canonical answer to the client
-drift problem (on synthetic(1,1) FedAvg misses the accuracy target that
FedProx/FedOpt reach), and because it exercises the one capability the
other algorithms don't: PERSISTENT per-client state (SURVEY §7 names the
client-state store as a hard part).

Algorithm (Option II of the paper):
  server state: x (params), c (control variate, same tree)
  client i state: c_i (persists across rounds; zero-init)
  local step:   y ← y − lr·(∇f_i(y) + c − c_i)
  after K steps: c_i⁺ = c_i − c + (x − y)/(K·lr)
  server:       x ← x + η_g·mean(Δy_i),  c ← c + (|S|/N)·mean(Δc_i)

TPU-first shape: the per-client control variates live as ONE stacked
pytree of [N, ...] device arrays; a round gathers the sampled rows,
runs the lifted local trains (same vmap/scan client schedules as FedAvg),
and scatters the updated rows back — all inside one jitted round
function, no host round-trips. Memory cost is N × |params|, inherent to
SCAFFOLD (it is why the paper targets cross-silo N); past
FedConfig.state_budget_bytes the stack SPILLS to the disk tier
(state_store.MmapClientState, cohort rows only in HBM — bit-identical
math, tests/test_state_spill.py) instead of refusing.

Restriction: plain-SGD local steps only (the control-variate correction
is defined on the SGD update; momentum/Adam change the fixed point) —
mirrors FedNova's guard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import (
    FedAvgAPI,
    client_axis_map,
    resolve_client_parallelism,
)
from fedml_tpu.config import RunConfig
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import (
    make_mixed_forward,
    make_task_loss,
    masked_epoch_perm,
)


def make_scaffold_local_train(model: ModelDef, tc, epochs: int, task: str = "classification"):
    """Per-client SCAFFOLD local train:
    ``(variables, c_server, c_i, x, y, mask, rng) ->
      (y_vars, c_i_new, metrics)``
    with x [S, B, *feat]. The correction (c − c_i) is added to every
    gradient step; K (the c_i⁺ normalizer) counts the steps that carried
    data (all-padding steps are where-gated no-ops, as in FedAvg)."""
    if tc.client_optimizer != "sgd" or tc.momentum:
        raise ValueError(
            "SCAFFOLD requires plain-SGD local steps "
            f"(got {tc.client_optimizer!r}, momentum={tc.momentum})"
        )
    if tc.prox_mu:
        raise ValueError("SCAFFOLD with prox_mu is not supported")
    if tc.wd:
        # refusing beats silently training without the flag's effect: the
        # control-variate update is defined on the bare-SGD step
        raise ValueError("SCAFFOLD with weight decay (wd) is not supported")
    fwd = make_mixed_forward(model, tc)
    task_loss = make_task_loss(task)
    lr = tc.lr

    def local_train(variables, c_server, c_i, x, y, mask, rng):
        params0 = variables["params"]
        extra0 = {k: v for k, v in variables.items() if k != "params"}
        S, B = mask.shape[0], mask.shape[1]
        n_flat = S * B
        x_flat = x.reshape((n_flat,) + x.shape[2:])
        y_flat = y.reshape((n_flat,) + y.shape[2:])
        m_flat = mask.reshape((n_flat,))
        correction = jax.tree_util.tree_map(
            lambda cs, ci: cs - ci, c_server, c_i
        )

        def loss_fn(params, extra, xb, yb, mb, step_rng):
            logits, new_extra = fwd(params, extra, xb, step_rng)
            l, correct, total = task_loss(logits, yb, mb)
            return l, (new_extra, l, correct, total)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def epoch_body(carry, epoch_idx):
            params, extra, k_steps = carry
            ep_rng = jax.random.fold_in(rng, epoch_idx)
            perm = masked_epoch_perm(ep_rng, m_flat)
            xe = x_flat[perm].reshape(x.shape)
            ye = y_flat[perm].reshape(y.shape)
            me = m_flat[perm].reshape(mask.shape)

            def step_body(carry, inp):
                params, extra, k_steps = carry
                xb, yb, mb, sidx = inp
                has_data = jnp.sum(mb) > 0
                step_rng = jax.random.fold_in(ep_rng, sidx)
                (_, (new_extra, l, correct, total)), grads = grad_fn(
                    params, extra, xb, yb, mb, step_rng
                )
                new_params = jax.tree_util.tree_map(
                    lambda p, g, corr: p - lr * (g + corr),
                    params, grads, correction,
                )
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda n, o: jnp.where(has_data, n, o), new, old
                )
                h = has_data.astype(jnp.float32)
                mets = jnp.stack([l * total, correct, total, jnp.float32(1)]) * h
                return (
                    keep(new_params, params),
                    keep(new_extra, extra),
                    k_steps + h,
                ), mets

            (params, extra, k_steps), mets = jax.lax.scan(
                step_body, (params, extra, k_steps),
                (xe, ye, me, jnp.arange(S)),
            )
            return (params, extra, k_steps), mets.sum(axis=0)

        (params, extra, k_steps), mets = jax.lax.scan(
            epoch_body, (params0, extra0, jnp.float32(0)), jnp.arange(epochs)
        )
        mets = mets.sum(axis=0)
        # Option II: c_i⁺ = c_i − c + (x − y)/(K·lr); K = data-carrying steps
        k_safe = jnp.maximum(k_steps, 1.0)
        c_i_new = jax.tree_util.tree_map(
            lambda ci, cs, x0, yk: ci
            - cs
            + (x0.astype(jnp.float32) - yk.astype(jnp.float32))
            / (k_safe * lr),
            c_i, c_server, params0, params,
        )
        # a client with NO data leaves its control variate untouched
        had_data = k_steps > 0
        c_i_new = jax.tree_util.tree_map(
            lambda new, old: jnp.where(had_data, new, old), c_i_new, c_i
        )
        metrics = {
            "loss_sum": mets[0],
            "correct": mets[1],
            "count": mets[2],
            "steps": mets[3],
        }
        return {"params": params, **extra}, c_i_new, metrics

    return local_train


def make_scaffold_round(
    model: ModelDef,
    config: RunConfig,
    task: str = "classification",
    donate: bool = False,
    client_mode: str | None = None,
):
    """Jitted SCAFFOLD round:
    ``(global_vars, c_server, c_stack, idx, x, y, mask, ns, rngs) ->
      (global_vars', c_server', c_stack', agg_metrics)``
    where c_stack is the FULL [N, ...] per-client control-variate store
    (rows gathered/scattered inside the program — only the small index
    vector crosses the host boundary) and ns weights the Δy average as in
    FedAvg."""
    body = _make_scaffold_cohort_body(model, config, task, client_mode)

    def round_fn(global_vars, c_server, c_stack, idx, x, y, mask, num_samples, rngs):
        c_gather = jax.tree_util.tree_map(lambda a: a[idx], c_stack)
        new_global, c_server_new, c_new, agg = body(
            global_vars, c_server, c_gather, x, y, mask, num_samples, rngs
        )
        c_stack_new = jax.tree_util.tree_map(
            lambda stack, new: stack.at[idx].set(new), c_stack, c_new
        )
        return new_global, c_server_new, c_stack_new, agg

    # program dedup (fedml_tpu/compile/): one jitted SCAFFOLD round per
    # (model, train config, epochs, task, schedule) per process
    from fedml_tpu.compile import get_program_cache, model_fingerprint

    return get_program_cache().get_or_build(
        "scaffold_round",
        {
            "kind": "scaffold_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            # client_mode=None resolves inside the body from this config
            # field — both enter the key so "vmap" and "scan" programs
            # can never merge
            "mode": client_mode,
            "parallelism": config.fed.client_parallelism,
            # the cohort body BAKES IN the server lr (η_g) and the /N of
            # the c-server update — they are program constants, not shape
            # classes, and merging across them is wrong numerics
            "server": config.server,
            "n_total": config.fed.client_num_in_total,
            "donate": donate,
        },
        lambda: jax.jit(round_fn, donate_argnums=(2,) if donate else ()),
    )


def _make_scaffold_cohort_body(model, config, task, client_mode):
    """THE cohort-level SCAFFOLD server math — one definition shared by
    the full-stack round (which wraps it with the in-program idx
    gather/scatter) and the spilled cohort round (which jits it bare), so
    the two can never drift and spilled == in-HBM holds by construction
    (tests/test_state_spill.py)."""
    local_train = make_scaffold_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    eta_g = config.server.server_lr  # paper's η_g; ServerConfig default 1.0
    n_total = config.fed.client_num_in_total
    # same client schedules as FedAvg (vmap for small models, sequential
    # scan for conv models whose per-client weights would under-tile the
    # MXU as grouped convs); global_vars and c_server broadcast
    mode = client_mode or resolve_client_parallelism(
        config.fed.client_parallelism, model
    )
    lifted = client_axis_map(local_train, mode, n_broadcast=2)

    def body(global_vars, c_server, c_rows, x, y, mask, num_samples, rngs):
        y_vars, c_new, metrics = lifted(
            global_vars, c_server, c_rows, x, y, mask, rngs
        )

        w = num_samples / jnp.maximum(jnp.sum(num_samples), 1e-9)
        # x ← x + η_g · Σ w_i Δy_i   (params through the control update;
        # non-param collections are plain weighted averages, as in FedAvg)
        def avg_delta(stacked, g):
            return jnp.tensordot(
                w, stacked.astype(jnp.float32) - g.astype(jnp.float32)[None],
                axes=1,
            )

        new_params = jax.tree_util.tree_map(
            lambda g, s: (g.astype(jnp.float32) + eta_g * avg_delta(s, g)).astype(g.dtype),
            global_vars["params"], y_vars["params"],
        )
        new_global = {
            k: (
                new_params
                if k == "params"
                else jax.tree_util.tree_map(
                    lambda s: jnp.tensordot(w, s.astype(jnp.float32), axes=1),
                    v,
                )
            )
            for k, v in y_vars.items()
        }
        # c ← c + (|S|/N) · mean Δc_i  (uniform mean, per the paper).
        # |S| and the mean are derived from the inclusion mask, not the
        # array axis: (|S|/N)·mean over REAL rows ≡ Σ_incl Δc_i / N, so a
        # padded cohort (num_samples == 0 dummy rows, pad_clients_to's
        # contract) cannot inflate |S| or deflate the update — advisor r4.
        incl = (num_samples > 0).astype(jnp.float32)
        c_server_new = jax.tree_util.tree_map(
            lambda cs, new, old: cs
            + jnp.tensordot(incl, new - old, axes=1) / n_total,
            c_server, c_new, c_rows,
        )
        agg = jax.tree_util.tree_map(jnp.sum, metrics)
        return new_global, c_server_new, c_new, agg

    return body


def make_scaffold_cohort_round(
    model: ModelDef,
    config: RunConfig,
    task: str = "classification",
    client_mode: str | None = None,
):
    """Cohort-form SCAFFOLD round for the SPILLED state store:
    ``(global_vars, c_server, c_rows, x, y, mask, ns, rngs) ->
      (global_vars', c_server', c_rows', agg_metrics)``
    — :func:`make_scaffold_round` with the [N, ...] stack gather/scatter
    moved out to the host store (state_store.MmapClientState); only the
    cohort's [C, ...] control rows enter HBM. The in-program math after
    the gather is the same code, so a spilled run bit-matches the in-HBM
    run (pinned in tests/test_state_spill.py)."""
    from fedml_tpu.compile import get_program_cache, model_fingerprint

    # donate the cohort rows (argnum 2): the host store keeps the durable
    # copy; the device rows are consumed by the round. Same digest shape
    # as make_scaffold_round: eta_g and 1/N are baked program constants.
    return get_program_cache().get_or_build(
        "scaffold_cohort_round",
        {
            "kind": "scaffold_cohort_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "mode": client_mode,
            "parallelism": config.fed.client_parallelism,
            "server": config.server,
            "n_total": config.fed.client_num_in_total,
        },
        lambda: jax.jit(
            _make_scaffold_cohort_body(model, config, task, client_mode),
            donate_argnums=(2,),
        ),
    )


def make_sharded_scaffold_cohort_round(
    model: ModelDef, config: RunConfig, mesh, task: str = "classification"
):
    """Cohort-form SCAFFOLD round over a client-sharded mesh — the
    composition of the two scale stories: the 100k-client spilled
    state tier and the multi-chip runtime in one round.

    ``(global_vars, c_server, c_rows, x, y, mask, ns, rngs) ->
      (global_vars', c_server', c_rows', agg_metrics)``
    where ``c_rows`` arrives SHARDED over the client axis (the host store
    gathered only the cohort — O(|S|·params) of disk IO and HBM, never
    the [N, ...] stack) and the updated rows leave sharded the same way
    for the host scatter. The server math matches
    :func:`_make_scaffold_cohort_body` exactly, with psums where the
    single-chip body reduces locally: Δy via the weighted psum, c-server
    via psum over the inclusion-masked row deltas / N (padded dummy rows
    carry num_samples == 0 AND exact-zero deltas). A spilled mesh run
    therefore matches the spilled single-chip run to float tolerance —
    pinned in tests/test_state_spill.py."""
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    mode = resolve_client_parallelism(config.fed.client_parallelism, model)
    local_train = make_scaffold_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    lifted = client_axis_map(local_train, mode, n_broadcast=2)
    eta_g = config.server.server_lr
    n_total = config.fed.client_num_in_total

    def shard_body(global_vars, c_server, c_rows, x, y, mask, num_samples, rngs):
        varying = lambda t: jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), t
        )
        gv = varying(global_vars)
        cs = varying(c_server)
        y_vars, c_new, metrics = lifted(gv, cs, c_rows, x, y, mask, rngs)

        wsum = jax.lax.psum(jnp.sum(num_samples), axis)
        w = num_samples / jnp.maximum(wsum, 1e-9)

        def psum_avg_delta(stacked, g):
            return jax.lax.psum(
                jnp.tensordot(
                    w,
                    stacked.astype(jnp.float32) - g.astype(jnp.float32)[None],
                    axes=1,
                ),
                axis,
            )

        new_params = jax.tree_util.tree_map(
            lambda g, s: (
                g.astype(jnp.float32) + eta_g * psum_avg_delta(s, g)
            ).astype(g.dtype),
            gv["params"], y_vars["params"],
        )
        new_global = {
            k: (
                new_params
                if k == "params"
                else jax.tree_util.tree_map(
                    lambda s: jax.lax.psum(
                        jnp.tensordot(w, s.astype(jnp.float32), axes=1), axis
                    ),
                    v,
                )
            )
            for k, v in y_vars.items()
        }
        # c ← c + Σ_incl Δc_i / N — the single-chip cohort body's masked
        # sum, psum'd across shards
        incl = (num_samples > 0).astype(jnp.float32)
        c_server_new = jax.tree_util.tree_map(
            lambda c, new, old: c + jax.lax.psum(
                jnp.tensordot(incl, new - old, axes=1), axis
            ) / n_total,
            cs, c_new, c_rows,
        )
        agg = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(jnp.sum(m), axis), metrics
        )
        return new_global, c_server_new, c_new, agg

    data_spec = P(axis)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        # (gv, c_server) replicated; (c_rows, x, y, mask, ns, rngs) sharded
        in_specs=(P(), P()) + (data_spec,) * 6,
        # rows leave sharded — the host scatter reads the real prefix
        out_specs=(P(), P(), data_spec, P()),
        check_vma=False,  # same stance as make_sharded_scaffold_round
    )
    from fedml_tpu.compile import (
        get_program_cache,
        mesh_fingerprint,
        model_fingerprint,
    )

    return get_program_cache().get_or_build(
        "sharded_scaffold_cohort_round",
        {
            "kind": "sharded_scaffold_cohort_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "parallelism": config.fed.client_parallelism,
            "server": config.server,
            "n_total": config.fed.client_num_in_total,
            "mesh": mesh_fingerprint(mesh),
        },
        lambda: jax.jit(sharded, donate_argnums=(2,)),
    )


def make_sharded_scaffold_round(model: ModelDef, config: RunConfig, mesh, task: str = "classification", donate: bool = True):
    """SCAFFOLD round over a client-sharded mesh (the reference has no
    distributed SCAFFOLD at all — this is the shard_map form of the vmap
    round above, same signature).

    Sharding layout: the per-client control store ``c_stack`` stays
    REPLICATED (cross-silo N × |params| fits every chip — SCAFFOLD's own
    regime) while the sampled cohort's data and index vector shard over
    the client axis. Each shard gathers its own clients' rows locally,
    trains, and contributes:
    - Δy via the same weighted psum as sharded FedAvg;
    - the cohort's (idx, Δc) rows via ``all_gather`` — O(|S|·params)
      over ICI, NOT an O(N·params) zeros-scattered stack psum — followed
      by one in-place ``.at[idx_all].add`` on the replicated store.
      Dummy padding clients train on all-zero masks, end with
      c_i⁺ == c_i, and therefore contribute exact zeros.
    c ← c + Σ Δc / N  (≡ the paper's (|S|/N)·mean over the real cohort,
    with padded rows vanishing)."""
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    mode = resolve_client_parallelism(config.fed.client_parallelism, model)
    local_train = make_scaffold_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    lifted = client_axis_map(local_train, mode, n_broadcast=2)
    eta_g = config.server.server_lr
    n_total = config.fed.client_num_in_total

    def shard_body(global_vars, c_server, c_stack, idx, x, y, mask, num_samples, rngs):
        varying = lambda t: jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), t
        )
        gv = varying(global_vars)
        cs = varying(c_server)
        stack = varying(c_stack)
        c_gather = jax.tree_util.tree_map(lambda a: a[idx], stack)
        y_vars, c_new, metrics = lifted(gv, cs, c_gather, x, y, mask, rngs)

        wsum = jax.lax.psum(jnp.sum(num_samples), axis)
        w = num_samples / jnp.maximum(wsum, 1e-9)

        def psum_avg_delta(stacked, g):
            return jax.lax.psum(
                jnp.tensordot(
                    w,
                    stacked.astype(jnp.float32) - g.astype(jnp.float32)[None],
                    axes=1,
                ),
                axis,
            )

        new_params = jax.tree_util.tree_map(
            lambda g, s: (
                g.astype(jnp.float32) + eta_g * psum_avg_delta(s, g)
            ).astype(g.dtype),
            gv["params"], y_vars["params"],
        )
        new_global = {
            k: (
                new_params
                if k == "params"
                else jax.tree_util.tree_map(
                    lambda s: jax.lax.psum(
                        jnp.tensordot(w, s.astype(jnp.float32), axes=1), axis
                    ),
                    v,
                )
            )
            for k, v in y_vars.items()
        }
        # Row updates travel as the gathered COHORT deltas (O(|S|·params)
        # over ICI), not a zeros-scattered full stack (O(N·params) psum +
        # a second full-stack temporary per shard — pathological when the
        # population is much larger than the cohort).
        delta = jax.tree_util.tree_map(
            lambda new, old: new - old, c_new, c_gather
        )
        idx_all = jax.lax.all_gather(idx, axis, tiled=True)
        delta_all = jax.tree_util.tree_map(
            lambda d: jax.lax.all_gather(d, axis, tiled=True), delta
        )
        # c ← c + Σ Δc / N (dummy padding rows are exact zeros)
        c_server_new = jax.tree_util.tree_map(
            lambda c, d: c + jnp.sum(d, axis=0) / n_total, cs, delta_all
        )
        c_stack_new = jax.tree_util.tree_map(
            lambda stack_l, d: stack_l.at[idx_all].add(d), stack, delta_all
        )
        agg = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(jnp.sum(m), axis), metrics
        )
        return new_global, c_server_new, c_stack_new, agg

    data_spec = P(axis)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(), P()) + (data_spec,) * 6,
        out_specs=(P(), P(), P(), P()),
        # every output is a psum-combined value, replicated by construction;
        # the custom-VJP norm ops inside local_train defeat static VMA
        # inference (same situation as parallel/long_context.py) — the
        # mesh-invariance test pins sharded == single-chip bitwise-close
        check_vma=False,
    )
    from fedml_tpu.compile import (
        get_program_cache,
        mesh_fingerprint,
        model_fingerprint,
    )

    return get_program_cache().get_or_build(
        "sharded_scaffold_round",
        {
            "kind": "sharded_scaffold_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "parallelism": config.fed.client_parallelism,
            "server": config.server,
            "n_total": config.fed.client_num_in_total,
            "mesh": mesh_fingerprint(mesh),
            "donate": donate,
        },
        lambda: jax.jit(sharded, donate_argnums=(2,) if donate else ()),
    )


class ScaffoldAPI(FedAvgAPI):
    """SCAFFOLD simulator on the FedAvg skeleton — adds the server control
    variate and the per-client control store. The store lives in HBM as a
    stacked [N, ...] pytree while it fits FedConfig.state_budget_bytes and
    SPILLS to the disk tier beyond it (state_store.MmapClientState —
    cohort rows only ride to device; round 3 refused instead)."""

    def __init__(self, config: RunConfig, data: FederatedDataset, model: ModelDef, **kw):
        super().__init__(config, data, model, **kw)
        from fedml_tpu.algorithms.state_store import (
            make_spill_store,
            resolve_state_store,
        )

        params = self.global_vars["params"]
        n = config.fed.client_num_in_total
        psize = sum(
            int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
        )
        zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
        self.c_server = jax.tree_util.tree_map(zeros32, params)
        self._state_mode = resolve_state_store(
            config.fed, 4 * psize * n, n_clients=n,
            population=getattr(config, "population", None),
        )
        if self._state_mode == "device":
            self.c_stack = jax.tree_util.tree_map(
                lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params
            )
            self._scaffold_round = self._build_scaffold_round()
        else:
            from fedml_tpu.algorithms.state_store import CohortPrefetcher

            self.c_stack = None
            self._c_store = make_spill_store(
                self._state_mode,
                jax.tree_util.tree_map(
                    lambda p: np.zeros(p.shape, np.float32), params
                ),
                n,
                config.fed.state_dir or None,
                population=getattr(config, "population", None),
            )
            # overlap the NEXT cohort's disk gather with the current
            # round's device compute (the recorded spill tax was 3.1x;
            # the gather is the front half of it)
            self._c_prefetch = CohortPrefetcher(self._c_store)
            self._scaffold_round = self._build_scaffold_cohort_round()

    def _build_scaffold_cohort_round(self):
        """Jitted cohort-form round for the SPILLED store. The mesh
        subclass swaps in the shard_map form — spill and multi-chip
        compose (round 4 refused here)."""
        return make_scaffold_cohort_round(
            self.model, self.config, task=self.task,
            client_mode=self._client_mode,
        )

    def _build_scaffold_round(self):
        # donate the c_stack (argnum 2): train_round keeps no alias to the
        # pre-round stack, and without donation every round would hold TWO
        # full N×|params| copies while .at[idx].set builds the new one —
        # exactly the thrashing the state budget exists to prevent
        return make_scaffold_round(
            self.model, self.config, task=self.task, donate=True,
            client_mode=self._client_mode,
        )

    def _place_client_indices(self, sampled):
        """The sampled client ids as the round fn's gather/scatter index
        vector — the sharded subclass pads to the mesh and shards it."""
        return jnp.asarray(np.asarray(sampled, np.int32))

    def _build_round_fn(self, local_train_fn):
        return None  # unused — train_round is fully overridden

    def checkpoint_state(self):
        """Control-variate state for checkpoint/resume — without this a
        resumed run would silently restart c/c_i at zero and degenerate
        to FedAvg until the variates re-learn. Spilled-store checkpoints
        embed the TOUCHED ROWS themselves (self-contained npz — a mere
        path to the live directory would roll forward as training
        continues and dangle after a tmp-cleaner pass); either
        representation restores into either store mode."""
        if self._state_mode == "device":
            return {"c_server": self.c_server, "c_stack": self.c_stack}
        # self-contained: the touched rows ARE the store's whole
        # information content (untouched rows gather as zeros), so the
        # checkpoint survives tmp-cleaners and never references the live
        # (still-mutating) directory
        self._c_store.flush()  # checkpoint == durability point for the spill tier
        idx = self._c_store.initialized_ids()
        rows = self._c_store.gather(idx)
        out = {"c_server": self.c_server, "c_rows_idx": idx}
        for i, leaf in enumerate(jax.tree_util.tree_leaves(rows)):
            out[f"c_rows_{i}"] = leaf
        return out

    def restore_state(self, tree):
        from fedml_tpu.utils.checkpoint import restore_like

        if self._state_mode != "device":
            # a pending prefetch holds PRE-restore rows; drop it (and let
            # any in-flight read finish before reset_to rewrites the store)
            self._c_prefetch.cancel()
        self.c_server = restore_like(self.c_server, tree["c_server"])
        n = self.config.fed.client_num_in_total
        zeros_stack = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros((n,) + p.shape, jnp.float32),
            self.global_vars["params"],
        )
        if "c_stack" in tree:
            if self._state_mode == "device":
                self.c_stack = restore_like(self.c_stack, tree["c_stack"])
            else:
                # a device-mode checkpoint restores into a spilled run
                stack = restore_like(zeros_stack(), tree["c_stack"])
                self._c_store.reset_to(np.arange(n), jax.device_get(stack))
        else:
            idx = np.asarray(tree["c_rows_idx"])
            leaves, treedef = jax.tree_util.tree_flatten(
                self.global_vars["params"]
            )
            rows = jax.tree_util.tree_unflatten(
                treedef,
                [np.asarray(tree[f"c_rows_{i}"]) for i in range(len(leaves))],
            )
            if self._state_mode == "device":
                # a spilled checkpoint restores into a device-mode run
                self.c_stack = jax.tree_util.tree_map(
                    lambda s, r: s.at[jnp.asarray(idx)].set(jnp.asarray(r)),
                    zeros_stack(),
                    rows,
                )
            else:
                self._c_store.reset_to(idx, rows)

    def train_round(self, round_idx: int):
        sampled, _steps, _bs = self._round_plan(round_idx)
        # batch via the shared warmup/pipeline stash contract — a
        # pipelined run pops the batch the host prepared during the
        # previous round's device execution (byte-identical by the
        # determinism contract, fedavg._round_placed)
        placed = self._round_placed(round_idx, sampled)
        if self._state_mode == "device":
            (
                self.global_vars,
                self.c_server,
                self.c_stack,
                metrics,
            ) = self._scaffold_round(
                self.global_vars,
                self.c_server,
                self.c_stack,
                self._place_client_indices(sampled),
                *placed,
            )
            return sampled, metrics
        # spilled store: host-gather the cohort's control rows (prefetched
        # last round when possible), run the cohort-form round, scatter
        # the updated rows back to disk
        ids, n_real = self._spill_pad_ids(sampled)
        c_rows = self._place_cohort_rows(self._c_prefetch.take(round_idx, ids))
        (
            self.global_vars,
            self.c_server,
            new_rows,
            metrics,
        ) = self._scaffold_round(
            self.global_vars,
            self.c_server,
            c_rows,
            *placed,
        )
        # the round is dispatched async: start reading the NEXT cohort's
        # rows off disk while the device computes this one. Rows being
        # scattered below are excluded from the background read and
        # re-fetched synchronously at the next take() — no torn rows.
        if round_idx + 1 < self.config.fed.comm_round:
            nxt_ids, _ = self._spill_pad_ids(self._round_plan(round_idx + 1)[0])
            self._c_prefetch.launch(
                round_idx + 1, nxt_ids,
                exclude=set(int(i) for i in np.asarray(sampled)),
            )
        host_rows = jax.device_get(new_rows)
        self._c_store.scatter(
            np.asarray(sampled),
            jax.tree_util.tree_map(lambda r: r[:n_real], host_rows),
        )
        return sampled, metrics
