"""DP-FedAvg — client-level differential privacy with a real ledger.

The reference ships "weak DP" (norm clipping + an arbitrary noise stddev,
fedml_core/robustness/robust_aggregation.py:38-55) as a backdoor DEFENSE;
it never says — or knows — what (epsilon, delta) it provides. This module
implements the DP-FedAvg recipe (McMahan et al., "Learning Differentially
Private Recurrent Language Models" — public algorithm, fresh
implementation) on the same round-hook skeleton the robust defenses use:

  1. the round's cohort is POISSON-sampled: every client independently
     with probability q = m_hat/N, from a per-round PRNG seeded by a
     128-bit OS-entropy secret drawn at API construction
     (np.random.SeedSequence), NOT the round index alone and NOT
     config.seed — a round-seeded or default-seeded draw would be
     publicly predictable, which voids amplification-by-subsampling
     (the adversary must not know who participated). The secret rides
     in checkpoint_state so a resume continues the same stream;
  2. each sampled client's UPDATE delta_i = w_i - w_t is clipped to L2
     norm S over the ENTIRE uploaded tree (params and any stats — the
     guarantee must cover everything transmitted, so unlike the robust
     defense's BN-stat-aware clipping nothing passes through unclipped);
  3. aggregation is w_t + (1/m_hat) * sum_{i in cohort} clip_S(delta_i)
     with the FIXED expected cohort size m_hat = qN as denominator (the
     DP-FedAvg fixed-denominator estimator): the sum's sensitivity to
     adding/removing one client is exactly S regardless of the realized
     cohort, and sample-count weighting is deliberately NOT used —
     weights would make the sensitivity depend on private shard sizes;
  4. Gaussian noise N(0, (z*S/m_hat)^2) is added to every coordinate
     (noise z*S on the sum => noise multiplier z, the accounted value);
  5. an RDP accountant (privacy/accountant.py) composes the rounds. The
     executed sampler and the accounted mechanism are the SAME object:
     Poisson(q) sampling, sum-sensitivity S, noise z*S.

Variable Poisson cohorts meet XLA's static shapes by padding the client
axis to a bucketed size with all-mask-zero dummy clients: their local
step is a gated no-op (delta exactly 0, pinned by tests) AND the
aggregate excludes them explicitly (num_samples == 0), so padding never
changes the mechanism. 2-4 run inside the one jitted round function via
the post_train/aggregate_fn/post_aggregate hooks of make_fedavg_round —
the DP math adds no host round-trips.
"""

from __future__ import annotations

import dataclasses
import secrets
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, make_fedavg_round
from fedml_tpu.algorithms.fedavg_robust import NOISE_FOLD
from fedml_tpu.data.base import ClientBatch, pad_clients_to, size_class
from fedml_tpu.privacy.accountant import RdpAccountant

# Domain tag folded into the cohort-sampling SeedSequence so the DP
# participation stream can never collide with any other consumer of the
# run seed (data shuffling uses seed*1_000_003+round, model init folds 0).
_DP_SAMPLE_TAG = 0x44505F53  # "DP_S"


def poisson_client_sampling(
    run_seed: int, round_idx: int, client_num_in_total: int, q: float
) -> np.ndarray:
    """One Poisson cohort draw: every client independently with probability
    ``q``, from a fresh per-round stream derived from ``run_seed`` — which
    the API feeds from a 128-bit OS-entropy secret (``fresh_sample_secret``),
    never from ``config.seed``.

    This is the sampler the RDP accountant's subsampled-Gaussian bound is
    FOR — and unlike :func:`fedavg.client_sampling`'s round-seeded draw
    (reference parity, FedAVGAggregator.py:80-88) it is not predictable
    from public information alone: amplification by subsampling requires
    the adversary not to know who participated, so the stream's seed must
    be secret AND high-entropy for the epsilon to hold."""
    if not 0.0 < q <= 1.0:
        raise ValueError(f"sampling probability q must be in (0, 1], got {q}")
    rng = np.random.default_rng(
        np.random.SeedSequence((int(run_seed), _DP_SAMPLE_TAG, int(round_idx)))
    )
    return np.flatnonzero(rng.random(client_num_in_total) < q)


def bucket_cohort(m: int) -> int:
    """Static client-axis size for a realized Poisson cohort of ``m`` —
    the shared size-class policy (data/base.size_class), so the set of
    compiled shapes stays small while padding waste is bounded."""
    return size_class(max(int(m), 1))


@dataclasses.dataclass(frozen=True)
class DpConfig:
    """Client-level DP-FedAvg knobs."""

    clip_norm: float = 1.0  # S: per-client update L2 bound
    noise_multiplier: float = 1.0  # z: noise stddev in units of S (on the sum)
    delta: float = 1e-5  # the delta at which epsilon is reported
    # Secret seeding the Poisson participation stream. None (the default)
    # draws 128 bits from OS entropy at API construction — the epsilon
    # claim requires the adversary not to predict who participated, and
    # config.seed is a public, low-entropy, reused value (data shuffling
    # and the broadcast w_0 both derive from it), so it must never seed
    # the cohorts. Pass an explicit value ONLY for tests/repro; anything
    # under 64 bits warns that amplification-by-subsampling is void.
    sample_secret: int | None = None


def fresh_sample_secret() -> int:
    """128 bits of OS entropy for the DP participation stream."""
    return secrets.randbits(128)


def _secret_to_words(secret: int, n_words: int = 8) -> np.ndarray:
    """Secret int -> uint32 word array (little-endian). uint32 because the
    words may ride through jax collectives (multi-host broadcast), where
    64-bit ints are silently truncated to 32 bits with x64 disabled — the
    one encoding shared by checkpointing and broadcast so a truncating
    variant can't creep in."""
    if secret.bit_length() > 32 * n_words:
        raise ValueError(f"secret exceeds {32 * n_words} bits")
    return np.asarray(
        [(secret >> (32 * i)) & 0xFFFFFFFF for i in range(n_words)], np.uint32
    )


def _words_to_secret(words) -> int:
    """Inverse of :func:`_secret_to_words`; decodes by the array's actual
    word width rather than assuming 32 bits (defensive — a checkpoint
    edited or produced by other tooling stays restorable)."""
    words = np.asarray(words)
    bits = words.dtype.itemsize * 8
    return sum(int(w) << (bits * i) for i, w in enumerate(words.tolist()))


def clip_update_tree(local_tree, global_tree, clip_norm: float):
    """w_t + clip_S(w_l - w_t) with the L2 norm taken over EVERY leaf of
    the update (full-tree sensitivity — see module docstring)."""
    sq = sum(
        jnp.sum(jnp.square((l - g).astype(jnp.float32)))
        for l, g in zip(
            jax.tree_util.tree_leaves(local_tree),
            jax.tree_util.tree_leaves(global_tree),
        )
    )
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(
        lambda l, g: (g + (l - g) * scale).astype(l.dtype), local_tree, global_tree
    )


def make_dp_hooks(dp: DpConfig, expected_cohort: int):
    """(post_train, aggregate_fn, post_aggregate) for make_fedavg_round /
    make_sharded_fedavg_round.

    The aggregate is the fixed-denominator estimator
    ``w_t + (1/m_hat) * sum_incl clip_S(delta_i)`` with ``m_hat =
    expected_cohort``: sensitivity of the sum is exactly clip_norm under
    add/remove adjacency whatever the realized Poisson cohort, so the
    noise z*S/m_hat on the result is the accounted subsampled-Gaussian
    mechanism. Padding rows (num_samples == 0) are excluded by the
    inclusion mask — and contribute exact-zero deltas anyway (gated no-op
    local steps). num_samples is used ONLY as the inclusion indicator,
    never as a weight (weights would tie sensitivity to private shard
    sizes)."""

    def post_train(client_vars, global_vars, noise_rng):
        return jax.vmap(
            lambda cv: clip_update_tree(cv, global_vars, dp.clip_norm)
        )(client_vars)

    def aggregate_fn(client_vars, num_samples, g):
        incl = (num_samples > 0).astype(jnp.float32)

        def mean_delta(s, gl):
            base = gl.astype(jnp.float32)
            delta = s.astype(jnp.float32) - base[None]
            return base + jnp.tensordot(incl, delta, axes=1) / float(
                expected_cohort
            )

        return jax.tree_util.tree_map(mean_delta, client_vars, g)

    stddev = dp.noise_multiplier * dp.clip_norm / expected_cohort

    def post_aggregate(new_global, noise_rng):
        flat, treedef = jax.tree_util.tree_flatten(new_global)
        rngs = jax.random.split(noise_rng, len(flat))
        noised = [
            leaf + jax.random.normal(r, leaf.shape, jnp.float32) * stddev
            for r, leaf in zip(rngs, flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, noised)

    return post_train, aggregate_fn, post_aggregate


class DPFedAvgAPI(FedAvgAPI):
    """FedAvg simulator with client-level DP and per-round accounting.

    ``client_num_per_round`` is reinterpreted as the EXPECTED cohort size
    m_hat: cohorts are Poisson(q = m_hat/N) draws (see
    :func:`poisson_client_sampling`), padded to a bucketed static client
    axis so realized sizes don't multiply compiled shapes."""

    sampling = "poisson"

    def __init__(self, config, data, model, dp: DpConfig = DpConfig(), **kw):
        self.dp = dp
        super().__init__(config, data, model, **kw)
        # The participation stream's seed is OS entropy, NOT config.seed:
        # config.seed is public/low-entropy (defaults to 0, reused by data
        # shuffling and the broadcast init), so cohorts derived from it are
        # predictable and the accountant's amplification-by-subsampling
        # claim is void (advisor r4, medium). An explicit dp.sample_secret
        # is honored for tests/repro and resume, with a warning when it is
        # too small to be credible entropy.
        if dp.sample_secret is None:
            self._sample_secret = fresh_sample_secret()
            self._secret_provenance = "128-bit OS entropy"
            if jax.process_count() > 1:
                # every process must draw the SAME cohorts (mismatched
                # cohort shapes would wedge the SPMD round's collectives):
                # process 0's draw wins, broadcast as uint32 words (jax
                # would silently truncate 64-bit words with x64 disabled)
                from jax.experimental import multihost_utils

                self._sample_secret = _words_to_secret(
                    np.asarray(
                        multihost_utils.broadcast_one_to_all(
                            _secret_to_words(self._sample_secret)
                        )
                    ).astype(np.uint32)
                )
        else:
            self._sample_secret = int(dp.sample_secret)
            if self._sample_secret < 0:
                raise ValueError(
                    "DpConfig.sample_secret must be a non-negative integer "
                    f"(got {self._sample_secret}); SeedSequence rejects "
                    "negative entropy"
                )
            if self._sample_secret.bit_length() > 256:
                # checkpoint_state serializes the secret into 8 uint32
                # words — reject at construction, not mid-run at the
                # first checkpoint
                raise ValueError(
                    "DpConfig.sample_secret wider than 256 bits cannot be "
                    "checkpointed; 128 bits is already full strength"
                )
            self._secret_provenance = (
                f"explicit DpConfig.sample_secret "
                f"({self._sample_secret.bit_length()} bits — amplification "
                "holds only if this value is secret and high-entropy)"
            )
            if self._sample_secret.bit_length() < 64:
                warnings.warn(
                    "DpConfig.sample_secret has <64 bits of entropy: the "
                    "Poisson cohorts are predictable and the reported "
                    "epsilon's amplification-by-subsampling does not hold. "
                    "Use this only for tests/reproduction.",
                    stacklevel=2,
                )
        self.accountant = RdpAccountant()
        # N from the DATA (the population actually sampled from), not the
        # config echo — the accounted q and the executed q must be the
        # same number
        self._q = config.fed.client_num_per_round / data.num_clients
        if not 0.0 < self._q <= 1.0:
            raise ValueError(
                f"fed.client_num_per_round={config.fed.client_num_per_round} "
                f"with {data.num_clients} clients gives DP sampling "
                f"probability q={self._q:.4g}; need 0 < q <= 1"
            )

    def _sample_clients(self, round_idx: int) -> np.ndarray:
        # the SAME q the accountant steps with — mechanism == ledger
        return poisson_client_sampling(
            self._sample_secret, round_idx, self.data.num_clients, self._q
        )

    def _round_batch(self, sampled, round_idx: int):
        m = len(sampled)
        if m == 0:
            # an empty Poisson cohort is a legal round: the model moves by
            # noise only. Build an all-masked zero batch at the SAME shape
            # class _round_plan advertised (bucket_steps([1]) — one
            # notional sample), so plan and executed shapes agree and the
            # dead compute is one tiny gated no-op step, not a full
            # client's worth.
            _, steps, bs = self._round_plan(round_idx)
            feat = self.data.client_x[0].shape[1:]
            lab = self.data.client_y[0].shape[1:]
            batch = ClientBatch(
                x=np.zeros((1, steps, bs) + feat, self.data.client_x[0].dtype),
                y=np.zeros((1, steps, bs) + lab, self.data.client_y[0].dtype),
                mask=np.zeros((1, steps, bs), np.float32),
                num_samples=np.zeros((1,), np.float32),
            )
        else:
            batch = super()._round_batch(sampled, round_idx)
        return pad_clients_to(batch, bucket_cohort(m))

    def _round_may_pad(self, round_idx: int) -> bool:
        sampled = self._round_plan(round_idx)[0]
        m = len(sampled)
        if m == 0 or bucket_cohort(m) > m:
            return True  # dummy cohort rows are all-padding steps
        return super()._round_may_pad(round_idx)

    def _build_round_fn(self, local_train_fn):
        post_train, aggregate_fn, post_aggregate = make_dp_hooks(
            self.dp, self.config.fed.client_num_per_round
        )
        return make_fedavg_round(
            self.model,
            self.config,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
            post_train=post_train,
            aggregate_fn=aggregate_fn,
            post_aggregate=post_aggregate,
        )

    def _place_batch(self, batch, round_rng):
        base = super()._place_batch(batch, round_rng)
        return base + (jax.random.fold_in(round_rng, NOISE_FOLD),)

    def train_round(self, round_idx: int):
        out = super().train_round(round_idx)
        self.accountant.step(self._q, self.dp.noise_multiplier)
        return out

    def checkpoint_state(self):
        """The RDP ledger is round state: a resume that dropped it would
        report the epsilon of the post-crash rounds only — under-claiming
        the true privacy cost of everything already released."""
        import numpy as np

        # the sampling secret rides along (as uint32 words — it exceeds
        # int64): a resume that re-drew it would fork the participation
        # stream mid-ledger, decoupling the executed mechanism from the
        # accounted one for the remaining rounds. DISCLOSURE: a checkpoint
        # carrying dp_sample_secret reveals the whole participation stream
        # to anyone who reads it — checkpoints of DP runs are secrets
        # themselves and must not be published while the epsilon claim is
        # supposed to hold against recipients of the artifact
        return {
            "dp_rdp": np.asarray(self.accountant._rdp, np.float64),
            "dp_rounds": np.asarray(self.accountant.rounds, np.int64),
            "dp_sample_secret": _secret_to_words(self._sample_secret),
        }

    def restore_state(self, tree):
        import numpy as np

        self.accountant._rdp = [float(v) for v in np.asarray(tree["dp_rdp"])]
        self.accountant.rounds = int(np.asarray(tree["dp_rounds"]))
        if "dp_sample_secret" in tree:
            secret = _words_to_secret(tree["dp_sample_secret"])
            if secret != self._sample_secret:
                # whatever was planned before the restore (a warm-up, a
                # ``round_program`` probe) drew its cohorts from the
                # discarded secret: running those plans would decouple the
                # executed cohorts from the accounted participation stream
                self._round_plans.clear()
                self._may_pad_cache.clear()
                self._warm_placed.clear()
            self._sample_secret = secret
        else:
            warnings.warn(
                "checkpoint predates dp_sample_secret: it was written by a "
                "build whose cohorts derived from the public config.seed "
                "(amplification-by-subsampling did not hold for those "
                "rounds). The participation stream forks here — continuing "
                "with this API's constructed secret (fresh OS entropy "
                "unless DpConfig.sample_secret was set); the ledger's "
                "epsilon is honest only from this round on.",
                stacklevel=2,
            )
            self._secret_provenance += (
                " (resumed from a pre-secret checkpoint: earlier cohorts "
                "derived from the public config.seed)"
            )

    def privacy_spent(self):
        eps, order = self.accountant.epsilon(self.dp.delta)
        return {
            "DP/epsilon": round(float(eps), 4),
            "DP/delta": self.dp.delta,
            "DP/rdp_order": order,
            "DP/rounds_accounted": self.accountant.rounds,
            "DP/sampling_note": (
                f"Poisson-sampled cohorts executed at q={self._q:.4g} — "
                "the accounted mechanism and the run sampler are the same "
                "object; participation stream seeded from "
                f"{self._secret_provenance} (epsilon assumes the seed "
                "stays secret)"
            ),
        }

    def train(self):
        final = dict(super().train() or {})
        spent = self.privacy_spent()
        final.update(spent)
        self.log_fn(spent)
        return final
