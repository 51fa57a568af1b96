"""Secure aggregation protocol: pairwise-masked sums with DH-agreed seeds —
the TurboAggregate capability (ref fedml_api/distributed/turboaggregate/
TA_decentralized_worker.py + mpc_function.py) as a complete, testable
protocol: in the aggregation path the server only ever combines masked
uploads, so the protocol *structure* reveals only the sum of client updates.

Key agreement runs in the RFC 3526 2048-bit MODP group with 256-bit
``secrets``-sourced exponents, and pair masks are expanded from the shared
secret by SHA-256 extract + SHAKE-256 XOF into the aggregation field
(mpc.dh_secret/dh_shared/derive_pair_mask) — ≥128-bit secret space, no
brute-forceable parameter anywhere (the reference's my_key_agreement runs
DH in its toy field, mpc_function.py:271). The 31-bit Mersenne FIELD is
kept for exact int64 share arithmetic; field size is about arithmetic
range, not secrecy. HONESTY NOTE — the protocol assumes an
honest-but-curious server and non-colluding parties: there are no
signatures or consistency checks against a MALICIOUS server (who could
partition parties into singleton "registries"), and the BGW seed-share
round of full SecAgg (Bonawitz et al.) is elided to the pair-key registry
(the share math itself is mpc.bgw_encode/decode, tested independently).

Fixed-point encode → field; client i's upload is
``x_i + Σ_{j>i} PRG(k_ij) − Σ_{j<i} PRG(k_ij)  (mod p)``
with k_ij the DH-agreed pair key, so every mask cancels in the sum. Dropout
tolerance (the reference has none — its barrier waits forever,
FedAVGAggregator.py:43-49 / SURVEY §5) comes from BGW-sharing each client's
mask seed to the others: if a client drops after masks were applied, the
survivors reconstruct its pairwise masks from T+1 shares and the server
removes them."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from fedml_tpu.secagg import mpc
from fedml_tpu.secagg.mpc import FIELD_PRIME

_SCALE = 1 << 16  # fixed-point fraction bits


def encode_fixed(x: np.ndarray, p: int = FIELD_PRIME) -> np.ndarray:
    """float → field: round(x * 2^16) mod p (two's-complement style)."""
    return np.mod(np.round(np.asarray(x, np.float64) * _SCALE).astype(np.int64), p)


def decode_fixed(v: np.ndarray, n_summed: int, p: int = FIELD_PRIME) -> np.ndarray:
    """field → float, recentring values above p/2 as negatives."""
    v = np.asarray(v, np.int64)
    half = p // 2
    signed = np.where(v > half, v - p, v)
    return signed.astype(np.float64) / _SCALE


class SecureAggregator:
    """N-party masked aggregation with dropout recovery."""

    def __init__(self, num_clients: int, dim: int, threshold: Optional[int] = None, p: int = FIELD_PRIME, seed: int = 0):
        self.N = num_clients
        self.dim = dim
        self.p = p
        self.T = threshold if threshold is not None else max(1, num_clients // 2)
        rng = np.random.default_rng(seed)
        self.sks = [mpc.dh_secret(rng) for _ in range(self.N)]
        self.pks = [mpc.dh_public(sk) for sk in self.sks]
        # pairwise DH keys in the 2048-bit group (ref my_key_agreement,
        # which ran in the toy field). Only unordered pairs: dh_shared is
        # symmetric and every consumer keys on (lo, hi) — the ordered
        # variant would double an O(N^2) bill of 2048-bit modexps.
        self.pair_keys: Dict[tuple, int] = {
            (i, j): mpc.dh_shared(self.sks[i], self.pks[j])
            for i in range(self.N)
            for j in range(i + 1, self.N)
        }

    def mask_of_pair(self, i: int, j: int) -> np.ndarray:
        lo, hi = min(i, j), max(i, j)
        return mpc.derive_pair_mask(
            self.pair_keys[(lo, hi)], lo, hi, self.dim, self.p
        )

    def client_upload(self, i: int, x: np.ndarray, active: Sequence[int]) -> np.ndarray:
        v = encode_fixed(x, self.p)
        for j in active:
            if j == i:
                continue
            m = self.mask_of_pair(i, j)
            v = np.mod(v + (m if i < j else -m), self.p)
        return v

    def aggregate(
        self,
        uploads: Dict[int, np.ndarray],
        intended: Sequence[int],
    ) -> np.ndarray:
        """Sum the received uploads; for clients that dropped AFTER masks
        were applied, survivors reconstruct the dropouts' pair masks and the
        server removes them (the BGW share step is elided to the pair-key
        registry here; the share/reconstruct math is mpc.bgw_encode/decode,
        tested independently)."""
        received = sorted(uploads)
        dropped = [i for i in intended if i not in uploads]
        total = np.zeros(self.dim, np.int64)
        for i in received:
            total = np.mod(total + uploads[i], self.p)
        # unwind masks that involve a dropped client
        for d in dropped:
            for i in received:
                m = self.mask_of_pair(i, d)
                total = np.mod(total - (m if i < d else -m), self.p)
        return decode_fixed(total, len(received), self.p)


# ---- round-loop integration (transport FedAvg, CommConfig.secure_agg) ----
# The reference's turboaggregate is a DISTRIBUTED algorithm (MPI workers,
# TA_decentralized_worker.py); these helpers put the masked-sum protocol on
# this framework's transport round: each sampled client is a party for ONE
# round, uploads encode(n_i · Δ_i) masked pairwise, and the server
# reconstructs only the weighted SUM. Party registries are re-derived per
# round from (seed, round_idx) so pair masks are never reused across rounds
# (mask reuse would leak update differences).


def flatten_tree(tree):
    """tree of arrays -> (flat float64 [D], shapes/treedef for unflatten).
    (Hand-rolled rather than jax.flatten_util.ravel_pytree so unflatten
    restores each leaf's ORIGINAL dtype after the float64 field math.)"""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    leaves = [np.asarray(l) for l in leaves]
    flat = np.concatenate([l.reshape(-1).astype(np.float64) for l in leaves])
    return flat, (treedef, [(l.shape, l.dtype) for l in leaves])


def tree_dim(tree) -> int:
    """Total flattened element count — the ONE definition both wire ends
    use to size the per-round mask registry."""
    import jax

    return int(sum(np.asarray(l).size for l in jax.tree_util.tree_leaves(tree)))


def unflatten_like(spec, flat: np.ndarray):
    import jax

    treedef, meta = spec
    out, off = [], 0
    for shape, dtype in meta:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        out.append(flat[off : off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _field_bound_check(update: np.ndarray, p: int, n_parties: int) -> None:
    """The fixed-point field has finite range: |value| must stay below
    (p/2)/2^16/N ≈ 16383/N so even the SUM over N parties cannot wrap.
    Exceeding it would silently corrupt the aggregate (mod-p wraparound),
    so it raises instead — rescale (smaller lr, fewer samples per upload)
    or use the plain path for such magnitudes."""
    bound = (p // 2) / _SCALE / max(n_parties, 1)
    worst = float(np.max(np.abs(update))) if update.size else 0.0
    if worst >= bound:
        raise ValueError(
            f"secure-agg update magnitude {worst:.1f} exceeds the fixed-"
            f"point field bound {bound:.1f} (p=2^31, 2^16 fraction bits, "
            f"{n_parties} parties) — the masked sum would wrap mod p"
        )


class ClientParty:
    """One round-party with a LOCALLY generated DH keypair.

    Round 2 derived every party's secret key from the shared ``config.seed``
, so the server could recompute every client's
    masks and the protocol structure hid nothing. Here the secret key is
    drawn from client-local entropy (``secrets`` OS entropy when ``rng``
    is None) and NEVER leaves this object; only the 2048-bit-group public
    key goes on the wire (contrast ref turboaggregate my_key_agreement,
    mpc_function.py:271, toy-field DH). Fresh party = fresh keys each
    round, so masks are never reused across rounds."""

    def __init__(self, party: int, dim: int, p: int = FIELD_PRIME, rng=None):
        self.party = party
        self.dim = dim
        self.p = p
        self._sk = mpc.dh_secret(rng)
        self.pk = mpc.dh_public(self._sk)
        self._pair_keys: Dict[int, int] = {}
        self.active: List[int] = []

    def set_registry(self, pks: Dict[int, int]) -> None:
        """Learn the other parties' public keys (broadcast by the server —
        public material only) and agree pairwise keys with OWN secret."""
        self.active = sorted(int(j) for j in pks)
        self._pair_keys = {
            int(j): mpc.dh_shared(self._sk, int(pk))
            for j, pk in pks.items()
            if int(j) != self.party
        }

    def _mask(self, j: int) -> np.ndarray:
        lo, hi = min(self.party, j), max(self.party, j)
        return mpc.derive_pair_mask(self._pair_keys[j], lo, hi, self.dim, self.p)

    def masked_update(self, w_local, w_round, n_samples: float) -> np.ndarray:
        """Masked field vector of n_i · (w_i − w_round), masks vs every
        OTHER registry party (cancel in the sum of active uploads)."""
        flat_local, _ = flatten_tree(w_local)
        flat_round, _ = flatten_tree(w_round)
        update = float(n_samples) * (flat_local - flat_round)
        _field_bound_check(update, self.p, len(self.active))
        v = encode_fixed(update, self.p)
        for j in self.active:
            if j == self.party:
                continue
            m = self._mask(j)
            v = np.mod(v + (m if self.party < j else -m), self.p)
        return v

    def recovery_mask(self, dropped: Sequence[int]) -> np.ndarray:
        """Survivor's unmasking contribution for parties that dropped after
        keys were agreed but before uploading: Σ_d ±PRG(k_{self,d}) with
        the sign THIS party applied in its own upload. (Stand-in for the
        BGW seed-share reconstruction round of the full protocol —
        mpc.bgw_encode/decode hold the share math.)"""
        total = np.zeros(self.dim, np.int64)
        for d in dropped:
            m = self._mask(int(d))
            total = np.mod(total + (m if self.party < int(d) else -m), self.p)
        return total


class ServerAggregator:
    """Server side of the client-held-key protocol: holds ONLY public
    material (the pk registry it relayed) and masked vectors — at no point
    does any party secret enter this object, so everything the server
    observes is the masked uploads plus their sum."""

    def __init__(self, dim: int, p: int = FIELD_PRIME):
        self.dim = dim
        self.p = p

    def masked_sum(self, uploads: Dict[int, np.ndarray]) -> np.ndarray:
        total = np.zeros(self.dim, np.int64)
        for i in sorted(uploads):
            total = np.mod(total + uploads[i], self.p)
        return total

    def remove_dropout_masks(
        self, total: np.ndarray, recovery: Dict[int, np.ndarray]
    ) -> np.ndarray:
        """Subtract the survivors' recovery contributions (each survivor
        reports the masks it shared with the dropped parties)."""
        for i in sorted(recovery):
            total = np.mod(total - recovery[i], self.p)
        return total

    def decode_average(self, total: np.ndarray, ns: Dict[int, float], w_round):
        """Σ_received n_i·Δ_i / Σ_received n_i applied to w_round."""
        decoded = decode_fixed(total, len(ns), self.p)
        total_n = float(sum(ns.values()))
        flat_round, spec = flatten_tree(w_round)
        return unflatten_like(spec, flat_round + decoded / max(total_n, 1e-9))


# -- legacy single-process simulation helpers (standalone turboaggregate /
#    CLI demo keep using the seed-derived SecureAggregator; the TRANSPORT
#    path uses ClientParty/ServerAggregator above) --


def round_aggregator(num_parties: int, dim: int, seed: int, round_idx: int) -> SecureAggregator:
    """Per-round party registry derived from (seed, round_idx) — fresh pair
    keys per round. SIMULATION ONLY: all secrets come from one seed, so
    this models the mask algebra, not the trust boundary (the transport
    protocol uses ClientParty, whose secrets are client-local)."""
    return SecureAggregator(
        num_parties, dim, seed=seed * 1_000_003 + round_idx * 7919 + 17
    )


def mask_round_update(
    agg: SecureAggregator, party: int, w_local, w_round, n_samples: float
) -> np.ndarray:
    """Client side (simulation registry): masked field vector of
    n_i · (w_i − w_round). See _field_bound_check for the range rule."""
    flat_local, _ = flatten_tree(w_local)
    flat_round, _ = flatten_tree(w_round)
    update = float(n_samples) * (flat_local - flat_round)
    _field_bound_check(update, agg.p, agg.N)
    return agg.client_upload(party, update, active=list(range(agg.N)))


def unmask_round_average(
    agg: SecureAggregator,
    uploads,
    ns,
    w_round,
):
    """Server side (simulation registry): Σ_received n_i·Δ_i (masked sum,
    dropout masks recovered) / Σ_received n_i, applied to w_round."""
    decoded = agg.aggregate(uploads, intended=list(range(agg.N)))
    total_n = float(sum(ns[i] for i in uploads))
    flat_round, spec = flatten_tree(w_round)
    return unflatten_like(spec, flat_round + decoded / max(total_n, 1e-9))
