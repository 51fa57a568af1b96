"""The one traffic generator: a population from the seed, and for every
round the cohort, the minibatches and their order.

Everything a cell's traffic is made of is data: the configuration's file
gives the population (how many clients, how their sizes are laid out, what
a sample is), the traffic file gives the cohort, the local work and the
evaluation cadence. The sizes come from the layout alone, never from
``--seed``, so every seed does the same amount of work on the same shapes;
the seed makes the samples and the weights.

The order of samples restates, in the benchmark's own code, the two
shuffles FedAvg applies here: a host permutation of each sampled client's
shard drawn from ``numpy.random.default_rng(seed * 1_000_003 + round)`` in
cohort order, then per epoch an argsort of uniform draws keyed by
``fold_in(split(fold_in(PRNGKey(seed), round + 1), C)[c], epoch)`` over the
padded slots. The cohort is ``numpy.random.seed(round); choice(range(n), k,
replace=False)`` (every client when k == n). The reference follows this
plan; a program that departs from it reads as not correct."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# --seed may exceed 31 bits; the program's seed is an int32-safe image of it.
SEED_MODULUS = 2**31 - 1


def program_seed(seed: int) -> int:
    return int(seed) % SEED_MODULUS


def size_class(n: int) -> int:
    """Step-count classes of a stacked cohort batch: powers of two up to 16,
    multiples of 8 above."""
    if n <= 16:
        return 1 << (n - 1).bit_length() if n > 1 else 1
    return -(-n // 8) * 8


def layout_sizes(pop: dict) -> np.ndarray:
    """Per-client sample counts, a function of the layout alone."""
    n = int(pop["clients"])
    kind = pop["sizes"]["kind"]
    if kind == "fixed":
        return np.full(n, int(pop["sizes"]["samples"]), np.int64)
    if kind == "lognormal":
        s = pop["sizes"]
        rng = np.random.default_rng(int(s["layout_seed"]))
        raw = rng.lognormal(np.log(s["median"]), s["sigma"], n).astype(int)
        return np.clip(raw, int(s["min"]), int(s["max"])).astype(np.int64)
    raise ValueError(f"unknown size layout {kind!r}")


def _make_images(sample: dict, total: int, seed: int):
    """Class-conditional Gaussian images in FEMNIST's geometry: a mean image
    per class (16 latent directions, as data/femnist_synth.py draws them)
    plus isotropic pixel noise. Made on the host, written in place chunk by
    chunk (fresh memory is slow to touch, so nothing is allocated twice),
    a few threads wide (numpy's generators release the interpreter lock).
    Set-up only: nothing of this runs in the window."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    classes, side = int(sample["classes"]), int(sample["side"])
    chunk = int(sample.get("chunk", 16384))
    scale = float(sample.get("pixel_scale", 1.0))
    rng = np.random.default_rng([program_seed(seed), 1])
    means = rng.standard_normal((classes, 16))
    proj = 0.3 * rng.standard_normal((16, side * side))
    class_pix = (scale * (means @ proj)).astype(np.float32)
    # within-class spread of the generator this copies: 0.6 in the latent
    # space through proj (16 * 0.36 * 0.09) plus 0.3 of pixel noise
    noise = np.float32(scale * np.sqrt(16 * 0.36 * 0.09 + 0.09))
    x = np.empty((total, side * side), np.float32)
    y = np.empty((total,), np.int32)
    local = threading.local()

    def fill(i):
        lo, hi = i * chunk, min(total, (i + 1) * chunk)
        rng = np.random.default_rng([program_seed(seed), 2, i])
        y[lo:hi] = rng.integers(0, classes, size=hi - lo)
        rng.standard_normal(dtype=np.float32, out=x[lo:hi])
        x[lo:hi] *= noise
        if getattr(local, "scratch", None) is None:
            local.scratch = np.empty((chunk, side * side), np.float32)
        pix = local.scratch[:hi - lo]
        np.take(class_pix, y[lo:hi], axis=0, out=pix)
        x[lo:hi] += pix

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(-(-total // chunk))))
    return x.reshape(total, side, side, 1), y


def _make_tokens(sample: dict, rows: int, length: int, seed: int, salt: int):
    """Random token documents; ids from 1 up, 0 being the pad id."""
    rng = np.random.default_rng([program_seed(seed), salt])
    doc = rng.integers(1, int(sample["vocab"]), size=(rows, length + 1), dtype=np.int32)
    return doc[:, :-1].copy(), doc[:, 1:].copy()


@dataclasses.dataclass
class Plan:
    round: int
    clients: np.ndarray      # cohort, in the order the round stacks it
    sizes: np.ndarray        # their real sample counts
    steps: int               # bucketed steps per epoch
    bs: int
    orders: list             # [epoch][client] -> indices into the flat population


class Feed:
    def __init__(self, model_cfg: dict, cell: dict, seed: int):
        self.cfg, self.cell, self.seed = model_cfg, cell, int(seed)
        pop = model_cfg["population"]
        self.sizes = layout_sizes(pop)
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])
        self.n_clients = len(self.sizes)
        self.k = int(cell["clients_per_round"])
        if self.k > self.n_clients:
            raise ValueError("cohort larger than the population")
        self.bs = int(cell["batch_size"])
        self.epochs = int(cell["epochs"])
        self.cadence = int(cell["eval_every"])
        sample = pop["sample"]
        self.kind = sample["kind"]
        total = int(self.offsets[-1])
        n_test = int(pop["test_samples"])
        if self.kind == "image":
            x, y = _make_images(sample, total + n_test, seed)
            self.flat_x, self.flat_y = x[:total], y[:total]
            self.test_x, self.test_y = x[total:], y[total:]
            self.units_per_sample = 1
        elif self.kind == "tokens":
            self.flat_x, self.flat_y = _make_tokens(
                sample, total, int(sample["length"]), seed, 0)
            self.test_x, self.test_y = _make_tokens(
                sample, n_test, int(sample["test_length"]), seed, 1)
            self.units_per_sample = int(sample["length"])
        else:
            raise ValueError(f"unknown sample kind {self.kind!r}")
        self._plans: dict = {}

    # -- what the program is given ------------------------------------
    def client_shards(self):
        """Per-client views of the flat population (no copy)."""
        cut = self.offsets[1:-1]
        return np.split(self.flat_x, cut), np.split(self.flat_y, cut)

    # -- the plan of a round --------------------------------------------
    def cohort(self, r: int) -> np.ndarray:
        if self.k == self.n_clients:
            return np.arange(self.n_clients)
        return np.random.RandomState(r).choice(range(self.n_clients), self.k, replace=False)

    def shape_class(self, r: int):
        """(steps, bs, any client with an all-padding step) of round r."""
        ns = self.sizes[self.cohort(r)]
        steps = size_class(-(-int(ns.max()) // self.bs))
        return steps, self.bs, bool(np.any(-(-ns // self.bs) < steps))

    def round_plan(self, r: int) -> Plan:
        if r in self._plans:
            return self._plans[r]
        clients = self.cohort(r)
        ns = self.sizes[clients]
        steps, bs, _ = self.shape_class(r)
        cap = steps * bs
        host = np.random.default_rng(program_seed(self.seed) * 1_000_003 + r)
        host_orders = [host.permutation(int(n)) for n in ns]
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(program_seed(self.seed)), r + 1),
            len(clients),
        )
        valid = jnp.arange(cap)[None, :] < jnp.asarray(ns)[:, None]
        orders = []
        for e in range(self.epochs):
            def perm(key, ok):
                u = jax.random.uniform(jax.random.fold_in(key, e), (cap,))
                return jnp.argsort(jnp.where(ok, u, jnp.inf))
            perms = np.asarray(jax.jit(jax.vmap(perm))(keys, valid))
            orders.append([
                self.offsets[c] + host_orders[j][perms[j, :int(ns[j])]]
                for j, c in enumerate(clients)
            ])
        plan = Plan(r, clients, ns, steps, bs, orders)
        self._plans[r] = plan
        return plan

    def client_batches(self, plan: Plan, lo: int, hi: int):
        """x [c, E, S, B, ...], y, mask [c, E, S, B] of cohort members
        lo..hi, padded with zeros to the round's step class."""
        cap = plan.steps * plan.bs
        c = hi - lo
        idx = np.zeros((c, self.epochs, cap), np.int64)
        mask = np.zeros((c, self.epochs, cap), np.float32)
        for j in range(c):
            n = int(plan.sizes[lo + j])
            for e in range(self.epochs):
                idx[j, e, :n] = plan.orders[e][lo + j]
                mask[j, e, :n] = 1.0
        x = self.flat_x[idx]
        y = self.flat_y[idx]
        x = x * mask.reshape(mask.shape + (1,) * (x.ndim - 3)).astype(x.dtype)
        y = y * mask.reshape(mask.shape + (1,) * (y.ndim - 3)).astype(y.dtype)
        shape = (c, self.epochs, plan.steps, plan.bs)
        return (
            jnp.asarray(x.reshape(shape + x.shape[3:])),
            jnp.asarray(y.reshape(shape + y.shape[3:])),
            jnp.asarray(mask.reshape(shape)),
        )

    def is_eval_round(self, r: int, horizon_end: int) -> bool:
        """Whether ``train()`` evaluates after round r when the followed
        rounds run as the horizons [0, 1) and [1, horizon_end)."""
        return r % self.cadence == 0 or r == 0 or r == horizon_end - 1

    def eval_batches(self, block: int = 256):
        n = len(self.test_y)
        for lo in range(0, n, block):
            hi = min(n, lo + block)
            m = np.ones((hi - lo,), np.float32)
            yield (
                jnp.asarray(self.test_x[lo:hi]), jnp.asarray(self.test_y[lo:hi]),
                jnp.asarray(m),
            )

    # -- counts -----------------------------------------------------------
    def real_samples(self, r0: int, r1: int) -> int:
        """Real (unpadded) samples trained in rounds [r0, r1)."""
        return int(sum(
            int(self.sizes[self.cohort(r)].sum()) for r in range(r0, r1)
        )) * self.epochs
