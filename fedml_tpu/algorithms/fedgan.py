"""FedGAN — FedAvg over a generator+discriminator pair (ref:
fedml_api/distributed/fedgan/{FedGanAPI.py, FedGANAggregator.py:15-112} with
the MNISTGan model, model/cv/mnistgan.py).

The aggregator is plain sample-weighted FedAvg over the COMBINED G+D state
(the reference averages the whole MNISTGan state dict); only the local
training differs — per batch: a discriminator step (BCE real=1/fake=0) then
a generator step (BCE fake=1), the standard alternating GAN update. The
local loop is a lax.scan like every other local trainer, so the GAN variant
vmaps over clients and shard_maps over the mesh unchanged."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.models import ModelDef
from fedml_tpu.models.gan import Discriminator, Generator


def make_gan_model_def(z_dim: int = 100) -> ModelDef:
    """ModelDef-shaped container for init only; apply() is unused (GAN local
    training needs the two-step update below, not a single forward)."""
    import dataclasses

    class _GanDef(ModelDef):
        def init(self, rng):
            g = Generator()
            d = Discriminator()
            k1, k2 = jax.random.split(rng)
            gv = g.init({"params": k1}, jnp.zeros((1, z_dim)), train=False)
            dv = d.init({"params": k2}, jnp.zeros((1, 28, 28, 1)), train=False)
            variables = {
                "params": {"netg": gv["params"], "netd": dv["params"]},
            }
            bs = {}
            if "batch_stats" in gv:
                bs["netg"] = gv["batch_stats"]
            if "batch_stats" in dv:
                bs["netd"] = dv["batch_stats"]
            if bs:
                variables["batch_stats"] = bs
            return variables

    return _GanDef(
        module=None,
        input_shape=(28, 28, 1),
        num_classes=1,
        has_batch_stats=True,
        name="mnistgan",
    )


def make_gan_local_train(train_config, epochs: int, z_dim: int = 100):
    """Local GAN trainer with the (variables, x, y, mask, rng) signature the
    FedAvg round skeleton expects; y is ignored (unsupervised)."""
    g = Generator()
    d = Discriminator()
    g_opt = optax.adam(train_config.lr, b1=0.5)
    d_opt = optax.adam(train_config.lr, b1=0.5)

    def apply_g(params, bs, z, train):
        variables = {"params": params}
        if bs is not None:
            variables["batch_stats"] = bs
        if train:
            out, mut = g.apply(variables, z, train=True, mutable=["batch_stats"])
            return out, mut["batch_stats"]
        return g.apply(variables, z, train=False), bs

    def d_logits(params, x):
        return d.apply({"params": params}, x, train=False)

    def local_train(variables, x, y, mask, rng):
        del y
        params0 = variables["params"]
        g_bs0 = variables.get("batch_stats", {}).get("netg")
        S, B = mask.shape

        def step(carry, inp):
            (gp, dp, g_bs, g_os, d_os) = carry
            xb, mb, sidx = inp
            step_rng = jax.random.fold_in(rng, sidx)
            z = jax.random.normal(step_rng, (B, z_dim))
            m = mb[:, None]

            # --- D step: real→1, fake(detached)→0
            def d_loss_fn(dparams):
                fake, _ = apply_g(gp, g_bs, z, True)
                lr_real = optax.sigmoid_binary_cross_entropy(
                    d_logits(dparams, xb), jnp.ones((B, 1))
                )
                lr_fake = optax.sigmoid_binary_cross_entropy(
                    d_logits(dparams, jax.lax.stop_gradient(fake)), jnp.zeros((B, 1))
                )
                return jnp.sum((lr_real + lr_fake) * m) / jnp.maximum(jnp.sum(m), 1e-9)

            d_l, d_grads = jax.value_and_grad(d_loss_fn)(dp)
            d_updates, d_os_new = d_opt.update(d_grads, d_os, dp)
            dp_new = optax.apply_updates(dp, d_updates)

            # --- G step: fake→1
            def g_loss_fn(gparams):
                fake, new_bs = apply_g(gparams, g_bs, z, True)
                lg = optax.sigmoid_binary_cross_entropy(
                    d_logits(dp_new, fake), jnp.ones((B, 1))
                )
                return jnp.sum(lg * m) / jnp.maximum(jnp.sum(m), 1e-9), new_bs

            (g_l, new_g_bs), g_grads = jax.value_and_grad(g_loss_fn, has_aux=True)(gp)
            g_updates, g_os_new = g_opt.update(g_grads, g_os, gp)
            gp_new = optax.apply_updates(gp, g_updates)

            has_data = jnp.sum(mb) > 0
            keep = lambda n, o: jax.tree_util.tree_map(
                lambda a, b: jnp.where(has_data, a, b), n, o
            )
            carry = (
                keep(gp_new, gp),
                keep(dp_new, dp),
                keep(new_g_bs, g_bs) if g_bs is not None else g_bs,
                keep(g_os_new, g_os),
                keep(d_os_new, d_os),
            )
            mets = jnp.stack([g_l * jnp.sum(mb), d_l * jnp.sum(mb), jnp.sum(mb)])
            return carry, mets

        def epoch(carry, _e):
            carry, mets = jax.lax.scan(step, carry, (x, mask, jnp.arange(S)))
            return carry, mets.sum(axis=0)

        g_os = g_opt.init(params0["netg"])
        d_os = d_opt.init(params0["netd"])
        carry = (params0["netg"], params0["netd"], g_bs0, g_os, d_os)
        carry, mets = jax.lax.scan(epoch, carry, jnp.arange(epochs))
        mets = mets.sum(axis=0)
        gp, dp, g_bs, _, _ = carry
        out = {"params": {"netg": gp, "netd": dp}}
        if g_bs is not None:
            out["batch_stats"] = {"netg": g_bs}
        metrics = {
            "loss_sum": mets[0],  # generator loss (weighted)
            "correct": mets[1],  # discriminator loss (weighted) — see train()
            "count": mets[2],
            "steps": jnp.zeros(()),
        }
        return out, metrics

    return local_train


class FedGANAPI(FedAvgAPI):
    """FedAvg round skeleton with the GAN local trainer (ref FedGanAPI.py)."""

    def __init__(self, config, data, model=None, z_dim: int = 100, **kw):
        model = model or make_gan_model_def(z_dim)
        kw["local_train_fn"] = make_gan_local_train(
            config.train, config.fed.epochs, z_dim
        )
        super().__init__(config, data, model, **kw)
        self.z_dim = z_dim

    def train(self):
        final = {}
        for round_idx in range(self.config.fed.comm_round):
            _, metrics = self.train_round(round_idx)
            count = float(metrics["count"])
            row = {
                "round": round_idx,
                "Train/G_Loss": float(metrics["loss_sum"]) / max(count, 1e-9),
                "Train/D_Loss": float(metrics["correct"]) / max(count, 1e-9),
            }
            self.history.append(row)
            self.log_fn(row)
            final = row
        return final

    def generate(self, n: int, seed: int = 0):
        g = Generator()
        variables = {"params": self.global_vars["params"]["netg"]}
        if "batch_stats" in self.global_vars and "netg" in self.global_vars["batch_stats"]:
            variables["batch_stats"] = self.global_vars["batch_stats"]["netg"]
        z = jax.random.normal(jax.random.PRNGKey(seed), (n, self.z_dim))
        return g.apply(variables, z, train=False)
