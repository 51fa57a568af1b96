#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fedml_tpu still starts on the chip.

    python chip_smoke.py               # one TPU chip: cli_cnn, store_gather,
                                       # lm_flagship, decoder, kernels
    python chip_smoke.py --phases decoder   # only the named phases
    python chip_smoke.py --multichip   # four TPU chips: ONLY the mesh runtime
                                       # and the single-device run it is compared with
    python chip_smoke.py --rehearse [--multichip]
                                       # any backend, tiny sizes, kernels where the
                                       # backend has them: finds wrong paths and
                                       # arguments before chip time is spent; never
                                       # prints the result line

One process, jax imported once, no child process. Every phase goes through
an entry point a user would call (``fedml_tpu.cli.main`` exactly as
``python -m fedml_tpu`` dispatches it, or ``FedAvgAPI(...).train()``),
checks what came out by the repo's own means, and prints one JSON line
with its wall seconds and what it asserted. A phase that fails raises: the
script exits non-zero and prints no result line. Without ``--rehearse`` it
refuses to run unless ``jax.devices()[0].platform == "tpu"``.

The last line of a passing run is the contract's device line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Compile cache: ``fedml_tpu.compile.resolve_cache_dir`` — the directory
``$JAX_COMPILATION_CACHE_DIR`` names when set, else ``<checkout>/.jax_cache``.
The ``compile`` line before the result line reports backend-compile events,
their summed wall seconds and persistent-cache hits, so two runs over one
cache directory can be told apart (cold vs warm).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent

# The north star (BASELINE.json): FEMNIST geometry, CNNOriginalFedAvg,
# 10 clients/round, batch 20, E=1, SGD lr 0.1.
NORTH_STAR = [
    "--algorithm", "fedavg", "--model", "cnn", "--dataset", "femnist_synth",
    "--client_num_per_round", "10", "--batch_size", "20", "--lr", "0.1",
    "--epochs", "1", "--comm_round", "5",
]

# The flagship LM: d768 / L6 / H8, vocab 1024,
# seq 256, batch 32, 8 clients x 512 sequences, bf16, Adam clients.
FLAGSHIP = dict(
    vocab=1024, seq=256, layers=6, heads=8, dim=768, clients=8,
    samples=512, batch=32, dtype="bfloat16",
)
FLAGSHIP_TINY = dict(
    vocab=64, seq=32, layers=2, heads=2, dim=32, clients=4, samples=32,
    batch=16, dtype="float32",
)

# The device store's population: image-shaped samples, far more of them than
# one cohort takes (10 clients a round), so that a program which copies the
# population shows in its planned temporaries.
STORE = dict(clients=2000, samples=200)
STORE_TINY = dict(clients=400, samples=40)

# The spec-driven decoder at Mellum2-12B-A2.5B's published widths, one chip's
# share of eight (experts 0..7 of 64, an eighth of the vocabulary), two
# layers (one window, one full/YaRN), 2 silos x 2 steps of 2 x 2048 tokens.
DECODER = dict(
    vocab=12288, seq=2048, clients=2, samples=4, batch=2, dtype="bfloat16",
    spec=dict(
        hidden_size=2304, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        layer_types=["sliding_attention", "full_attention"], sliding_window=1024,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
        num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
        experts_held=[0, 8],
    ),
)
# The same decoder at Kanana-2-30B-A3B's published widths (latent attention
# with heads of 128 | 64 and values of 128 out of a latent of 512, a dense
# layer of 6144, 128 sigmoid-routed experts of 768 top-6 by a biased choice,
# a shared expert of 2 x 768), one chip's share of sixteen (experts 0..7, an
# eighth of the vocabulary), the dense layer and one expert layer, 2 silos x
# 2 steps of one 2048-token document.
LATENT = dict(
    vocab=16032, seq=2048, clients=2, samples=2, batch=1, dtype="bfloat16",
    spec=dict(
        hidden_size=2048, num_attention_heads=32, num_hidden_layers=2,
        kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=1000000, rope_interleave=True,
        first_k_dense_replace=1, intermediate_size=6144,
        n_routed_experts=128, n_shared_experts=2, num_experts_per_tok=6,
        moe_intermediate_size=768, scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=True, routed_scaling_factor=2.448, experts_held=[0, 8],
    ),
)
LATENT_TINY = dict(
    vocab=97, seq=32, clients=2, samples=4, batch=1, dtype="float32",
    spec=dict(
        hidden_size=64, num_attention_heads=4, num_hidden_layers=2,
        kv_lora_rank=24, q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=1000000, rope_interleave=True,
        first_k_dense_replace=1, intermediate_size=96,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        moe_intermediate_size=32, scoring_func="sigmoid", topk_method="noaux_tc",
        norm_topk_prob=True, routed_scaling_factor=2.448, experts_held=[0, 4],
    ),
)
DECODER_TINY = dict(
    vocab=97, seq=32, clients=2, samples=4, batch=2, dtype="float32",
    spec=dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        layer_types=["sliding_attention", "full_attention"], sliding_window=8,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        experts_held=[0, 4],
    ),
)

# The attention check: clients (a vmap), (B, T, H, KV, D) a client, window,
# dtype and the largest error allowed against float32 plain attention, as a
# share of the reference's largest magnitude (or of 1 where that is smaller).
# FLASH is the two language-model cells' training step. The float32 rehearsal
# is held to tests/test_flash_attention.py's pins. At bfloat16 the inputs, P,
# dS and the outputs each round to 8 bits: the limit is two bfloat16 eps,
# three times the largest share the v5e read (PERF.md section 6, PR 29: silo4
# 0.0029 out, 0.0052 dq, 0.0029 dk, 0.0036 dv; silo2 0.0034, 0.0045, 0.0029,
# 0.0024, where dv reaches 63.6 and its error 0.152).
_F32_TOL = {"out": 2e-5, "dq": 5e-5, "dk": 5e-5, "dv": 5e-5}
_BF16_TOL = {"out": 0.016, "dq": 0.016, "dk": 0.016, "dv": 0.016}
FLASH = {
    "gpt2-124m.silo4": dict(clients=4, shape=(4, 1024, 12, 12, 64), window=None,
                            dtype="bfloat16", tol=_BF16_TOL),
    "mellum2-12b-a2.5b.silo2": dict(clients=1, shape=(2, 2048, 32, 4, 128), window=1024,
                                    dtype="bfloat16", tol=_BF16_TOL),
    # latent attention: a second score term of 64 against one shared key
    "kanana-2-30b-a3b.silo2b1": dict(clients=1, shape=(1, 2048, 32, 32, 128), rope=64,
                                     window=None, dtype="bfloat16", tol=_BF16_TOL),
}
FLASH_TINY = {
    "equal_heads": dict(clients=2, shape=(1, 256, 2, 2, 64), window=None,
                        dtype="float32", tol=_F32_TOL),
    "grouped_window": dict(clients=1, shape=(1, 256, 4, 1, 128), window=100,
                           dtype="float32", tol=_F32_TOL),
    "two_terms": dict(clients=1, shape=(1, 256, 2, 2, 128), rope=64, window=None,
                      dtype="float32", tol=_F32_TOL),
}


class CompileClock:
    """Wall-clock stamps of every XLA backend-compile event — the stream
    ``fedml_tpu.analysis.sentinel.RecompileSentinel`` counts — so a window
    of ROUNDS inside one ``train()`` call can be shown to compile nothing.
    jax wraps persistent-cache retrievals in the same event; ``hits``
    counts those."""

    def __init__(self):
        import jax.monitoring

        self.events = []  # (unix time at event end, seconds)
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.events.append((time.time(), float(secs)))

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return len(self.events), self.hits

    def since(self, mark):
        n0, h0 = mark
        ev = self.events[n0:]
        return {
            "backend_compile_events": len(ev),
            "backend_compile_s": round(sum(s for _, s in ev), 3),
            "persistent_cache_hits": self.hits - h0,
        }

    def between(self, t0, t1):
        return [t for t, _ in self.events if t0 < t <= t1]


def check(cond, what):
    """An assertion that survives ``python -O`` and names what failed."""
    if not cond:
        raise AssertionError(what)
    return what


def run_cli(args, log_dir):
    """``python -m fedml_tpu <args> --log_dir <log_dir>``, in this process:
    the same click command ``fedml_tpu/__main__.py`` dispatches to.
    Returns (api, per-round metric rows, summary.json)."""
    from fedml_tpu import cli

    shutil.rmtree(log_dir, ignore_errors=True)
    api = cli.main(
        [*args, "--log_dir", str(log_dir)], standalone_mode=False
    )
    rows = [
        json.loads(line)
        for line in (log_dir / "metrics.jsonl").read_text().splitlines()
    ]
    check((log_dir / "summary.json").is_file(), "summary.json written")
    summary = json.loads((log_dir / "summary.json").read_text())
    return api, rows, summary


def train_losses(rows):
    losses = [r["Train/Loss"] for r in rows if "Train/Loss" in r]
    check(losses and all(math.isfinite(x) for x in losses),
          f"every Train/Loss finite: {losses}")
    return losses


def round_program_text(api):
    """(placed round-0 batch, optimized HLO) of the round program
    ``api.train_round(0)`` dispatches. Lowering executes nothing."""
    fn, (global_vars, *placed) = api.round_program(0)
    return placed, fn.lower(global_vars, *placed).compile().as_text()


def devices_of(tree):
    import jax

    return {d for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()}


def platforms_of(tree):
    return {d.platform for d in devices_of(tree)}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_cli_cnn(ctx):
    """The north star through the CLI: 5 FedAvg rounds of the FEMNIST CNN."""
    api, rows, summary = run_cli(
        [*NORTH_STAR, "--seed", str(ctx.seed), "--recompile_budget", "200"],
        ctx.out / "cli_cnn",
    )
    losses = train_losses(rows)
    asserted = [
        "summary.json written",
        check(len(losses) == 5, f"5 rounds logged, got {len(losses)}"),
        check(losses[-1] < losses[0],
              f"last Train/Loss {losses[-1]:.4f} < round 0's {losses[0]:.4f}"),
        check(platforms_of(api.global_vars) == {ctx.platform},
              f"parameters live on {ctx.platform}"),
    ]
    # rounds 2-4: from the moment round 2's cohort is selected (before its
    # dispatch — the round pipeline logs that row first) to the last
    # round's metrics row, no XLA backend compile may happen.
    t_sel2 = min(r["_ts"] for r in rows if r.get("round") == 2)
    t_end = max(r["_ts"] for r in rows if "Train/Loss" in r)
    late = ctx.clock.between(t_sel2, t_end)
    asserted.append(check(
        not late, f"rounds 2-4 compiled nothing ({len(late)} events)"
    ))
    return {
        "asserted": asserted,
        "train_loss": [round(x, 4) for x in losses],
        "recompiles_total": summary.get("compile/recompiles"),
    }


def phase_store_gather(ctx):
    """The device store under ``FedAvgAPI``: an image-shaped population held
    as rows, whose gather program plans cohort-sized temporaries only."""
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset, stack_clients
    from fedml_tpu.models import create_model

    m = STORE_TINY if ctx.rehearse else STORE
    rng = np.random.default_rng(ctx.seed)
    n = m["clients"] * m["samples"]
    x = rng.random((n, 28, 28, 1), dtype=np.float32)
    y = rng.integers(0, 62, size=n).astype(np.int32)
    data = FederatedDataset(
        name="store_smoke",
        client_x=np.split(x, m["clients"]), client_y=np.split(y, m["clients"]),
        test_x=x[:256], test_y=y[:256], num_classes=62,
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=20),
        fed=FedConfig(
            client_num_in_total=m["clients"], client_num_per_round=10,
            comm_round=2, epochs=1, frequency_of_the_test=10_000,
        ),
        train=TrainConfig(lr=0.1),
        seed=ctx.seed,
    )
    rows = []
    api = FedAvgAPI(
        cfg, data, create_model("cnn", "femnist_synth", (28, 28, 1), 62),
        log_fn=rows.append,
    )
    store = api._store
    check(store is not None, "the population is held on the device")
    sampled = api._round_plan(0)[0]
    idx, mask, steps, bs, _ = store.round_indices(sampled, 20, seed=1)
    temp = store.gather_program(steps, bs).lower(
        store.flat_x, store.flat_y, jnp.asarray(idx), jnp.asarray(mask)
    ).compile().memory_analysis().temp_size_in_bytes
    host = stack_clients(data, sampled, 20, seed=1)
    dev = store.round_batch(sampled, 20, seed=1)
    api.train()
    losses = train_losses(rows)
    return {
        "asserted": [
            check(store.flat_x.shape == (n, 896),
                  "samples are held as rows of whole 128-lane tiles"),
            # the assertion that catches a per-round copy of the population
            check(temp * 10 < store.resident_bytes,
                  f"the gather program plans {temp} B of temporaries, under a "
                  f"tenth of the {store.resident_bytes} B the store holds"),
            check(all(np.array_equal(np.asarray(getattr(dev, k)), getattr(host, k))
                      for k in ("x", "y", "mask")),
                  "the gathered batch is bit-equal to stack_clients"),
            check(len(losses) == 2, f"2 rounds logged, got {len(losses)}"),
        ],
        "store": {"rows": n, "row_bytes": store.row_bytes,
                  "resident_bytes": store.resident_bytes,
                  "gather_temp_bytes": temp},
        "train_loss": [round(v, 4) for v in losses],
    }


def phase_lm_flagship(ctx):
    """Full width of the flagship LM through ``FedAvgAPI(...).train()``."""
    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.synthetic import synthetic_shakespeare
    from fedml_tpu.models import create_model

    m = FLAGSHIP_TINY if ctx.rehearse else FLAGSHIP
    data = synthetic_shakespeare(
        num_clients=m["clients"], samples_per_client=m["samples"],
        seq_len=m["seq"], vocab_size=m["vocab"], seed=ctx.seed,
        seq_targets=True,
    )
    model = create_model(
        "transformer", "shakespeare_synth", (m["seq"],), m["vocab"],
        num_layers=m["layers"], num_heads=m["heads"], embed_dim=m["dim"],
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=m["batch"], pad_bucket=1),
        fed=FedConfig(
            client_num_in_total=m["clients"],
            client_num_per_round=m["clients"],
            comm_round=3, epochs=1, frequency_of_the_test=10_000,
            # client_parallelism stays at its default ("auto", which
            # resolves to vmap for every transformer): the default path
            # is what a user gets, and on one v5e chip it runs — with
            # 13.9 GB of the chip's 16 reserved (CHANGES.md, PR 21).
        ),
        train=TrainConfig(
            client_optimizer="adam", lr=1e-3, compute_dtype=m["dtype"]
        ),
        seed=ctx.seed,
    )
    rows = []
    api = FedAvgAPI(cfg, data, model, task="nwp", log_fn=rows.append)
    api.train()
    losses = train_losses(rows)
    return {
        "asserted": [
            check(len(losses) == 3, f"3 rounds logged, got {len(losses)}"),
            check(losses[-1] < losses[0],
                  f"last Train/Loss {losses[-1]:.4f} < round 0's {losses[0]:.4f}"),
            check(platforms_of(api.global_vars) == {ctx.platform},
                  f"parameters live on {ctx.platform}"),
        ],
        "client_parallelism": cfg.fed.client_parallelism,
        "model": {k: m[k] for k in ("dim", "layers", "heads", "vocab", "seq")},
        "train_loss": [round(x, 4) for x in losses],
    }


def phase_decoder(ctx):
    """The spec-driven decoder's round through ``FedAvgAPI(...).train()``,
    and its grouped product against the dense form of the same product in
    float32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.decoder import grouped_dot

    m = DECODER_TINY if ctx.rehearse else DECODER
    spec = m["spec"]
    d, f = spec["hidden_size"], spec["moe_intermediate_size"]
    held = spec["experts_held"][1] - spec["experts_held"][0]
    rows = m["batch"] * m["seq"] * spec["num_experts_per_tok"]

    # the grouped product: rows x d times [held, d, f], a share of the rows in groups
    rng = np.random.default_rng(ctx.seed)
    sizes = rng.multinomial(rows // 8, np.ones(held) / held).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    w = jnp.asarray(0.02 * rng.standard_normal((held, d, f)), jnp.float32)
    group = np.repeat(np.arange(held + 1), list(sizes) + [rows - int(sizes.sum())])
    onehot = jnp.asarray(group[:, None] == np.arange(held)[None, :], jnp.float32)
    live = int(sizes.sum())
    with jax.default_matmul_precision("highest"):
        got = jax.jit(grouped_dot)(x, w, jnp.asarray(sizes))
        want = jax.jit(lambda x, w, oh: jnp.einsum("me,med->md", oh, jnp.einsum(
            "mk,ekd->med", x, w)))(x[:live], w, onehot[:live])
    err32 = float(jnp.max(jnp.abs(got[:live] - want)))
    scale = float(jnp.max(jnp.abs(want)))
    low = jax.jit(grouped_dot)(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), jnp.asarray(sizes))
    err16 = float(jnp.max(jnp.abs(low[:live].astype(jnp.float32) - want)))

    mellum = _decoder_round(ctx, m, "grouped-query")
    latent = _decoder_round(ctx, LATENT_TINY if ctx.rehearse else LATENT, "latent")
    return {
        "asserted": [
            check(err32 <= 1e-5 * scale,
                  f"grouped product in float32 at highest = dense form: {err32:.3g} of {scale:.3g}"),
            check(err16 <= 2e-2 * scale, f"in bfloat16 within rounding: {err16:.3g} of {scale:.3g}"),
        ] + mellum.pop("asserted") + latent.pop("asserted"),
        "grouped_product": {"rows": rows, "live": live, "err_f32": err32, "err_bf16": err16,
                            "scale": scale},
        **mellum, "latent": latent,
    }


def _decoder_round(ctx, m, kind):
    """Two rounds of one decoder spec through ``FedAvgAPI(...).train()``:
    finite losses, the expert counters on the ``flush`` spans, no call over
    its row bound, and on the chip the attention kernel in the round program."""
    import jax
    import numpy as np

    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.models import create_model
    from fedml_tpu.models.decoder import row_bound
    from fedml_tpu.telemetry import get_tracer

    spec = m["spec"]
    cx, cy = [], []
    for c in range(m["clients"]):
        doc = np.random.default_rng([ctx.seed, c]).integers(
            1, m["vocab"], size=(m["samples"], m["seq"] + 1), dtype=np.int32)
        cx.append(doc[:, :-1].copy())
        cy.append(doc[:, 1:].copy())
    data = FederatedDataset(
        name="random_tokens", client_x=cx, client_y=cy,
        test_x=cx[0][:2, :64], test_y=cy[0][:2, :64], num_classes=m["vocab"])
    model = create_model("decoder", "random_tokens", (m["seq"],), m["vocab"], **spec)
    cfg = RunConfig(
        data=DataConfig(batch_size=m["batch"], pad_bucket=1),
        fed=FedConfig(
            client_num_in_total=m["clients"], client_num_per_round=m["clients"],
            comm_round=2, epochs=1, frequency_of_the_test=10_000,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.01, compute_dtype=m["dtype"]),
        model="decoder", seed=ctx.seed,
    )
    def round_programs():
        return {e.hlo_modules()[0].to_string()
                for e in jax.devices()[0].client.live_executables()
                if e.hlo_modules() and e.hlo_modules()[0].name.startswith("jit_round_fn")}

    out = []
    tracer = get_tracer()
    t0 = tracer.now_us()
    before = round_programs()
    api = FedAvgAPI(cfg, data, model, task="nwp", log_fn=out.append)
    api.train()
    losses = train_losses(out)
    flushes = [e.attrs for e in tracer.events()
               if e.name == "flush" and e.ts_us >= t0 and "moe_pairs" in e.attrs]
    moe = {k: sum(a[k] for a in flushes) for k in model.counters if k != "moe_dropped"}
    consts = model.flush_attrs(m["batch"])
    layers, top_k = consts["expert_layers"], consts["top_k"]
    held = model.module.held()[1] - model.module.held()[0]
    tokens = 2 * m["clients"] * m["samples"] * m["seq"]
    per_token = moe["moe_pairs"] / (tokens * layers)
    # every (token, slot) row of every expert layer and step, and the rows
    # under the layer's bound (a quarter of them at a share of 8 in 64)
    rows = m["batch"] * m["seq"] * top_k
    all_rows = tokens * top_k * layers
    bound = row_bound(rows, held, model.module.experts())
    even = top_k * held / model.module.experts()
    asserted = [
        check(len(losses) == 2 and all(math.isfinite(v) for v in losses),
              f"{kind}: 2 rounds logged with finite losses: {losses}"),
        check(bool(flushes) and sum(a["moe_dropped"] for a in flushes) == 0,
              f"{kind}: the flush spans carry the expert counters and no pair was dropped"),
        check(0.5 * even < per_token < 2.0 * even,
              f"{kind}: held pairs per token and expert layer {per_token:.3f} near {even:.3f}"),
        check(bool(flushes) and moe["moe_overflow"] == 0
              and moe["moe_rows"] == moe["moe_calls"] * bound == all_rows * bound // rows
              and (ctx.rehearse or moe["moe_rows"] < all_rows),
              f"{kind}: no call over its bound of {bound} rows; the grouped products ran over "
              f"{moe['moe_rows']:.0f} of {all_rows} (token, slot) rows"),
        check(platforms_of(api.global_vars) == {ctx.platform},
              f"{kind}: parameters live on {ctx.platform}"),
    ]
    if "moe_bias_moved" in moe:
        asserted.append(check(
            0 <= moe["moe_bias_moved"] < tokens * top_k * layers,
            f"{kind}: the selection bias (zeros at init) moved "
            f"{moe['moe_bias_moved']:.0f} chosen pairs"))
    if ctx.platform == "tpu":
        asserted.append(check(
            any("tpu_custom_call" in t for t in round_programs() - before),
            f"{kind}: the round program holds the attention kernel's tpu_custom_call"))
    return {
        "asserted": asserted,
        "schedule": api._client_mode, "train_loss": [round(v, 4) for v in losses],
        "held_pairs_per_token": per_token, "moe": moe,
    }


def _flash_check(ctx):
    """The attention entry at the two language-model cells' training shapes
    (``FLASH``): forward + gradient through ``ops/attention.attention``,
    compiled, against the plain form in float32 at the highest matmul
    precision. silo4's case runs under a ``vmap`` over its 4 clients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.attention import attention, takes_kernel
    from fedml_tpu.ops.flash_attention import _use_interpret
    from fedml_tpu.parallel.ring_attention import full_attention

    results, asserted = {}, []
    if ctx.platform == "tpu":
        asserted.append(check(_use_interpret() is False, "flash interpret resolved to False"))
    for name, case in (FLASH_TINY if ctx.rehearse else FLASH).items():
        clients, (B, T, H, KV, D), window = case["clients"], case["shape"], case["window"]
        R = case.get("rope", 0)
        dtype = jnp.dtype(case["dtype"])
        asserted.append(check(takes_kernel(T, H, KV, D, R, D), f"{name}: the entry takes the kernel"))
        # q, k, v, and with a second score term q_rope and the one k_rope
        shapes = [(H, D), (KV, D), (KV, D)] + ([(H, R), (1, R)] if R else [])
        keys = jax.random.split(jax.random.PRNGKey(ctx.seed), len(shapes))
        operands = [jax.random.normal(kk, (clients, B, T) + tail, dtype)
                    for kk, tail in zip(keys, shapes)]

        def fwd_and_grads(fn, q, k, v, *rope):
            def loss(q, k, v, *rope):
                two = dict(q_rope=rope[0], k_rope=rope[1], scale=(D + R) ** -0.5) if rope else {}
                out = fn(q, k, v, causal=True, window=window, **two)
                return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

            (_, out), grads = jax.value_and_grad(
                loss, argnums=tuple(range(3 + len(rope))), has_aux=True)(q, k, v, *rope)
            return (out,) + grads

        step = jax.jit(jax.vmap(functools.partial(fwd_and_grads, attention)))
        compiled = step.lower(*operands).compile()
        if ctx.platform == "tpu":
            asserted.append(check(
                compiled.as_text().count("tpu_custom_call") >= 2,
                f"{name}: fwd+grad program contains the forward and backward tpu_custom_call"))
        got = compiled(*operands)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.vmap(functools.partial(fwd_and_grads, full_attention)))(
                *(a.astype(jnp.float32) for a in operands))

        errs = {}
        parts = ("out", "dq", "dk", "dv") + (("dq_rope", "dk_rope") if R else ())
        for part, a, b in zip(parts, got, want):
            a = np.asarray(a.astype(jnp.float32))
            check(np.isfinite(a).all(), f"{name}: flash {part} finite")
            b = np.asarray(b)
            err = float(np.abs(a - b).max())
            tol = case["tol"][part.removesuffix("_rope")] * max(1.0, float(np.abs(b).max()))
            errs[part] = {"max_abs_err": err, "tol": tol, "ref_max": float(np.abs(b).max())}
            asserted.append(check(
                err <= tol,
                f"{name}: flash {part} within {tol:.3g} of plain attention (err {err:.3g})"))
        results[name] = {"clients": clients, "shape": [B, T, H, KV, D], "window": window,
                         "dtype": dtype.name, "errors": errs}
    return results, asserted


def _robust_stats_check(ctx):
    """median_1d / trimmed_mean_1d at C=10, D = FEMNIST-CNN parameter
    count, kernel path vs the jnp.sort path."""
    import jax
    import numpy as np

    from fedml_tpu.models import create_model
    from fedml_tpu.ops.robust_stats import median_1d, trimmed_mean_1d

    shapes = jax.eval_shape(
        create_model("cnn", "femnist", (28, 28, 1), 62).init,
        jax.random.PRNGKey(0),
    )
    D = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    if ctx.rehearse:
        D = 5000
    x = jax.random.normal(jax.random.PRNGKey(ctx.seed + 1), (10, D))
    asserted = []
    for name, kernel, ref in (
        ("median_1d", median_1d(x, use_kernel=True),
         median_1d(x, use_kernel=False)),
        ("trimmed_mean_1d", trimmed_mean_1d(x, 2, use_kernel=True),
         trimmed_mean_1d(x, 2, use_kernel=False)),
    ):
        np.testing.assert_allclose(  # tests/test_robust_stats.py tolerance
            np.asarray(kernel), np.asarray(ref), atol=1e-6, rtol=1e-6,
            err_msg=name,
        )
        asserted.append(f"{name} kernel == sort path at [10, {D}] (1e-6)")
    return {"C": 10, "D": D}, asserted


def _pool_check(ctx):
    """ops/pooling.max_pool at the FEMNIST CNN's first pool (post-ReLU
    values, one plane constant so that every window in it is tied): value
    and gradient equal flax's to the last bit, with no select_and_scatter
    in the compiled gradient."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.pooling import max_pool

    shape = (4, 8, 8, 32) if ctx.rehearse else (20, 28, 28, 32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(ctx.seed + 2))
    x = jnp.maximum(jax.random.normal(k1, shape) - 0.5, 0.0).at[:, :, :, 0].set(0.75)
    dy = jax.random.normal(k2, (shape[0], shape[1] // 2, shape[2] // 2, shape[3]))

    def both(pool):
        fn = jax.jit(lambda x, dy: (pool(x), jax.vjp(pool, x)[1](dy)[0]))
        return fn.lower(x, dy).compile().as_text(), fn(x, dy)

    text, ours = both(lambda x: max_pool(x, (2, 2), strides=(2, 2)))
    _, flax = both(lambda x: nn.max_pool(x, (2, 2), strides=(2, 2)))
    asserted = []
    for part, a, b in zip(("value", "gradient"), ours, flax):
        asserted.append(check(
            np.array_equal(np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32)),
            f"max_pool {part} at f32{list(shape)} bit-equal to nn.max_pool, ties included"))
    asserted.append(check("select-and-scatter" not in text,
                          "max_pool's compiled gradient holds no select-and-scatter"))
    return {"shape": list(shape)}, asserted


def _rotary_check(ctx):
    """ops/rotary.rotary at the two cells' q and k (heads of 128 under YaRN's
    scale, heads of 64), bfloat16: value and gradient equal the plain form's
    to the last bit (float32 products and sum, one rounding: the vector unit
    contracts nothing), through the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.decoder import rotary_tables
    from fedml_tpu.ops import rotary as op

    yarn = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192}
    cases = {"mellum_q": ((2, 2048, 32, 128), yarn), "mellum_k": ((2, 2048, 4, 128), yarn),
             "lfm2_q": ((1, 4096, 32, 64), {"rope_theta": 1000000}),
             "lfm2_k": ((1, 4096, 8, 64), {"rope_theta": 1000000})}
    if ctx.rehearse:
        cases = {"heads_of_128": ((1, 256, 2, 128), yarn), "heads_of_64": ((1, 256, 2, 64), yarn)}
    asserted = []
    for name, (shape, rope) in cases.items():
        cos, sin = rotary_tables(rope, shape[3], shape[1])
        x, dy = (jax.random.normal(k, shape, jnp.bfloat16)
                 for k in jax.random.split(jax.random.PRNGKey(ctx.seed + 3)))
        if ctx.rehearse:
            # the CPU contracts the plain form's sum into a multiply-add: keep
            # the products exact (tests/test_rotary.py has the argument)
            cos, sin = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (cos, sin))

        def both(form):
            fn = jax.jit(lambda x, dy: (form(x, cos, sin),
                                        jax.vjp(lambda x: form(x, cos, sin), x)[1](dy)[0]))
            return fn.lower(x, dy).compile().as_text(), fn(x, dy)

        text, ours = both(op.rotary)
        _, plain = both(op.plain)
        asserted.append(check(op.takes_kernel(*shape[1:]), f"rotary {name} {list(shape)} takes the kernel"))
        if ctx.platform == "tpu":
            asserted.append(check("rotary_fwd" in text and "rotary_bwd" in text,
                                  f"rotary {name}: both kernels are in the compiled program"))
        for part, a, b in zip(("value", "gradient"), ours, plain):
            asserted.append(check(
                np.array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16)),
                f"rotary {name} {part} at bf16{list(shape)} bit-equal to the plain form"))
    return {"cases": {k: list(v[0]) for k, v in cases.items()}}, asserted


def _slot_sum_check(ctx):
    """ops/slot_sum.slot_sum at two expert cells' training steps (top-8 and
    top-6 slots), over a bfloat16 table of the row bound and readers as the
    routing's sort makes them (a held pair's row, odd or even; every other
    slot empty): weighted with float32 weights and unweighted rounded to
    bfloat16, equal to ``sum_readers`` to the last bit through the kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.decoder import row_bound, sum_readers
    from fedml_tpu.ops import slot_sum as op

    # tokens, top_k, width, experts, held
    cases = {"mellum2_silo2": (4096, 8, 2304, 64, 8), "kanana2_silo2b1": (2048, 6, 2048, 128, 8)}
    if ctx.rehearse:
        cases = {"top_8": (256, 8, 128, 64, 8), "top_6": (128, 6, 128, 32, 4)}
    asserted = []
    for name, (N, top_k, d, experts, held) in cases.items():
        R = row_bound(N * top_k, held, experts)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(ctx.seed + 4), 3)
        expert = jax.random.randint(k1, (N * top_k,), 0, experts)
        inverse = jnp.argsort(jnp.argsort(jnp.minimum(expert, held), stable=True))
        pairs = jnp.sum(expert < held)
        readers = jnp.where(inverse < jnp.minimum(pairs, R), inverse, R).reshape(N, top_k)
        table = jax.random.normal(k2, (R, d), jnp.bfloat16)
        weights = jax.random.uniform(k3, (N, top_k))
        if ctx.rehearse:
            # the CPU contracts the interpreted multiply and add: exact products
            weights = weights.astype(jnp.bfloat16).astype(jnp.float32)
        asserted.append(check(op.takes_kernel(N, top_k, d, R) or ctx.rehearse,
                              f"slot_sum {name} [{N}, {top_k}] over {R} rows takes the kernel"))
        for part, ours, plain in (
                ("weighted", jax.jit(op.slot_sum)(table, readers, weights),
                 jax.jit(sum_readers)(table, readers, weights)),
                ("unweighted", jax.jit(lambda t, r: op.slot_sum(t, r, out_dtype=t.dtype))(table, readers),
                 jax.jit(lambda t, r: sum_readers(t, r).astype(t.dtype))(table, readers))):
            asserted.append(check(
                np.array_equal(np.asarray(ours.astype(jnp.float32)).view(np.uint32),
                               np.asarray(plain.astype(jnp.float32)).view(np.uint32)),
                f"slot_sum {name} {part} ({int(pairs)} live slots) bit-equal to sum_readers"))
    return {"cases": {k: list(v) for k, v in cases.items()}}, asserted


def phase_kernels(ctx):
    """The Pallas kernels, compiled, against their references — alone and
    (robust stats) through the normal CLI path — and the written-out pool
    gradient against flax's."""
    flash, asserted = _flash_check(ctx)
    robust, more = _robust_stats_check(ctx)
    asserted += more
    pool, more = _pool_check(ctx)
    asserted += more
    rotary, more = _rotary_check(ctx)
    asserted += more
    slots, more = _slot_sum_check(ctx)
    asserted += more

    api, rows, _ = run_cli(
        ["--algorithm", "fedavg_robust", "--defense", "trimmed_mean",
         "--num_byzantine", "2", "--model", "cnn", "--dataset",
         "femnist_synth", "--comm_round", "2", "--seed", str(ctx.seed)],
        ctx.out / "cli_robust",
    )
    losses = train_losses(rows)
    asserted.append(
        "fedavg_robust/trimmed_mean: 2 rounds, losses finite "
        f"{[round(x, 4) for x in losses]}"
    )
    if ctx.platform == "tpu":
        _, text = round_program_text(api)
        asserted.append(check(
            "tpu_custom_call" in text,
            "robust round (aggregation) program contains tpu_custom_call",
        ))
    return {"asserted": asserted, "flash": flash, "robust_stats": robust, "max_pool": pool,
            "rotary": rotary, "slot_sum": slots}


def phase_multichip(ctx):
    """``--runtime mesh`` on four chips against the single-device run."""
    import jax
    import numpy as np

    common = [
        "--algorithm", "fedavg", "--model", "cnn", "--dataset",
        "femnist_synth", "--client_num_in_total", "32",
        "--client_num_per_round", "16", "--batch_size", "20", "--lr", "0.1",
        "--epochs", "1", "--comm_round", "2", "--seed", str(ctx.seed),
    ]
    mesh_api, mesh_rows, _ = run_cli(
        [*common, "--runtime", "mesh", "--client_shards", "4"],
        ctx.out / "mesh",
    )
    single_api, single_rows, _ = run_cli(common, ctx.out / "single")
    mesh_losses, single_losses = train_losses(mesh_rows), train_losses(single_rows)

    placed, text = round_program_text(mesh_api)
    shard_devices = {s.device for s in placed[0].addressable_shards}
    asserted = [
        check(len(shard_devices) == 4,
              "cohort batch shards sit on 4 distinct devices: "
              f"{sorted(map(str, shard_devices))}"),
        check("all-reduce" in text, "compiled mesh round contains an all-reduce"),
        check(len(devices_of(single_api.global_vars)) == 1,
              "the single-device run kept its parameters on one device"),
    ]

    def flat(api):
        return np.concatenate([
            np.asarray(l, np.float32).ravel()
            for l in jax.tree_util.tree_leaves(api.global_vars)
        ])

    single, mesh = flat(single_api), flat(mesh_api)
    diff = np.abs(single - mesh)
    # tests/test_sharded_fedavg.py::test_sharded_matches_single_chip holds
    # EVERY parameter to atol = rtol = 1e-5, and on the CPU every parameter
    # meets it. On the chip the two programs tile their convolutions
    # differently (16 clients in one vmap vs 4 per shard), float32 sums
    # round differently, and a pre-activation that lands on the other side
    # of a ReLU moves the few parameters it feeds by one sample's step,
    # lr/B/C * |g| (0.1/20/16 * |g| ~ 3e-4 |g|). So: the round losses must
    # agree, 99% of the parameters must meet the test's tolerance, and
    # none may differ by more than 1e-3. A dropped or mis-weighted client
    # moves every parameter and the loss, and fails all three. (PR 21, four
    # v5e chips: losses identical, 6 of 1 690 046 parameters outside the
    # tolerance, the largest difference 1.64e-5.)
    within = float(np.mean(diff <= 1e-5 + 1e-5 * np.abs(single)))
    stats = {
        "parameters": int(diff.size),
        "max_abs_diff": float(diff.max()),
        "fraction_within_1e-5": within,
        "train_loss_single": single_losses, "train_loss_mesh": mesh_losses,
    }
    print(json.dumps({"phase": "multichip", "comparison": stats}), flush=True)
    asserted += [
        check(np.allclose(mesh_losses, single_losses, rtol=1e-6, atol=0),
              "per-round Train/Loss: mesh == single-device (rtol 1e-6)"),
        check(within >= 0.99,
              f"{within:.4%} of parameters within atol=rtol=1e-5 (need 99%)"),
        check(stats["max_abs_diff"] <= 1e-3,
              f"max abs parameter diff {stats['max_abs_diff']:.3g} <= 1e-3"),
    ]
    return {"asserted": asserted}


# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Ctx:
    seed: int
    rehearse: bool
    platform: str
    out: pathlib.Path
    clock: CompileClock


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip mesh phase and the "
                         "single-device run it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, tiny sizes; prints no result line")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phases", default="",
                    help="comma-separated phases to run, of those the mode has (default: all)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()
    device = {
        "platform": dev[0].platform,
        "kind": dev[0].device_kind,
        "count": len(dev),
    }
    need = 4 if args.multichip else 1
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax found {device}); nothing was run",
              file=sys.stderr)
        return 1
    if len(dev) < need:
        print(f"chip_smoke: need {need} devices, jax found {device}",
              file=sys.stderr)
        return 1

    from fedml_tpu.compile import install_hardened_cache

    cache = install_hardened_cache()

    ctx = Ctx(
        seed=args.seed, rehearse=args.rehearse, platform=device["platform"],
        out=REPO / "chiprun_out"
        / ("chip_smoke_rehearsal" if args.rehearse else "chip_smoke"),
        clock=CompileClock(),
    )

    phases = (
        [("multichip", phase_multichip)]
        if args.multichip
        else [("cli_cnn", phase_cli_cnn), ("store_gather", phase_store_gather),
              ("lm_flagship", phase_lm_flagship), ("decoder", phase_decoder),
              ("kernels", phase_kernels)]
    )
    if args.phases:
        wanted = args.phases.split(",")
        unknown = sorted(set(wanted) - {n for n, _ in phases})
        if unknown:
            ap.error(f"unknown phases {unknown}; have {[n for n, _ in phases]}")
        phases = [(n, fn) for n, fn in phases if n in wanted]
    t_run = time.perf_counter()
    for name, fn in phases:
        mark, t0 = ctx.clock.mark(), time.perf_counter()
        result = fn(ctx)
        hbm = dev[0].memory_stats() or {}  # None on the CPU backend
        print(json.dumps({
            "phase": name, "passed": True,
            "wall_s": round(time.perf_counter() - t0, 2),
            "hbm": {k: hbm.get(k) for k in (
                "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")},
            **ctx.clock.since(mark), **result,
        }), flush=True)
    print(json.dumps({
        "phase": "compile", "cache_dir": str(cache.path),
        "wall_s_total": round(time.perf_counter() - t_run, 2),
        **ctx.clock.since((0, 0)), "hardened_store": cache.stats(),
    }), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "phases": [n for n, _ in phases],
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
