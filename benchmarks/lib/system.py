"""The system under test, built from a cell's files. The one module of the
benchmark that imports the program: it takes the program's entry point
(``FedAvgAPI`` and its ``train()``), its spans and its compile cache, and
nothing that measures."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from . import feed as feed_mod

ROOT = pathlib.Path(__file__).resolve().parents[1]


def load_system(algorithm: str, runtime: str) -> dict:
    path = ROOT / "systems" / f"{algorithm}.{runtime}.json"
    if not path.exists():
        raise FileNotFoundError(
            f"no system file {path.name}: add benchmarks/systems/{path.name} "
            "naming the program's API class for this algorithm and runtime"
        )
    return json.loads(path.read_text())


def load_round_reference(cell: dict):
    """The plain reference of the cell's round, the module under ``lib/``
    that its system file names (``follow``, ``task_loss``, ``Ops``)."""
    name = load_system(cell["algorithm"], cell["runtime"])["reference"]
    return importlib.import_module(f"benchmarks.lib.{name}")


def install_compile_cache() -> str:
    """Point the program's (hardened) persistent compile cache at its one
    resolver's answer: $JAX_COMPILATION_CACHE_DIR when set, else the fixed
    ``<checkout>/.jax_cache``. Every program is persisted, however short its
    compile, so that a later run of the cell compiles nothing."""
    from fedml_tpu.compile import install_hardened_cache

    return str(install_hardened_cache(min_compile_time_secs=0.0).path)


class CompileCounter:
    """Counts XLA backend-compile events (persistent-cache retrievals are
    wrapped in the same event, and count: inside a window both mean that a
    shape was not warmed)."""

    def __init__(self):
        import jax.monitoring

        self.events = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.events += 1
            self.seconds += float(secs)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.events, self.seconds, self.hits)

    def since(self, mark):
        return {
            "compiles": self.events - mark[0],
            "compile_s": self.seconds - mark[1],
            "cache_hits": self.hits - mark[2],
        }


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, leaf in flat.items():
        node = tree
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def flat_params(variables) -> dict:
    """The program's ``{"params": ...}`` tree as {"a/b/c": leaf}."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(variables["params"])[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return out


def build(model_cfg: dict, cell: dict, feed, seed: int, ref, rows: list):
    """The cell's API object, holding the feed's population and the
    reference's seed weights, ready for ``run_rounds``."""
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.models import create_model

    system = load_system(cell["algorithm"], cell["runtime"])
    mod, cls = system["api"].split(":")
    api_cls = getattr(importlib.import_module(mod), cls)

    m = model_cfg["model"]
    model = create_model(
        m["name"], m["dataset"], tuple(m["input_shape"]), int(m["num_classes"]),
        **m.get("kwargs", {}),
    )
    want = {k: tuple(v) for k, v in ref.param_shapes(model_cfg).items()}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if set(shapes) != {"params"}:
        raise ValueError(f"the model holds more than parameters: {sorted(shapes)}")
    have = {k: tuple(v.shape) for k, v in flat_params(shapes).items()}
    if have != want:
        diff = sorted(set(have.items()) ^ set(want.items()))
        raise ValueError(f"the program's parameters and the reference's differ: {diff[:6]}")

    # The seed's weights enter as a checkpoint would: the model's init
    # returns them (models/registry._with_pretrained does the same).
    seeded = dataclasses.replace(model)
    seeded.init = lambda rng: {"params": _nest(ref.init_params(seed, model_cfg))}

    cx, cy = feed.client_shards()
    data = FederatedDataset(
        name=m["dataset"], client_x=cx, client_y=cy,
        test_x=feed.test_x, test_y=feed.test_y, num_classes=int(m["num_classes"]),
    )
    cfg = RunConfig(
        data=DataConfig(dataset=m["dataset"], batch_size=int(cell["batch_size"]), pad_bucket=1),
        fed=FedConfig(
            client_num_in_total=feed.n_clients,
            client_num_per_round=int(cell["clients_per_round"]),
            comm_round=1, epochs=int(cell["epochs"]),
            frequency_of_the_test=int(cell["eval_every"]),
        ),
        train=TrainConfig(**model_cfg["train"]),
        model=m["name"], seed=feed_mod.program_seed(seed),
    )
    return api_cls(cfg, data, seeded, task=model_cfg["task"], log_fn=rows.append)


def run_rounds(api, first: int, end: int):
    """Rounds [first, end) as ONE call of ``train()``, synchronised: returns
    once the last round's parameters are on the device and the last flush
    has returned. The horizon is set from outside, as a resumed run's is."""
    fed = dataclasses.replace(api.config.fed, comm_round=int(end))
    api.config = dataclasses.replace(api.config, fed=fed)
    api.start_round = int(first)
    api.train()
    jax.block_until_ready(api.global_vars)


@jax.jit
def _norms(new: dict, old: dict):
    return {
        k: jnp.sqrt(jnp.sum(jnp.square(new[k].astype(jnp.float32) - old[k].astype(jnp.float32))))
        for k in new
    }


def change_norms(api, params0: dict) -> dict:
    """Per-leaf norm of the parameters' change from ``params0``."""
    return {k: float(v) for k, v in _norms(flat_params(api.global_vars), params0).items()}


def follow(api, params0: dict, followed: int):
    """Drive the followed rounds as the horizons [0, 1) and [1, followed):
    (change norms after round 0, after the last, seconds of the first)."""
    t = time.perf_counter()
    run_rounds(api, 0, 1)
    first_s = time.perf_counter() - t
    norms_first = change_norms(api, params0)
    run_rounds(api, 1, followed)
    return norms_first, change_norms(api, params0), first_s


class PlacementLog:
    """What the program places on the device for each round: the sample
    slots of the stacked cohort batch it built (the shape of its mask) and
    the real samples it says are in them, read from the batch handed to
    ``_place_batch``. Shapes and host numbers only: nothing waits for the
    device."""

    def __init__(self, api):
        self.placed = []  # (sample slots, real samples) per placement
        inner = api._place_batch

        def wrapped(batch, *a, **k):
            slots = 1
            for d in batch.mask.shape:
                slots *= int(d)
            self.placed.append((slots, float(sum(batch.num_samples))))
            return inner(batch, *a, **k)

        api._place_batch = wrapped

    def since(self, mark: int):
        """(sample slots, real samples) placed from placement ``mark`` on,
        or None where nothing was placed."""
        rows = self.placed[mark:]
        if not rows:
            return None
        return sum(r[0] for r in rows), sum(r[1] for r in rows)


def device_memory(devices) -> dict:
    """``memory_stats()`` of the fullest device (by what it holds now)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(stats, key=lambda s: s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0))


def live_programs(device) -> list:
    """Every program the process holds loaded on the device, largest
    temporaries first: (module name, bytes of temporaries, of arguments, of
    outputs, of generated code) as the compiler planned them
    (``get_compiled_memory_stats``). Asks the runtime; compiles nothing."""
    out = []
    for exe in device.client.live_executables():
        stats = exe.get_compiled_memory_stats()
        modules = exe.hlo_modules()
        out.append((
            modules[0].name if modules else "?",
            int(stats.temp_size_in_bytes), int(stats.argument_size_in_bytes),
            int(stats.output_size_in_bytes), int(stats.generated_code_size_in_bytes),
        ))
    return sorted(out, key=lambda row: -row[1])


class SpanLog:
    """The benchmark's own spans round calls into the program's layers
    (``_pipeline_prepare``, ``_flush_pending``, ``_log_round``), on the
    program tracer's clock, and mirrored into the profiler's trace when one
    is running (an annotation costs about a microsecond when none is)."""

    def __init__(self, tracer):
        self.tracer = tracer
        self.spans = []  # (name, start_us, end_us)

    def wrap(self, obj, method: str, name: str):
        inner = getattr(obj, method)

        def wrapped(*a, **k):
            t0 = self.tracer.now_us()
            with jax.profiler.TraceAnnotation(name):
                out = inner(*a, **k)
            self.spans.append((name, t0, self.tracer.now_us()))
            return out

        setattr(obj, method, wrapped)


def program_spans(tracer, since_us: float):
    """The program's finished spans that started at or after ``since_us``."""
    return [
        (e.name, e.ts_us, e.ts_us + e.dur_us, dict(e.attrs))
        for e in tracer.events() if e.ts_us >= since_us
    ]


def get_tracer():
    from fedml_tpu.telemetry import get_tracer as _g

    return _g()


def schedule_of(api) -> str:
    return getattr(api, "_client_mode", "?")
