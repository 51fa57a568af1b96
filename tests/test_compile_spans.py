"""Start-up from the inside (ISSUE 36): the sentinel's jax.monitoring
listeners record every program's trace, lowering and compile-or-cache-load
as a finished span under the span that paid for it, ``FedAvgAPI.__init__``
is an ``api_init`` span, and the counters the listeners kept before read
what they read before. CPU, a tiny model, the lazy path (no ``--warmup``)."""

import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from fedml_tpu.algorithms import FedAvgAPI
from fedml_tpu.analysis import sentinel
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.telemetry import TelemetryScope, get_tracer
from fedml_tpu.telemetry.spans import Tracer

_REPO = pathlib.Path(__file__).resolve().parents[1]
JIT_SPANS = ("jit_trace", "jit_lower", "jit_backend")
# what jax calls the round program in each event (``fun_name``)
ROUND_PROGRAM = {
    "jit_trace": "round_fn", "jit_lower": "jit(round_fn)", "jit_backend": "jit(round_fn)",
}


def _api(rows: list) -> FedAvgAPI:
    # widths no other test file uses: the ProgramCache shares programs
    # across the files of one worker, and a shared one would not compile
    data = synthetic_classification(
        num_clients=6, num_classes=5, feat_shape=(13,), samples_per_client=16,
        partition_method="hetero",
    )
    model = ModelDef(
        module=LogisticRegression(num_classes=5), input_shape=(13,), num_classes=5
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=6, client_num_per_round=3, comm_round=2,
            epochs=1, frequency_of_the_test=1,
        ),
        train=TrainConfig(lr=0.05),
    )
    return FedAvgAPI(cfg, data, model, log_fn=rows.append)


class _Run:
    """A fresh API built and trained for two rounds, then trained over the
    same two rounds again, all under a scope of its own: the listener
    records on the calling thread's tracer, which is the scope's."""

    def __init__(self):
        self.scope = TelemetryScope(tenant="compile-spans")
        before = (sentinel.backend_compile_count(), sentinel.persistent_cache_hit_count())
        with self.scope.activate():
            self.api = _api([])
            self.api.train()
            self.first = self.scope.tracer.events()
            self.api.start_round = 0
            self.api.train()
        self.second = self.scope.tracer.events()[len(self.first):]
        self.counted = (
            sentinel.backend_compile_count() - before[0],
            sentinel.persistent_cache_hit_count() - before[1],
        )

    def named(self, name: str, events=None):
        return [e for e in (self.first if events is None else events) if e.name == name]


@pytest.fixture(scope="module")
def run():
    return _Run()


def _inside(child, parent, slack_us: float = 1e3) -> bool:
    return (
        child.ts_us >= parent.ts_us - slack_us
        and child.ts_us + child.dur_us <= parent.ts_us + parent.dur_us + slack_us
    )


@pytest.mark.parametrize("name", JIT_SPANS)
def test_round_programs_first_call_is_spanned_under_local_train(run, name):
    # one per shape class: round 0's here, and round 1's if its cohort
    # bucketed to other steps (a lazy recompile, named with its round)
    of_round = {
        e.attrs["round"]: e for e in run.named(name)
        if e.attrs["program"] == ROUND_PROGRAM[name]
    }
    ev = of_round[0]
    assert ev.attrs["parent"] == "local_train"
    # round -> local_train -> jit_*: never a depth-0 span of the loop
    assert ev.attrs["depth"] == 2
    (parent,) = [e for e in run.named("local_train") if e.attrs["round"] == 0]
    assert ev.dur_us > 0 and _inside(ev, parent)


def test_the_three_phases_of_one_program_follow_each_other(run):
    trace, lower, backend = (
        next(e for e in run.named(n) if e.attrs["program"] == ROUND_PROGRAM[n])
        for n in JIT_SPANS
    )
    assert trace.ts_us < lower.ts_us < backend.ts_us
    assert trace.ts_us + trace.dur_us <= lower.ts_us + lower.dur_us
    assert lower.ts_us + lower.dur_us <= backend.ts_us + backend.dur_us


def test_inner_traces_fire_inside_the_outer_trace(run):
    """Why a reader sums the union of the ``jit_trace`` intervals."""
    outer = next(e for e in run.named("jit_trace") if e.attrs["program"] == "round_fn")
    inner = [
        e for e in run.named("jit_trace")
        if e is not outer and _inside(e, outer, slack_us=0.0)
    ]
    assert inner, "the round program traces no inner jit?"
    assert sum(e.dur_us for e in inner) <= outer.dur_us


def test_backend_span_says_what_the_persistent_cache_did(run):
    for ev in run.named("jit_backend"):
        # the suite persists compiles of 2 s and more: a tiny program is
        # compiled and not written (``off``), or written (``miss``)
        assert ev.attrs["cache"] in ("hit", "miss", "off")
        assert ("retrieval_s" in ev.attrs) == (ev.attrs["cache"] == "hit")
    for name in ("jit_trace", "jit_lower"):
        assert all("cache" not in e.attrs for e in run.named(name))


def test_a_second_train_over_the_same_shapes_records_no_event(run):
    assert run.named("round", run.second), "the second train() ran no round"
    assert [e for e in run.second if e.name in JIT_SPANS] == []


def test_api_init_is_the_parent_of_store_upload_and_carries_the_models_size(run):
    (init,) = run.named("api_init")
    (up,) = run.named("store_upload")
    assert up.attrs["parent"] == "api_init" and up.attrs["depth"] == 1
    assert _inside(up, init, slack_us=0.0)
    assert "round" not in init.attrs and init.attrs["depth"] == 0
    assert init.attrs["params"] == 13 * 5 + 5
    assert init.attrs["param_bytes"] == (13 * 5 + 5) * 4
    assert init.attrs["client_mode"] == run.api._client_mode
    # what model.init and the store made jax compile is beneath it
    under = [e for e in run.first if e.name in JIT_SPANS and e.attrs.get("parent") == "api_init"]
    assert under and all("round" not in e.attrs and _inside(e, init) for e in under)


def test_the_counters_read_what_they_read_before(run):
    backends = run.named("jit_backend")
    hits = [e for e in backends if e.attrs["cache"] == "hit"]
    # one jit_backend span per backend-compile event, one ``hit`` per
    # cache-hit event: process-wide and in the scope's attribution
    assert run.counted == (len(backends), len(hits))
    assert run.scope.backend_compiles == len(backends)
    assert run.scope.persistent_cache_hits == len(hits)
    assert run.scope.recompiles() == len(backends) - len(hits)


def test_listener_is_installed_by_the_api_and_only_once(run):
    assert sentinel._listener_state["installed"] is True
    assert sentinel.ensure_backend_listener() is True
    from jax._src import monitoring as m

    listeners = m.get_event_duration_listeners()
    assert listeners.count(sentinel._on_jax_event) == 1


def test_child_event_takes_parent_depth_and_round_from_the_open_span():
    tracer = Tracer()
    with tracer.span("round", round=7):
        with tracer.span("local_train", round=7):
            ev = tracer.record_child_event("jit_backend", 0.25, program="p")
    assert ev.attrs == {"program": "p", "parent": "local_train", "depth": 2, "round": 7}
    assert ev.dur_us == 0.25e6
    (parent,) = [e for e in tracer.events() if e.name == "local_train"]
    assert abs((ev.ts_us + ev.dur_us) - (parent.ts_us + parent.dur_us)) < 1e5


def test_child_event_outside_any_span_is_no_depth0_span():
    tracer = Tracer()
    ev = tracer.record_child_event("jit_backend", 0.5, program="p")
    assert ev.attrs == {"program": "p"}
    with tracer.span("api_init"):  # a parent without a round gives none
        ev = tracer.record_child_event("jit_trace", 0.0, program="q")
    assert ev.attrs == {"program": "q", "parent": "api_init", "depth": 1}


def test_other_jax_events_record_nothing():
    scope = TelemetryScope(tenant="other-events")
    with scope.activate():
        sentinel._on_jax_event("/jax/core/compile/something_else_duration", 1.0)
        sentinel._on_jax_plain_event("/jax/compilation_cache/tasks_using_cache")
    assert scope.tracer.events() == [] and scope.backend_compiles == 0


def test_the_caches_verdict_is_kept_per_thread_until_the_next_backend_event():
    scope = TelemetryScope(tenant="verdicts")
    with scope.activate():
        before = sentinel.backend_compile_count(), sentinel.persistent_cache_hit_count()
        sentinel._on_jax_plain_event("/jax/compilation_cache/cache_hits")
        sentinel._on_jax_event("/jax/compilation_cache/compile_time_saved_sec", 3.0)
        sentinel._on_jax_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
        sentinel._on_jax_event(
            "/jax/core/compile/backend_compile_duration", 0.6, fun_name="jit(a)")
        sentinel._on_jax_plain_event("/jax/compilation_cache/cache_misses")
        sentinel._on_jax_event(
            "/jax/core/compile/backend_compile_duration", 2.0, fun_name="jit(b)")
        sentinel._on_jax_event(
            "/jax/core/compile/backend_compile_duration", 1.0, fun_name="jit(c)")
    a, b, c = scope.tracer.events()
    assert a.attrs == {"program": "jit(a)", "cache": "hit", "saved_s": 3.0, "retrieval_s": 0.5}
    assert b.attrs == {"program": "jit(b)", "cache": "miss"}
    assert c.attrs == {"program": "jit(c)", "cache": "off"}
    assert (scope.backend_compiles, scope.persistent_cache_hits) == (3, 1)
    assert sentinel.backend_compile_count() - before[0] == 3
    assert sentinel.persistent_cache_hit_count() - before[1] == 1


def test_lazy_probe_of_the_executable_store_is_a_span_of_its_true_length(tmp_path):
    """``_load_serialized`` on the lazy path: the load that replaced a
    compile is a ``compile`` span with a duration, not a marker."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.compile import ProgramCache, install_run_executable_cache

    def prog():
        return ProgramCache().get_or_build(
            "xc-span", {"tag": "compile-spans"}, lambda: jax.jit(lambda x: jnp.sin(x) * 2)
        )

    x = np.arange(16, dtype=np.float32).reshape(4, 4) / 7
    _, restore = install_run_executable_cache(str(tmp_path))
    scope = TelemetryScope(tenant="lazy-probe")
    try:
        with scope.activate():
            prog().warmup(x)  # compiles and exports
            n = len(scope.tracer.events())
            with get_tracer().span("local_train", round=0):
                prog()(x)  # no warmup: the first dispatch probes the store
    finally:
        restore()
    (load,) = [e for e in scope.tracer.events()[n:] if e.name == "compile"]
    assert load.attrs["deserialized"] is True and load.attrs["aot"] is True
    assert load.attrs["parent"] == "local_train" and load.attrs["round"] == 0
    assert load.dur_us > 0


_HIT_PROG = r"""
import json, sys
import jax
from fedml_tpu.compile import install_hardened_cache
install_hardened_cache(sys.argv[1], min_compile_time_secs=0.0)
sys.path.insert(0, sys.argv[2])
from test_compile_spans import _api
from fedml_tpu.telemetry import get_tracer

def build():
    n = len(get_tracer().events())
    api = _api([])
    api.train()
    return [
        (e.attrs["program"], e.attrs["cache"], e.attrs.get("retrieval_s"), e.attrs.get("parent"))
        for e in get_tracer().events()[n:] if e.name == "jit_backend"
    ]

first = build()
jax.clear_caches()
from fedml_tpu.compile import get_program_cache
get_program_cache().reset()
print(json.dumps({"first": first, "second": build()}))
"""


def test_second_build_reads_the_persistent_cache_and_says_so(tmp_path):
    """Two builds in ONE fresh process (a subprocess: reading tiny entries
    back into the suite's own process is what conftest.py keeps out), the
    persistent cache at a temporary directory, jax's in-memory caches
    cleared between: the second build's round program is a ``hit``."""
    out = subprocess.run(
        [sys.executable, "-c", _HIT_PROG, str(tmp_path), str(_REPO / "tests")],
        capture_output=True, text=True, timeout=600, cwd=str(_REPO),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    first = {p: (c, r, parent) for p, c, r, parent in got["first"]}
    second = {p: (c, r, parent) for p, c, r, parent in got["second"]}
    assert first["jit(round_fn)"] == ("miss", None, "local_train")
    cache, retrieval_s, parent = second["jit(round_fn)"]
    assert (cache, parent) == ("hit", "local_train") and retrieval_s > 0
    assert all(c == "hit" for c, _, _ in second.values())
