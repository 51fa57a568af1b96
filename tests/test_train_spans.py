"""The train loop measures itself (ISSUE 25): the host spans and counts
``FedAvgAPI.train()`` emits at its layer boundaries, the tracer's annotation
hook, and the names the device programs and their scopes carry."""

import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms import FedAvgAPI
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.telemetry import TelemetryScope
from fedml_tpu.telemetry.spans import Tracer

ROUNDS = 5
EVAL_EVERY = 2
EVAL_ROUNDS = [0, 2, 4]  # r % 2 == 0, and the last round


def _api(pipeline: str, rows: list) -> FedAvgAPI:
    data = synthetic_classification(
        num_clients=12, num_classes=4, feat_shape=(8,), samples_per_client=24,
        partition_method="hetero",
    )
    model = ModelDef(
        module=LogisticRegression(num_classes=4), input_shape=(8,), num_classes=4
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=12, client_num_per_round=4, comm_round=ROUNDS,
            epochs=1, frequency_of_the_test=EVAL_EVERY, pipeline=pipeline,
        ),
        train=TrainConfig(lr=0.05),
    )
    return FedAvgAPI(cfg, data, model, log_fn=rows.append)


class _Run:
    """One short ``train()`` on a tracer of its own, with a recording
    ``_place_batch`` and a recording annotation factory underneath."""

    def __init__(self, pipeline: str):
        self.rows: list = []
        self.placed: list = []  # (slots, real samples) per _place_batch call
        self.annotations: list = []  # [name, round, entered, left]
        # a scope of its own: the API, its scheduler and its fault injector
        # all take this run's tracer, and the process tracer stays untouched
        scope = TelemetryScope(tenant=f"spans-{pipeline}")
        with scope.activate():
            api = _api(pipeline, self.rows)
        tracer = scope.tracer
        assert api._tracer is tracer
        inner = api._place_batch

        def recording_place(batch, *a, **k):
            self.placed.append(
                (int(np.prod(batch.mask.shape)), float(np.sum(batch.num_samples)))
            )
            return inner(batch, *a, **k)

        api._place_batch = recording_place
        run = self

        class Annotation:
            def __init__(self, name, round_idx):
                self.row = [name, round_idx, 0, 0]
                run.annotations.append(self.row)

            def __enter__(self):
                self.row[2] += 1

            def __exit__(self, *exc):
                self.row[3] += 1

        tracer.annotate = Annotation
        api.train()
        self.api = api
        self.events = tracer.events()

    def named(self, name: str):
        return [e for e in self.events if e.name == name]


@pytest.fixture(scope="module")
def piped():
    return _Run("auto")


@pytest.fixture(scope="module")
def serial():
    return _Run("off")


@pytest.mark.parametrize("name", ["round", "broadcast", "local_train", "pack"])
def test_every_round_has_exactly_one(piped, name):
    assert sorted(e.attrs["round"] for e in piped.named(name)) == list(range(ROUNDS))


def test_one_prepare_for_every_round_but_the_first(piped):
    # round r is prepared while round r-1 runs; nothing prepares round 0,
    # and nothing is prepared past the horizon
    prepares = piped.named("prepare")
    assert [e.attrs["round"] for e in prepares] == list(range(1, ROUNDS))
    assert all(e.attrs["depth"] == 0 for e in prepares)
    assert piped.api.pipeline_rounds == ROUNDS - 1


@pytest.mark.parametrize("child", ["select", "stack", "place"])
def test_prepare_owns_select_stack_place_of_the_prepared_round(piped, child):
    beneath = [e for e in piped.named(child) if e.attrs.get("parent") == "prepare"]
    assert [e.attrs["round"] for e in beneath] == list(range(1, ROUNDS))
    for e in beneath:
        prepare = next(p for p in piped.named("prepare")
                       if p.attrs["round"] == e.attrs["round"])
        assert prepare.ts_us <= e.ts_us
        assert e.ts_us + e.dur_us <= prepare.ts_us + prepare.dur_us + 1.0


def test_select_only_on_a_memo_miss_and_names_who_paid(piped):
    selects = piped.named("select")
    # one per round: round 0's inside its own round, the others a round early
    assert sorted(e.attrs["round"] for e in selects) == list(range(ROUNDS))
    assert {e.attrs["round"]: e.attrs["parent"] for e in selects} == {
        0: "round", **{r: "prepare" for r in range(1, ROUNDS)}
    }
    # the scheduler names its policy on the caller's span and adds no second one
    assert all(e.attrs["policy"] == "uniform" and e.attrs["clients"] == 4
               for e in selects)


def test_stack_and_select_carry_their_counts(piped):
    for e in piped.named("stack"):
        assert e.attrs["bs"] == 8 and e.attrs["steps"] >= 1
    for e in piped.named("health"):
        assert e.attrs["clients"] == 4
        assert e.attrs["first_round"] == e.attrs["last_round"]
    assert [e.attrs["first_round"] for e in piped.named("health")] == list(range(ROUNDS))


def test_flush_holds_flush_wait_and_eval_on_evaluation_rounds(piped):
    flushes = piped.named("flush")
    assert [e.attrs["last_round"] for e in flushes] == EVAL_ROUNDS
    assert [e.attrs["first_round"] for e in flushes] == [0, 1, 3]
    assert [e.attrs["rows"] for e in flushes] == [1, 2, 2]
    for name in ("flush_wait", "eval"):
        beneath = piped.named(name)
        assert len(beneath) == len(flushes)
        for f, e in zip(flushes, beneath):
            assert e.attrs["parent"] == "flush" and e.attrs["depth"] == 1
            assert f.ts_us <= e.ts_us
            assert e.ts_us + e.dur_us <= f.ts_us + f.dur_us + 1.0
    assert [e.attrs["rows"] for e in piped.named("flush_wait")] == [1, 2, 2]
    assert [e.attrs["round"] for e in piped.named("eval")] == EVAL_ROUNDS


def test_depth0_spans_are_the_loop_and_do_not_overlap(piped):
    top = sorted((e for e in piped.events if e.attrs.get("depth") == 0),
                 key=lambda e: e.ts_us)
    # set-up's one span comes first and is no part of the loop
    assert top[0].name == "api_init"
    top = top[1:]
    assert {e.name for e in top} == {"round", "pack", "prepare", "health", "flush"}
    for a, b in zip(top, top[1:]):
        # start and duration come from two clocks (epoch anchor, perf
        # counter): a microsecond of slack
        assert a.ts_us + a.dur_us <= b.ts_us + 1.0, (a, b)
    # every other span sits beneath one of them
    assert all("parent" in e.attrs for e in piped.events if e.attrs.get("depth", 0) > 0)


def test_store_upload_says_what_the_device_holds(piped):
    (up,) = piped.named("store_upload")
    store = piped.api._store
    assert "round" not in up.attrs  # set-up: it works for no round
    assert up.attrs["rows"] == 12 * 24 == store.flat_x.shape[0]
    # 8 floats a sample, held as one 128-lane row
    assert up.attrs["row_bytes"] == 128 * 4
    assert store.flat_x.shape == (288, 128)
    assert up.attrs["resident_bytes"] == store.resident_bytes == 288 * (128 * 4 + 4)


@pytest.mark.parametrize("run_name", ["piped", "serial"])
def test_place_counts_equal_what_place_batch_saw(run_name, request):
    run = request.getfixturevalue(run_name)
    places = run.named("place")
    assert len(places) == len(run.placed) == ROUNDS
    assert sum(e.attrs["slots"] for e in places) == sum(s for s, _ in run.placed)
    assert sum(e.attrs["real_samples"] for e in places) == sum(r for _, r in run.placed)
    assert all(0 < e.attrs["real_samples"] <= e.attrs["slots"] for e in places)


def test_broadcast_says_whether_its_batch_was_prepared(piped):
    said = {e.attrs["round"]: e.attrs["prepared"] for e in piped.named("broadcast")}
    assert said == {0: False, **{r: True for r in range(1, ROUNDS)}}


def test_pipeline_off_broadcast_owns_stack_and_place(serial):
    assert not serial.named("prepare")
    assert all(e.attrs["prepared"] is False for e in serial.named("broadcast"))
    for child in ("stack", "place"):
        beneath = serial.named(child)
        assert [e.attrs["round"] for e in beneath] == list(range(ROUNDS))
        assert all(e.attrs["parent"] == "broadcast" and e.attrs["depth"] == 2
                   for e in beneath)
    # the round itself pays for selection
    assert all(e.attrs["parent"] == "round" for e in serial.named("select"))


def test_pipeline_overlap_is_the_prepare_spans_duration(piped):
    by_round = {e.attrs["round"]: e for e in piped.named("round")}
    for p in piped.named("prepare"):
        assert by_round[p.attrs["round"]].attrs["overlap_s"] == round(p.dur_us / 1e6, 6)
    assert "overlap_s" not in by_round[0].attrs


@pytest.mark.parametrize("run_name", ["piped", "serial"])
def test_rows_no_longer_carry_the_dispatch_time(run_name, request):
    rows = [r for r in request.getfixturevalue(run_name).rows if "Train/Loss" in r]
    assert [r["round"] for r in rows] == list(range(ROUNDS))
    assert all(set(r) <= {"round", "Train/Loss", "Train/Acc", "Test/Loss", "Test/Acc"}
               for r in rows)
    assert [r["round"] for r in rows if "Test/Loss" in r] == EVAL_ROUNDS


def test_pipelined_and_serial_runs_log_the_same_rows(piped, serial):
    assert [r for r in piped.rows if "Train/Loss" in r] == [
        r for r in serial.rows if "Train/Loss" in r]


def test_annotation_entered_and_left_once_per_span(piped):
    # set-up's api_init and store_upload ran before this test put its hook
    # in; what jax's compile events leave (jit_*) is recorded when it is
    # over, and never entered
    entered = [e for e in piped.events if not e.name.startswith("jit_")]
    assert len(piped.annotations) == len(entered) - 2
    assert all(entered == 1 and left == 1 for _, _, entered, left in piped.annotations)
    # the identifier is the round the span works for, where it has one
    seen = {(name, r) for name, r, *_ in piped.annotations}
    assert ("prepare", 1) in seen and ("round", 0) in seen and ("flush_wait", None) in seen


def test_annotation_left_on_an_exception_and_not_used_by_handle_spans():
    tracer = Tracer()
    log = []

    class Annotation:
        def __init__(self, name, round_idx):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc[0]))

    tracer.annotate = Annotation
    with pytest.raises(KeyError):
        with tracer.span("outer", round=1):
            with tracer.span("inner", round=1):
                raise KeyError("boom")
    assert log == [("enter", "outer"), ("enter", "inner"),
                   ("exit", "inner", KeyError), ("exit", "outer", KeyError)]
    assert [e.name for e in tracer.events()] == ["inner", "outer"]
    assert tracer.current_span() is None
    # a handle span may end on another thread: it is not mirrored
    tracer.start_span("handle").end()
    assert len(log) == 4


def test_span_keeps_its_duration_after_it_ends():
    tracer = Tracer()
    with tracer.span("x") as sp:
        assert sp.dur_us is None
    assert sp.dur_us == tracer.events()[0].dur_us


def test_fedavg_api_installs_the_profiler_annotation():
    from fedml_tpu.telemetry import get_tracer
    from fedml_tpu.utils.profiling import span_annotation

    _api("auto", [])
    assert get_tracer().annotate is span_annotation
    for args in (("round", 3), ("flush_wait", None)):
        with span_annotation(*args):  # a no-op while no profile runs
            pass


def test_scheduler_marks_selection_itself_when_nobody_times_it():
    from fedml_tpu.scheduler import ClientScheduler

    tracer = Tracer()
    cfg = _api("off", []).config
    sched = ClientScheduler.from_config(cfg, num_clients=12, tracer=tracer)
    sched.select(0)
    with tracer.span("select", round=1):
        sched.select(1)
    marks = tracer.events()
    assert [(e.name, e.attrs["round"], e.attrs["policy"]) for e in marks] == [
        ("select", 0, "uniform"), ("select", 1, "uniform")]


def _lowered_text(fn, args) -> str:
    """The lowered module with its debug locations: each is the name stack
    that becomes the HLO instruction's ``op_name``. Lowers, compiles nothing."""
    return fn.lower(*args).as_text(debug_info=True)


def _op_names(text: str) -> set:
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize(
    "scope", ["local_train", "aggregate", "round_metrics", "forward_backward",
              "optimizer_update", "keep_gate"])
def test_round_program_names_its_scopes(piped, scope):
    fn, args = piped.api.round_program(0)
    text = _lowered_text(fn, args)
    assert re.search(r"module @jit_round_fn\b", text)
    assert any(scope in name.split("/") for name in _op_names(text)), scope


@pytest.mark.parametrize("scope", ["gather", "mask_pad"])
def test_gather_program_is_named_and_scoped(piped, scope):
    store = piped.api._store
    idx, mask, steps, bs, _ = store.round_indices([0, 1], 8, seed=0)
    text = _lowered_text(store.gather_program(steps, bs),
                     (store.flat_x, store.flat_y, jax.numpy.asarray(idx),
                      jax.numpy.asarray(mask)))
    assert re.search(r"module @jit_device_store_gather\b", text)
    assert any(scope in name.split("/") for name in _op_names(text)), scope


def test_eval_program_is_named_and_scoped(piped):
    text = _lowered_text(piped.api.eval_fn, (piped.api.global_vars, *piped.api._eval_batches()))
    assert re.search(r"module @jit_eval_fn\b", text)
    assert any("eval" in name.split("/") for name in _op_names(text))


def test_lm_head_and_attention_are_told_apart_in_op_names():
    from fedml_tpu.models import create_model

    lm = create_model("transformer", "random_tokens", (16,), 31,
                      num_layers=1, num_heads=2, embed_dim=16)
    variables = lm.init(jax.random.PRNGKey(0))
    tokens = jax.numpy.zeros((2, 16), jax.numpy.int32)
    text = _lowered_text(jax.jit(lambda v, t: lm.apply(v, t, train=False)[0]),
                     (variables, tokens))
    dots = [n for n in _op_names(text) if n.endswith("dot_general")]
    assert any("/head/" in n for n in dots)
    assert any("/block0/attention/" in n for n in dots)
    assert any("/block0/qkv/" in n for n in dots)


def test_telemetry_still_imports_without_jax():
    code = ("import sys; import fedml_tpu.telemetry, fedml_tpu.telemetry.spans; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], timeout=120).returncode == 0
