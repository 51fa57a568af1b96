"""Runtime recompile sentinel — static analysis can only prove a hazard
CLASS absent; this watches the live process for the symptom itself:
unexpected XLA compilation.

Two event streams feed it:

- **backend compiles** — jax's ``/jax/core/compile/backend_compile_duration``
  monitoring events, one per XLA-executable acquisition in the process
  (including lazy recompiles on a new shape class, which the
  ProgramCache never sees), MINUS ``/jax/compilation_cache/cache_hits``
  events: jax wraps the persistent-cache HIT path in the same duration
  event, and a hit deserializes an already-compiled program — it must
  not consume a recompile budget (the zero-cold-start CI gate asserts a
  warm process reports ``compile/recompiles == 0`` on exactly this
  difference). One process-wide listener pair is installed on first use
  and increments global counters plus the
  ``fedml_compile_backend_compiles`` Prometheus gauge; sentinels
  snapshot-diff those counters, so N nested sentinels cost one listener.
- **ProgramCache events** — build/hit/bypass/aot_compile from
  :class:`fedml_tpu.compile.ProgramCache` listeners, recorded with their
  program labels so a budget violation names WHICH programs compiled.

The same listener pair also RECORDS what it hears, as finished spans on
the compiling thread's tracer (``jit_trace``, ``jit_lower``,
``jit_backend``: see :func:`_record_span`), so that a trace shows which
program a span's first call traced, lowered and compiled or loaded, and
under which round. The counters above do not depend on it.

``--recompile_budget N`` on the CLI runs the whole federation under a
sentinel and raises :class:`RecompileBudgetExceeded` at the end when
more than N backend compiles happened — the per-run compile-storm tripwire
(a cache-key instability that recompiles every round burns exactly the
budget this catches). The pytest marker ``@pytest.mark.recompile_budget(N)``
plus the ``recompile_sentinel`` fixture (tests/conftest.py) give tests
the same tripwire. Budgets are deliberately coarse upper bounds: tiny
utility programs (``jnp.ones``, dtype converts) also compile, so a
budget asserts "no storm", not an exact program count."""

from __future__ import annotations

import contextlib
import threading
from typing import List, Optional, Tuple

_BACKEND_EVENT_SUFFIX = "backend_compile_duration"
# jax wraps the WHOLE compile_or_get_cached call — persistent-cache hit
# path included — in the backend_compile_duration event, so a disk hit
# would read as a "recompile". jax emits this companion event on every
# persistent-cache hit; the sentinel subtracts it: a hit deserializes an
# already-compiled program, which is precisely NOT a compile (and is the
# mechanism the zero-cold-start gate asserts compile/recompiles == 0 on).
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# Deliberate residual blind spot: per-round builder churn absorbed by a
# 0-threshold HLO cache subtracts to zero here (retrieval, not
# compilation); it still shows as climbing compile/program_builds in the
# same summary row — docs/ANALYSIS.md "what counts as a compile".

# What jax 0.9 times on the way from a Python function to an executable,
# each as one duration event with the program's ``fun_name``, fired on the
# thread that called the program: the jaxpr trace (inner jits fire inside
# the outer trace's interval: sum the UNION of ``jit_trace`` spans, never
# their durations), the lowering to MLIR, and ``compile_or_get_cached``.
_SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/" + _BACKEND_EVENT_SUFFIX: "jit_backend",
}
# The persistent cache's verdict on the program being acquired arrives on
# the same thread just BEFORE its backend_compile_duration: ``cache_hits``
# with the two durations below, or ``cache_misses`` when the compiled
# program is written (one whose compile is under the persistence threshold
# is never written and fires neither: it reads ``off``, like a process
# with no cache at all, and like there it will compile again next time).
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_ATTR_OF_EVENT = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
# per thread: what the cache said since the last ``jit_backend``
_cache_said = threading.local()

_lock = threading.Lock()
_backend_compiles = 0
_cache_hits = 0
_listener_state = {"installed": None}  # None = not attempted


class RecompileBudgetExceeded(RuntimeError):
    """More XLA compiles happened than the declared budget allows."""


def _record_span(span: str, secs: float, program) -> None:
    """One jax compile-path event as a finished span on the calling
    thread's tracer: it ends at the callback and started ``secs`` earlier,
    under whatever span is open there (``local_train`` of round 0 on the
    lazy path, ``api_init``, a ``compile`` span of ``--warmup``).
    ``jit_backend`` carries the persistent cache's verdict: ``hit`` (with
    the retrieval's seconds and the compile seconds it saved), ``miss``
    (compiled and written) or ``off`` (compiled, nothing read or written)."""
    from fedml_tpu.telemetry import get_tracer

    attrs = {"program": program}
    if span == "jit_backend":
        attrs["cache"] = "off"
        attrs.update(_cache_said.__dict__)
        _cache_said.__dict__.clear()
    get_tracer().record_child_event(span, secs, **attrs)


def _on_jax_event(name: str, secs: float, **kw) -> None:
    global _backend_compiles
    span = _SPAN_OF_EVENT.get(name)
    try:
        if span is not None:
            _record_span(span, secs, kw.get("fun_name"))
        elif name in _CACHE_ATTR_OF_EVENT:
            setattr(_cache_said, _CACHE_ATTR_OF_EVENT[name], float(secs))
    except Exception:  # noqa: BLE001 — telemetry must not break compiles
        pass
    if not name.endswith(_BACKEND_EVENT_SUFFIX):
        return
    # per-tenant attribution (fedml_tpu/serve/): jax.monitoring fires on
    # the COMPILING thread, so the telemetry scope active there names the
    # tenant whose dispatch triggered this compile — the counter a
    # co-tenant session's compile/recompiles == 0 gate reads
    from fedml_tpu.telemetry.scope import current_scope

    sc = current_scope()
    with _lock:
        _backend_compiles += 1
        total = _backend_compiles
        if sc is not None:
            sc.backend_compiles += 1
    try:
        from fedml_tpu.telemetry import get_global_registry

        # process total → the GLOBAL registry always (a tenant registry
        # must not carry a process-wide gauge under a tenant label)
        get_global_registry().gauge(
            "fedml_compile_backend_compiles",
            "XLA backend compilations observed in this process",
        ).set(total)
    except Exception:  # noqa: BLE001 — telemetry must not break compiles
        pass


def _on_jax_plain_event(name: str, **kw) -> None:
    global _cache_hits
    if name == _CACHE_MISS_EVENT:
        _cache_said.cache = "miss"
        return
    if name != _CACHE_HIT_EVENT:
        return
    _cache_said.cache = "hit"
    from fedml_tpu.telemetry.scope import current_scope

    sc = current_scope()
    with _lock:
        _cache_hits += 1
        if sc is not None:
            sc.persistent_cache_hits += 1


def ensure_backend_listener() -> bool:
    """Install the process-wide jax.monitoring listeners (idempotent).
    Returns False when this jax has no monitoring API — the sentinel
    then degrades to ProgramCache-event counting."""
    if _listener_state["installed"] is not None:
        return _listener_state["installed"]
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _listener_state["installed"] = True
        try:
            # persistent-cache hit events (see _CACHE_HIT_EVENT) — best
            # effort: without them the sentinel merely OVER-counts, which
            # keeps every budget a valid upper bound
            jax.monitoring.register_event_listener(_on_jax_plain_event)
        except Exception:  # noqa: BLE001 — older monitoring API
            pass
    except Exception:  # noqa: BLE001 — jaxlib without monitoring support
        _listener_state["installed"] = False
    return _listener_state["installed"]


def backend_compile_count() -> int:
    """Process-lifetime XLA backend compile count (0 until the listener
    is installed by the first sentinel)."""
    with _lock:
        return _backend_compiles


def persistent_cache_hit_count() -> int:
    """Process-lifetime persistent-compile-cache hit count (each one is
    wrapped in a backend-compile event by jax and must be discounted)."""
    with _lock:
        return _cache_hits


def global_recompiles() -> int:
    """Process-lifetime ACTUAL compiles: backend-compile events minus
    persistent-cache hits (a hit deserializes an already-compiled
    program — not a compile). The ONE definition of "recompile" for
    unscoped consumers, mirroring ``TelemetryScope.recompiles`` for the
    scoped case."""
    with _lock:
        return max(0, _backend_compiles - _cache_hits)


class RecompileSentinel:
    """Snapshot-diff watcher over a region of execution.

    >>> s = RecompileSentinel(budget=8, label="parity").start()
    >>> ...  # run rounds
    >>> s.stop(); s.check()   # raises RecompileBudgetExceeded on a storm
    """

    def __init__(self, budget: Optional[int] = None, label: str = "run"):
        self.budget = budget if budget is None else int(budget)
        self.label = label
        self._start_backend = 0
        self._stop_backend: Optional[int] = None
        self._start_hits = 0
        self._stop_hits: Optional[int] = None
        self._events: List[Tuple[str, str]] = []  # (kind, program label)
        self._active = False
        self._have_monitoring = False
        self._cache = None  # the ProgramCache this sentinel subscribed to

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "RecompileSentinel":
        if self._active:
            return self
        self._have_monitoring = ensure_backend_listener()
        self._start_backend = backend_compile_count()
        self._start_hits = persistent_cache_hit_count()
        from fedml_tpu.compile import get_program_cache

        # remember WHICH cache we subscribed to: a use_program_cache swap
        # between start and stop must not leak the listener
        self._cache = get_program_cache()
        self._cache.add_listener(self._on_cache_event)
        self._active = True
        return self

    def stop(self) -> "RecompileSentinel":
        if not self._active:
            return self
        self._stop_backend = backend_compile_count()
        self._stop_hits = persistent_cache_hit_count()
        if self._cache is not None:
            self._cache.remove_listener(self._on_cache_event)
            self._cache = None
        self._active = False
        return self

    def __enter__(self) -> "RecompileSentinel":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _on_cache_event(self, kind: str, label: str, digest) -> None:
        if kind in ("build", "bypass", "aot_compile"):
            self._events.append((kind, label))

    # -- accounting --------------------------------------------------------

    def recompiles(self) -> int:
        """ACTUAL XLA compilations observed since start(): backend-compile
        events minus persistent-cache hits — jax wraps the cache-HIT path
        in the same event, and a hit deserializes an already-compiled
        program (the zero-cold-start gate asserts exactly this difference
        is 0 in a warm process). Falls back to ProgramCache build/aot
        events when jax.monitoring is absent — NOT bypass events:
        wrap_uncached wrappers compile nothing, so they must not consume
        the budget."""
        if self._have_monitoring:
            end = (
                self._stop_backend
                if self._stop_backend is not None
                else backend_compile_count()
            )
            hits_end = (
                self._stop_hits
                if self._stop_hits is not None
                else persistent_cache_hit_count()
            )
            return max(
                0,
                (end - self._start_backend) - (hits_end - self._start_hits),
            )
        return sum(1 for k, _ in self._events if k in ("build", "aot_compile"))

    def events(self) -> List[Tuple[str, str]]:
        return list(self._events)

    def exceeded(self) -> bool:
        return self.budget is not None and self.recompiles() > self.budget

    def describe(self) -> str:
        n = self.recompiles()
        labels = ", ".join(
            f"{kind}:{label}" for kind, label in self._events[:12]
        ) or "no ProgramCache builds — lazy shape-class recompiles"
        budget = "∞" if self.budget is None else str(self.budget)
        return (
            f"recompile sentinel [{self.label}]: {n} XLA compile(s) "
            f"(budget {budget}); program-cache events: {labels}"
        )

    def check(self) -> None:
        if self.exceeded():
            raise RecompileBudgetExceeded(self.describe())

    def summary_row(self) -> dict:
        """Flat MetricsLogger row — summary.json stays the CI oracle for
        the recompile budget, not just the raised exception."""
        row = {
            "compile/recompiles": self.recompiles(),
            "compile/program_builds": sum(
                1 for k, _ in self._events if k == "build"
            ),
            "compile/program_bypasses": sum(
                1 for k, _ in self._events if k == "bypass"
            ),
        }
        if self.budget is not None:
            row["compile/recompile_budget"] = self.budget
        return row


@contextlib.contextmanager
def watch_recompiles(budget: Optional[int] = None, label: str = "region"):
    """Context-manager form: stop + budget-check on clean exit (an
    exception from the body propagates untouched — the sentinel never
    masks the real failure)."""
    sentinel = RecompileSentinel(budget=budget, label=label).start()
    try:
        yield sentinel
    except BaseException:
        sentinel.stop()
        raise
    sentinel.stop()
    sentinel.check()
