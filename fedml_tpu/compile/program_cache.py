"""In-process program deduplication + AOT warmup handles.

The XLA jit cache is keyed by the *jit object*: two factories that build
byte-identical round functions still compile twice, because each
``jax.jit`` call wraps a fresh closure. Every algorithm family here
builds its round/eval/train programs through small factories, and a full
test run constructs hundreds of such factories over a handful of model ×
config shapes — so a cold tier-1 run used to spend the bulk of its
budget recompiling near-identical small programs (ROADMAP timeout note).

:class:`ProgramCache` closes that gap: factories describe the program's
static determinants (see :mod:`fedml_tpu.compile.digest`) and get back a
process-wide shared :class:`CachedProgram` — one jit object, one compile
per (program structure, shape class) per process. Factories handed
opaque callables (custom ``local_train_fn``, defense hooks) must bypass
the registry via :meth:`ProgramCache.wrap_uncached`; a digest that
over-merged two different programs would be a silent-wrong-numerics bug,
so the keying is deliberately conservative.

:class:`CachedProgram` is also the AOT warmup surface:
``prog.warmup(*args)`` runs ``jit(...).lower(...).compile()`` ahead of
round 0 (emitting a ``compile`` telemetry span + XLA cost analysis) and
keeps the compiled executable; subsequent calls whose abstract signature
matches dispatch straight to it, so the warmup compile IS the run's
compile — warm runs are numerically identical to cold runs because the
executable is built from the exact same lowering either way."""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from fedml_tpu.compile.digest import call_signature, program_digest
from fedml_tpu.telemetry import get_registry, get_tracer


def _device_pin_token() -> Optional[tuple]:
    """The thread-local ``jax.default_device`` pin as a signature token,
    or None when the thread is unpinned.

    Tenant placement (fedml_tpu/serve/placement.py) pins each tenant's
    threads to a device slice, but XLA executables are compiled PER
    DEVICE — the AOT dispatch map and the persistent executable store
    are keyed by abstract call signature, which is device-blind. Without
    this token a tenant pinned to device 3 could adopt a co-tenant's (or
    a predecessor process's) executable committed to device 0 and
    silently dispatch there, defeating the placement. Pinned threads
    therefore fold the pin into the signature; unpinned threads (every
    single-run path, the whole pre-placement world) keep signatures —
    and on-disk executable keys — byte-identical to every historical
    run."""
    try:
        import jax

        d = jax.config.jax_default_device
    except Exception:  # noqa: BLE001 — jax-free/old-jax contexts
        return None
    if d is None:
        return None
    return ("__device__", getattr(d, "platform", str(d)),
            int(getattr(d, "id", -1)))


def _pinned_signature(args) -> tuple:
    sig = call_signature(args)
    pin = _device_pin_token()
    return sig if pin is None else sig + (pin,)


class CachedProgram:
    """A jit-compiled program handle: callable, lowerable, warmable.

    Transparent stand-in for the wrapped ``jax.jit`` object at every call
    site (``__call__``/``lower`` forward to it). After :meth:`warmup`,
    calls whose signature matches a warmed executable dispatch to the AOT
    executable directly; anything else (different shape class, kwargs,
    sharding mismatch) falls back to the ordinary jit dispatch path."""

    def __init__(
        self,
        fn: Callable,
        label: str,
        digest: Optional[str] = None,
        cache: Optional["ProgramCache"] = None,
        key_fields: Optional[Dict[str, Any]] = None,
    ):
        self.fn = fn
        self.label = label
        self.digest = digest
        # The exact key_fields dict this program was registered under —
        # introspection surface for the digest-completeness fuzzer
        # (fedml_tpu/analysis/digest_audit.py recomputes digests with
        # fields deliberately dropped to prove the audit catches the
        # scaffold eta_g bug class). None for bypassed programs.
        self.key_fields = key_fields
        self._cache = cache
        self._aot: Dict[tuple, Any] = {}
        self._aot_stats: Dict[tuple, dict] = {}
        # signatures already probed against the persistent executable
        # store (executable_cache.py) — each shape class pays at most one
        # disk lookup, hit or miss
        self._exec_probed: set = set()
        # lazy-probe circuit breaker: every dispatch that computes a
        # signature while NOTHING has been adopted burns one unit —
        # probed-miss or repeat call alike — so after a few calls an
        # installed-but-empty store stops taxing the hot path with
        # per-call tree_flatten (e.g. the whole test suite under the
        # conftest session store). warmup() still probes regardless, and
        # any adoption re-arms the signature path via the non-empty _aot.
        self._exec_probe_budget = 4

    def _exec_cache(self):
        """The installed persistent executable store, when this program
        is eligible for it (a canonical digest is the cross-process half
        of the key — bypassed/opaque programs have none and never
        persist)."""
        if self.digest is None:
            return None
        from fedml_tpu.compile.executable_cache import (
            installed_executable_cache,
        )

        return installed_executable_cache()

    def _load_serialized(self, sig, tracer=None):
        """Try to adopt a persisted executable for ``sig``; returns its
        stats row or None. On a hit the executable enters the same AOT
        dispatch map warmup fills, so a warm-from-disk run takes exactly
        the dispatch path a warm-in-process run takes (byte-identical
        numerics — the executable IS the one a compile would build,
        pinned by tests/test_compile.py). The load that replaced a compile
        is a ``compile`` span (``deserialized=True``) of its true length,
        on the warmup and the lazy-probe path alike; a miss leaves none."""
        cache = self._exec_cache()
        if cache is None:
            return None
        t0 = time.perf_counter()
        exe = cache.load(self.digest, sig)
        if exe is None:
            return None
        dt = time.perf_counter() - t0
        (tracer or get_tracer()).record_child_event(
            "compile", dt, program=self.label, aot=True, deserialized=True
        )
        flops = bytes_accessed = None
        try:
            ca = exe.cost_analysis()
            if isinstance(ca, list):  # older jax returns [dict]
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0)) or None
            bytes_accessed = float(ca.get("bytes accessed", 0.0)) or None
        except Exception:  # noqa: BLE001 — no cost model on this backend
            pass
        self._aot[sig] = exe
        st = {
            "compile_s": 0.0,
            "flops": flops,
            "bytes": bytes_accessed,
            "aot_cache_hit": False,
            "deserialized": True,
            "deserialize_s": dt,
        }
        self._aot_stats[sig] = st
        if self._cache is not None:
            self._cache._note_deserialize(dt, label=self.label, digest=self.digest)
        return st

    def __call__(self, *args, **kwargs):
        if not kwargs and (
            self._aot
            or (self._exec_probe_budget > 0 and self._exec_cache() is not None)
        ):
            sig = _pinned_signature(args)
            exe = self._aot.get(sig)
            if exe is None and self._exec_probe_budget > 0:
                if sig not in self._exec_probed:
                    # lazy dispatch of a shape class nobody warmed:
                    # before paying a compile, probe the persistent
                    # executable store once — a fresh process whose
                    # predecessor warmed this (program, shape class)
                    # dispatches with zero compiles
                    self._exec_probed.add(sig)
                    try:
                        if self._load_serialized(sig) is not None:
                            exe = self._aot.get(sig)
                    except Exception:  # noqa: BLE001 — the store must
                        import logging  # never break a dispatch

                        logging.exception(
                            "executable-cache probe failed for %r",
                            self.label,
                        )
                if exe is None and not self._aot:
                    # nothing adopted so far: burn breaker budget per
                    # CALL (not per class) so a program whose store
                    # entries don't exist stops paying call_signature
                    # after a handful of dispatches
                    self._exec_probe_budget -= 1
            if exe is not None:
                try:
                    return exe(*args)
                except (TypeError, ValueError):
                    # same shapes/dtypes but a different sharding/layout
                    # than the warmed executable (checked BEFORE anything
                    # executes) — evict the signature so later rounds
                    # don't re-pay the failed dispatch, and let the jit
                    # path compile/dispatch that variant normally. The
                    # stats entry goes too: a later warmup() must really
                    # recompile, not report a stale aot_cache_hit while
                    # the executable is gone
                    self._aot.pop(sig, None)
                    self._aot_stats.pop(sig, None)
        return self.fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.fn.lower(*args, **kwargs)

    def measured_cost(self) -> Optional[dict]:
        """The measured XLA cost analysis of this program's warmed /
        adopted executables — ``{"flops", "bytes"}`` maxed over shape
        classes (the cohort-max class is what a round dispatches), or
        None when nothing has been AOT-compiled yet. The admission
        controller (fedml_tpu/serve/admission.py) prices candidate
        tenants from this: a MEASURED per-dispatch cost, not a guess."""
        flops = [
            st["flops"] for st in self._aot_stats.values()
            if st.get("flops")
        ]
        byts = [
            st["bytes"] for st in self._aot_stats.values()
            if st.get("bytes")
        ]
        if not flops and not byts:
            return None
        return {
            "flops": max(flops) if flops else None,
            "bytes": max(byts) if byts else None,
        }

    def warmup(self, *args, tracer=None) -> dict:
        """AOT-compile this program for the signature of ``args``
        (``jit(...).lower(...).compile()``) and keep the executable for
        dispatch. Lowering never executes the function, so donated
        buffers in ``args`` are untouched. Idempotent per signature —
        a second warmup is a hit with ``compile_s == 0``. Returns
        ``{compile_s, flops, bytes, aot_cache_hit}``."""
        sig = _pinned_signature(args)
        st = self._aot_stats.get(sig)
        if st is not None:
            # a hit costs nothing: report compile_s=0 (the docstring
            # contract) so a repeat run in a long-lived process doesn't
            # re-bill the first run's compile seconds in its summary rows
            return dict(st, compile_s=0.0, aot_cache_hit=True)
        tracer = tracer or get_tracer()
        # zero-cold-start path: a predecessor process may have persisted
        # this exact (program digest, shape class, environment) —
        # deserialize it instead of compiling (executable_cache.py; the
        # environment fingerprint guarantees skew lands here as a clean
        # miss, never as wrong numerics)
        self._exec_probed.add(sig)
        st = self._load_serialized(sig, tracer=tracer)
        if st is not None:
            return dict(st)
        t0 = time.perf_counter()
        with tracer.span("compile", program=self.label, aot=True):
            compiled = self.fn.lower(*args).compile()
        dt = time.perf_counter() - t0
        flops = bytes_accessed = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, list):  # older jax returns [dict]
                ca = ca[0] if ca else {}
            flops = float(ca.get("flops", 0.0)) or None
            bytes_accessed = float(ca.get("bytes accessed", 0.0)) or None
        except Exception:  # noqa: BLE001 — no cost model on this backend
            pass
        self._aot[sig] = compiled
        st = {
            "compile_s": dt,
            "flops": flops,
            "bytes": bytes_accessed,
            "aot_cache_hit": False,
        }
        self._aot_stats[sig] = st
        if self._cache is not None:
            self._cache._note_compile_time(dt, label=self.label, digest=self.digest)
        exec_cache = self._exec_cache()
        if exec_cache is not None:
            # export the executable so the NEXT process deserializes
            # instead of compiling (best-effort; save() warns on programs
            # this jaxlib cannot serialize)
            try:
                exec_cache.save(self.digest, sig, compiled)
            except Exception:  # noqa: BLE001 — persistence must not
                import logging  # break warmup

                logging.exception(
                    "persisting executable for %r failed", self.label
                )
        return dict(st)


class ProgramCache:
    """Process-wide registry of :class:`CachedProgram`s keyed by the
    canonical digest of their static determinants (thread-safe)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[str, CachedProgram] = {}
        self.hits = 0
        self.misses = 0
        self.bypassed = 0
        self.compile_s = 0.0  # accumulated measured (AOT) compile seconds
        # zero-cold-start accounting (executable_cache.py): programs
        # adopted from the persistent executable store instead of
        # compiled, and the seconds spent deserializing them
        self.deserialize_hits = 0
        self.deserialize_s = 0.0
        # compile-event listeners (fedml_tpu/analysis/sentinel.py): called
        # OUTSIDE the lock as listener(kind, label, digest) with kind in
        # {"build", "hit", "bypass", "aot_compile", "aot_deserialize"} —
        # "build" = a new jit object was constructed (a cache miss),
        # "hit" = a dedup hit, "bypass" = an uncacheable wrap,
        # "aot_compile" = a warmup actually compiled an executable,
        # "aot_deserialize" = a PERSISTED executable was adopted instead
        # of compiling (the sentinel must not count these).
        self._listeners: List[Callable[[str, str, Optional[str]], None]] = []

    def add_listener(self, fn: Callable[[str, str, Optional[str]], None]) -> None:
        """Subscribe to compile events (see ``_listeners``). Listeners
        must be fast and must not raise — they run on the caller's
        thread inside factory construction paths."""
        with self._lock:
            self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        with self._lock:
            try:
                self._listeners.remove(fn)
            except ValueError:
                pass

    def _emit(self, kind: str, label: str, digest: Optional[str]) -> None:
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(kind, label, digest)
            except Exception:  # noqa: BLE001 — observers never break a build
                import logging

                logging.exception("program-cache listener failed")
        self._publish_gauges()

    def _publish_gauges(self) -> None:
        """Mirror the cache counters into the Prometheus registry
        (telemetry/metrics.py) so the recompile picture is scrapeable
        live, not only visible in the end-of-run summary.json row."""
        try:
            snap = self.stats()
            # the ProgramCache is process-wide by design (co-tenant
            # federations share programs), so its gauges publish into the
            # GLOBAL registry even on a tenant-scoped thread — a tenant
            # registry must not carry process totals under a tenant label
            from fedml_tpu.telemetry import get_global_registry

            reg = get_global_registry()
            for key in ("hits", "misses", "bypassed", "programs"):
                reg.gauge(
                    f"fedml_compile_cache_{key}",
                    "ProgramCache activity (fedml_tpu/compile/)",
                ).set(snap[key])
        except Exception:  # noqa: BLE001 — telemetry must not break builds
            pass

    def get_or_build(
        self, label: str, key_fields: Dict[str, Any], builder: Callable[[], Callable]
    ) -> CachedProgram:
        """The shared program for ``key_fields``, building it via
        ``builder()`` on first request. ``builder`` must return a jit
        object whose traced program is FULLY determined by
        ``key_fields`` — when any closure input is not canonically
        describable, use :meth:`wrap_uncached` instead."""
        digest = program_digest(key_fields)
        with self._lock:
            prog = self._programs.get(digest)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._emit("hit", label, digest)
            return prog
        # build outside the lock: builders only wrap jax.jit (compilation
        # itself stays lazy), so a racing duplicate build is cheap and the
        # second one below is discarded
        fn = builder()
        built = False
        with self._lock:
            prog = self._programs.get(digest)
            if prog is None:
                prog = CachedProgram(
                    fn, label, digest=digest, cache=self, key_fields=key_fields
                )
                self._programs[digest] = prog
                self.misses += 1
                built = True
            else:
                self.hits += 1
        self._emit("build" if built else "hit", label, digest)
        return prog

    def wrap_uncached(self, label: str, fn: Callable) -> CachedProgram:
        """Wrap a jit object that must NOT be deduplicated (opaque
        closures), still counting it and giving it the warmup surface."""
        with self._lock:
            self.bypassed += 1
        self._emit("bypass", label, None)
        return CachedProgram(fn, label, cache=self)

    def iter_programs(self) -> List[CachedProgram]:
        """Snapshot of the registered (deduped) programs — the digest
        fuzzer's enumeration surface."""
        with self._lock:
            return list(self._programs.values())

    def lookup(self, digest: str) -> Optional[CachedProgram]:
        """The registered program for ``digest`` WITHOUT building or
        counting a hit/miss — the admission controller's warm-program
        probe (a probe is a question, not a use)."""
        with self._lock:
            return self._programs.get(digest)

    def _note_compile_time(
        self, dt: float, label: str = "?", digest: Optional[str] = None
    ) -> None:
        with self._lock:
            self.compile_s += float(dt)
        self._emit("aot_compile", label, digest)

    def _note_deserialize(
        self, dt: float, label: str = "?", digest: Optional[str] = None
    ) -> None:
        """A persisted executable replaced a compile. Emitted as its own
        event kind — the recompile sentinel must NOT count it (nothing
        compiled; that is the whole point)."""
        with self._lock:
            self.deserialize_hits += 1
            self.deserialize_s += float(dt)
        self._emit("aot_deserialize", label, digest)

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "bypassed": self.bypassed,
                "programs": len(self._programs),
                "compile_s": self.compile_s,
                "deserialize_hits": self.deserialize_hits,
                "deserialize_s": self.deserialize_s,
            }

    def summary_row(self, baseline: Optional[dict] = None) -> dict:
        """Flat MetricsLogger row of (baseline-relative) cache activity —
        the summary.json compile-accounting contract (docs/COMPILE.md)."""
        snap = self.stats()
        base = baseline or {}
        return {
            "compile/cache_hits": snap["hits"] - base.get("hits", 0),
            "compile/cache_misses": snap["misses"] - base.get("misses", 0),
            "compile/cache_bypassed": snap["bypassed"] - base.get("bypassed", 0),
            "compile/programs": snap["programs"],
            "compile/compile_s": snap["compile_s"] - base.get("compile_s", 0.0),
            "compile/deserialize_hits": snap["deserialize_hits"]
            - base.get("deserialize_hits", 0),
            "compile/deserialize_s": snap["deserialize_s"]
            - base.get("deserialize_s", 0.0),
        }

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()
            self.hits = self.misses = self.bypassed = 0
            self.compile_s = 0.0
            self.deserialize_hits = 0
            self.deserialize_s = 0.0


def hooks_cacheable(*hooks) -> bool:
    """THE cache-bypass predicate shared by every round factory: a
    factory may dedupe its program ONLY when every opaque hook that could
    shape the traced computation is None. Single-sourced so a factory
    growing a new hook parameter cannot forget the matching bypass term
    in one copy (an over-merged digest is silent wrong numerics)."""
    return all(h is None for h in hooks)


_GLOBAL = ProgramCache()


def get_program_cache() -> ProgramCache:
    """The process-wide program cache every factory dedupes through (the
    session-scoped ``program_cache`` pytest fixture exposes this same
    object, so test modules share each other's compiles)."""
    return _GLOBAL


@contextlib.contextmanager
def use_program_cache(cache: ProgramCache):
    """Temporarily swap the process-wide cache for ``cache`` (restored on
    exit, even on error). The digest-completeness fuzzer
    (fedml_tpu/analysis/digest_audit.py) builds each perturbed config's
    program in a FRESH cache so colliding digests cannot silently hand
    back the base program instead of invoking the factory's builder —
    the collision is exactly what the audit must observe. Not
    thread-safe: meant for single-threaded audit/test harnesses only."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = cache
    try:
        yield cache
    finally:
        _GLOBAL = prev
