"""Serialized AOT executables — compile once per *machine*, not per process.

The compile runtime (PR 4/5) removed redundant compiles *in-process*: the
ProgramCache dedupes structurally identical programs onto one jit object,
and ``--warmup`` AOT-compiles them before round 0. But a fresh process — a
production restart, an autoscaled replica, a CI shard — still recompiles
everything from scratch: the persistent HLO cache (persistent.py) only
skips the *backend* half of slow compiles, and every warmed executable
dies with the process.

This module closes that gap: :class:`ExecutableCache` exports the
executables ``CachedProgram.warmup`` builds (via jax's AOT serialization,
``jax.experimental.serialize_executable``) through the existing
:class:`~fedml_tpu.compile.persistent.HardenedFileCache` — reusing its
atomic writes, sha256 integrity verification, quarantine, advisory lock
and LRU size cap rather than re-implementing them — so a second process
*deserializes* its programs instead of compiling them.

Keying: an entry is addressed by sha256 of

- the program's **ProgramCache canonical digest** (digest.py — the
  complete static determinants of the traced program; completeness is
  mechanically audited by fedml_tpu/analysis/digest_audit.py),
- the **call signature** (pytree structure + per-leaf shape/dtype — one
  executable per shape class, exactly like the in-process AOT map), and
- an **environment fingerprint**: jax/jaxlib versions, backend platform,
  device kind/count/topology, the jax config flags that change lowering
  (threefry partitioning, x64), ``XLA_FLAGS``, and a content hash of the
  fedml_tpu package source. Version skew — a jaxlib upgrade, a different
  accelerator, an edited round body — lands on a different key and
  deserializes to a clean MISS (the program recompiles), never to wrong
  numerics. The fingerprint is *also* embedded in every entry and
  re-verified on load, so an entry copied or forged under the right key
  is quarantined rather than trusted.

SECURITY — the cache directory is a CODE-TRUST boundary. Entries are
transported as pickles (jax's AOT serialization is itself pickle-based),
and unpickling attacker-controlled bytes is arbitrary code execution —
the sha256 frame and embedded fingerprint authenticate INTEGRITY, not
AUTHORSHIP (both live in the same file an attacker would write). Point
``--executable_cache`` only at directories writable solely by principals
you would let run code in the training process (the same trust you
already extend to the Python environment itself). The store chmods a
directory it creates to 0700, so the default posture on shared machines
is private.

Device binding: an executable is compiled for an ordered device
assignment, and ``deserialize_and_load`` binds to EVERY device of the
backend unless told otherwise — so :meth:`ExecutableCache.save` records
the assignment's device ids and :meth:`ExecutableCache.load` hands the
same devices back as ``execution_devices``. A one-device program stays a
one-device program on an 8-device host.

Observability: deserialize hits/seconds land in summary.json
(``compile/deserialize_hits``, ``compile/deserialize_s``, plus the
store's ``compile/executable_*`` counters) and mirror into Prometheus
(``fedml_compile_deserialize_hits``, ``fedml_compile_deserialize_s``,
``fedml_compile_executable_quarantined``). See docs/COMPILE.md."""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pathlib
import pickle
import threading
import time
from typing import Any, Optional

from fedml_tpu.compile.persistent import (
    HardenedFileCache,
    resolve_executable_cache_dir,
)

_KEY_PREFIX = "xc-"
_FORMAT = 2  # bump to invalidate every persisted executable at once
# Entries not READ for this long are pruned on store construction. The
# environment fingerprint contains a source-content hash, so every code
# edit permanently orphans all prior entries under never-again-read keys
# — without age pruning a developer's session store (tests/conftest.py)
# would accumulate unreachable multi-MB pickles indefinitely (the LRU
# size cap only engages when jax_compilation_cache_max_size is set).
_PRUNE_AGE_S = 14 * 24 * 3600

_code_fp_lock = threading.Lock()
_code_fp: Optional[str] = None


def code_fingerprint() -> str:
    """sha256 over every ``.py`` file of the fedml_tpu package (relative
    path + content), memoized per process. A serialized executable bakes
    in the *traced program*, which the ProgramCache digest keys by config
    — but an edit to a round body changes the program without changing
    any config field. In-process that cannot go stale; across processes
    it can, so the code itself enters the environment fingerprint: any
    source change invalidates every persisted executable (clean miss,
    recompile)."""
    global _code_fp
    with _code_fp_lock:
        if _code_fp is not None:
            return _code_fp
        import fedml_tpu

        root = pathlib.Path(fedml_tpu.__file__).parent
        h = hashlib.sha256()
        for p in sorted(root.rglob("*.py")):
            h.update(str(p.relative_to(root)).encode("utf-8"))
            h.update(b"\0")
            h.update(p.read_bytes())
        _code_fp = h.hexdigest()
        return _code_fp


def environment_fingerprint() -> dict:
    """Canonical identity of everything that must match for a serialized
    executable to be safe to run here: jaxlib/XLA version, backend,
    device topology, the lowering-relevant jax config flags, and the
    package source hash (see :func:`code_fingerprint`). Any mismatch is
    a different cache key — skew deserializes to a recompile, never to
    wrong numerics."""
    import jax
    import jaxlib

    devs = jax.devices()

    def flag(name: str, default: Any = None) -> Any:
        try:
            return getattr(jax.config, name)
        except Exception:  # noqa: BLE001 — flag-name drift across versions
            return default

    return {
        "format": _FORMAT,
        "jax": str(jax.__version__),
        "jaxlib": str(jaxlib.__version__),
        "backend": str(jax.default_backend()),
        "device_kind": str(getattr(devs[0], "device_kind", "?")),
        "device_count": len(devs),
        "process_count": int(jax.process_count()),
        "threefry_partitionable": bool(flag("jax_threefry_partitionable", False)),
        "enable_x64": bool(flag("jax_enable_x64", False)),
        # precision/PRNG policy is BAKED into the traced dot/conv/random
        # ops — two processes differing here build different programs
        # under identical configs, so both must split the key (a
        # JAX_DEFAULT_MATMUL_PRECISION env var is jax config, not
        # XLA_FLAGS, and would otherwise adopt a wrong-precision
        # executable under a matching key)
        "matmul_precision": str(flag("jax_default_matmul_precision", None)),
        "prng_impl": str(flag("jax_default_prng_impl", "threefry2x32")),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "code": code_fingerprint(),
    }


class ExecutableCache:
    """Persistent store of serialized AOT executables (thread-safe).

    A thin policy layer over :class:`HardenedFileCache` — the store
    already guarantees atomic writes, sha256-verified reads with
    quarantine, and LRU eviction; this class adds the (digest, signature,
    environment) keying, the embedded-fingerprint re-verification, and
    the serialize/deserialize transport."""

    def __init__(self, path: str):
        existed = pathlib.Path(path).is_dir()
        self._store = HardenedFileCache(path)
        self.path = self._store.path
        if not existed:
            # a directory WE created is private by default (the module
            # docstring's trust boundary); a pre-existing dir keeps its
            # owner's chosen policy — an operator sharing a cache across
            # trusted CI users must be able to
            try:
                os.chmod(self.path, 0o700)
            except OSError:
                pass
        self._prune_stale()
        self._mu = threading.Lock()
        self._env_doc: Optional[dict] = None
        self.hits = 0          # entries deserialized into live executables
        self.misses = 0        # clean key misses (incl. env-skew keys)
        self.puts = 0          # executables serialized + persisted
        self.put_errors = 0    # serialization not supported for a program
        # semantic-verification quarantines are counted by the STORE
        # (quarantine_entry); this stays for API shape + future non-store
        # quarantine paths, and summary_row sums both
        self.quarantined = 0
        self.deserialize_s = 0.0
        self.serialize_s = 0.0

    def _prune_stale(self) -> None:
        """Best-effort drop of OUR entries (xc- prefix only — a shared
        dir's HLO entries are untouched) whose last read/touch is older
        than ``_PRUNE_AGE_S``: code-hash skew orphans entries under keys
        that will never be read again (see _PRUNE_AGE_S). ``get()``
        refreshes atime-via-utime on every hit, so live entries
        survive."""
        now = time.time()
        pruned = 0
        try:
            for p in self.path.glob(f"{_KEY_PREFIX}*.ftpc"):
                try:
                    if now - p.stat().st_atime > _PRUNE_AGE_S:
                        p.unlink()
                        pruned += 1
                except OSError:  # racing process — already gone
                    continue
        except OSError:
            return
        if pruned:
            logging.info(
                "executable cache %s: pruned %d stale entr%s (untouched "
                "> %d days)", self.path, pruned,
                "y" if pruned == 1 else "ies", _PRUNE_AGE_S // 86400,
            )

    # -- keying ------------------------------------------------------------

    def _env(self) -> dict:
        with self._mu:
            if self._env_doc is None:
                self._env_doc = environment_fingerprint()
            return self._env_doc

    def key_for(self, digest: str, sig) -> str:
        doc = json.dumps(
            {"program": digest, "sig": repr(tuple(sig)), "env": self._env()},
            sort_keys=True,
            separators=(",", ":"),
        )
        return _KEY_PREFIX + hashlib.sha256(doc.encode("utf-8")).hexdigest()

    # -- load/save ---------------------------------------------------------

    def load(self, digest: str, sig):
        """The deserialized executable for (digest, sig) in THIS
        environment, or None. Entries that unpickle to a mismatched
        fingerprint, or fail to deserialize, are quarantined (forensics
        preserved) and reported as a miss — the program recompiles with
        identical numerics, mirroring the persistent store's
        corrupt-entry contract."""
        key = self.key_for(digest, sig)
        blob = self._store.get(key)  # sha256-verified; torn/bit-rotted
        if blob is None:             # entries already quarantined inside
            with self._mu:
                self.misses += 1
            return None
        t0 = time.perf_counter()
        try:
            doc = pickle.loads(blob)
            if (
                not isinstance(doc, dict)
                or doc.get("format") != _FORMAT
                or doc.get("program") != digest
                or doc.get("env") != self._env()
            ):
                raise ValueError(
                    "embedded environment/program fingerprint mismatch"
                )
            import jax
            from jax.experimental import serialize_executable as se

            # bind to the devices the executable was compiled for — the
            # default is every device of the backend, which turns a
            # one-device program into an N-shard one on an N-device host
            by_id = {d.id: d for d in jax.devices()}
            exe = se.deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"],
                execution_devices=[by_id[i] for i in doc["device_ids"]],
            )
        except Exception as e:  # noqa: BLE001 — any load fault = quarantine
            # quarantine_entry increments the STORE's quarantined counter
            # — the single source for this event (summary_row/gauges sum
            # store + semantic counters, so counting here too would
            # double-report one quarantine as two)
            self._store.quarantine_entry(key)
            with self._mu:
                self.misses += 1
            logging.warning(
                "serialized executable %s failed to load (%s: %s) — "
                "quarantined; the program recompiles", key, type(e).__name__, e,
            )
            self._publish_gauges()
            return None
        dt = time.perf_counter() - t0
        with self._mu:
            self.hits += 1
            self.deserialize_s += dt
        self._publish_gauges()
        return exe

    def save(self, digest: str, sig, compiled) -> bool:
        """Serialize ``compiled`` and persist it under (digest, sig, env).
        Best-effort: a program this jaxlib cannot serialize (exotic
        sharding, host callbacks) is skipped with a warning — the run is
        merely slower to restart, never wrong."""
        key = self.key_for(digest, sig)
        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            # the same private handle serialize() reads: the ordered
            # device assignment the executable was compiled for
            unloaded = compiled._executable._unloaded_executable
            blob = pickle.dumps(
                {
                    "format": _FORMAT,
                    "program": digest,
                    "env": self._env(),
                    "device_ids": [int(d.id) for d in unloaded.device_list],
                    "payload": payload,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                },
                protocol=pickle.HIGHEST_PROTOCOL,
            )
        except Exception as e:  # noqa: BLE001 — capability gap, not a bug
            with self._mu:
                self.put_errors += 1
            logging.warning(
                "executable for program %s could not be serialized "
                "(%s: %s) — it will recompile in fresh processes",
                digest[:12], type(e).__name__, e,
            )
            return False
        written = self._store.put(key, blob)
        with self._mu:
            if written:
                # only REAL persists count: a declined write (first
                # writer already holds the slot) or a failed one (full /
                # read-only filesystem) must not let the ci.sh
                # export-happened assertion pass vacuously
                self.puts += 1
            self.serialize_s += time.perf_counter() - t0
        self._publish_gauges()
        return written

    # -- observability -----------------------------------------------------

    def _publish_gauges(self) -> None:
        try:
            from fedml_tpu.telemetry import get_registry

            snap = self.stats()
            reg = get_registry()
            reg.gauge(
                "fedml_compile_deserialize_hits",
                "serialized AOT executables loaded instead of compiled",
            ).set(snap["hits"])
            reg.gauge(
                "fedml_compile_deserialize_s",
                "seconds spent deserializing persisted executables",
            ).set(snap["deserialize_s"])
            reg.gauge(
                "fedml_compile_executable_quarantined",
                "persisted executables that failed verification on load",
            ).set(snap["quarantined"] + snap["store"]["quarantined"])
        except Exception:  # noqa: BLE001 — telemetry must not break loads
            pass

    def stats(self) -> dict:
        with self._mu:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "puts": self.puts,
                "put_errors": self.put_errors,
                "quarantined": self.quarantined,
                "deserialize_s": self.deserialize_s,
                "serialize_s": self.serialize_s,
                "store": self._store.stats(),
            }

    def summary_row(self, baseline: Optional[dict] = None) -> dict:
        """Flat MetricsLogger row of the store mechanics (docs/COMPILE.md
        observability contract). The headline zero-cold-start keys —
        ``compile/deserialize_hits``/``_s`` — come from the
        :class:`~fedml_tpu.compile.program_cache.ProgramCache` row (the
        programs that actually adopted a persisted executable), so this
        row only carries the ``executable_*`` store counters."""
        snap = self.stats()
        base = baseline or {}
        return {
            "compile/executable_puts": snap["puts"] - base.get("puts", 0),
            "compile/executable_misses": snap["misses"] - base.get("misses", 0),
            "compile/executable_quarantined": (
                snap["quarantined"] + snap["store"]["quarantined"]
            )
            - (
                base.get("quarantined", 0)
                + base.get("store", {}).get("quarantined", 0)
            ),
        }


_INSTALLED: Optional[ExecutableCache] = None


def installed_executable_cache() -> Optional[ExecutableCache]:
    """The process's installed executable cache, if any."""
    return _INSTALLED


def install_executable_cache(requested: str = "") -> ExecutableCache:
    """Install an :class:`ExecutableCache` as the process's executable
    store (``CachedProgram`` warmup/dispatch consults it), at
    :func:`~fedml_tpu.compile.persistent.resolve_executable_cache_dir`
    ``(requested)`` — beside the HLO cache, under the same
    ``$JAX_COMPILATION_CACHE_DIR``-wins rule. Idempotent per directory."""
    global _INSTALLED
    path = resolve_executable_cache_dir(requested)
    if _INSTALLED is None or str(_INSTALLED.path) != str(path):
        _INSTALLED = ExecutableCache(str(path))
    return _INSTALLED


def install_run_executable_cache(requested: str = ""):
    """Install an executable cache for ONE run and return ``(cache,
    restore)`` — ``restore()`` reinstates whatever binding existed before
    (the conftest-installed session store, or nothing), mirroring
    :func:`fedml_tpu.compile.persistent.install_run_cache` so a run
    embedded in a long-lived process can't hijack later loads."""
    global _INSTALLED
    prev = _INSTALLED
    cache = install_executable_cache(requested)

    def restore() -> None:
        global _INSTALLED
        _INSTALLED = prev

    return cache, restore
