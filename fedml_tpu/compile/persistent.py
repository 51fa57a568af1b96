"""Hardened persistent XLA compile cache — atomic, integrity-checked,
advisory-locked.

jax's stock file cache (``jax._src.lru_cache.LRUCache``) writes entries
with a plain ``write_bytes`` and reads them back with no integrity
check. Under concurrent writer processes a reader can observe a torn
write, and a corrupt entry then deserializes into a *wrong executable* —
the PR 3 incident class (deterministic ~1e-5 resume-numerics drift plus
munmap/segfault noise until the cache dir was wiped; ROADMAP
"compile-cache hygiene").

:class:`HardenedFileCache` is a drop-in ``CacheInterface`` replacement
that makes every failure mode loud-or-harmless:

- **atomic writes**: entries are written to a same-directory temp file,
  fsynced, then ``os.replace``d into place — a reader can only ever see
  a complete entry or no entry.
- **content-hash verification**: every entry embeds
  ``sha256(payload)``; a mismatch on load (torn write from a non-atomic
  writer, bit rot, truncation) returns a miss instead of wrong bytes.
- **quarantine**: corrupt entries are moved aside into ``quarantine/``
  (preserved for forensics, never re-read) and the program simply
  recompiles.
- **advisory file lock**: writers serialize on ``.ftpc.lock`` via
  ``fcntl.flock``, so concurrent pytest processes can no longer race
  each other's puts (best-effort: a lock timeout degrades to the
  still-atomic unlocked write rather than blocking training).

Entries use our own ``.ftpc`` suffix/format, so a directory previously
populated by the stock cache is simply treated as empty rather than
misread.

:func:`install_hardened_cache` wires an instance in as the process's jax
compilation cache and applies the cache-dir/threshold config in one
place. WHERE the cache lives is decided by :func:`resolve_cache_dir`
alone — ``$JAX_COMPILATION_CACHE_DIR`` when set, else an explicitly
requested directory, else ``<checkout>/.jax_cache`` — and the CLI,
chip_smoke.py and tests/conftest.py all go through it."""

from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import pathlib
import threading
import time
from typing import Optional

_MAGIC = b"FTPC1\n"
_SUFFIX = ".ftpc"
_HASH_LEN = 32  # sha256 digest bytes


class HardenedFileCache:
    """Corruption-proof persistent byte store (jax CacheInterface shape:
    ``get(key) -> bytes | None``, ``put(key, value)``)."""

    def __init__(self, path: str, lock_timeout_s: float = 10.0):
        self._path = pathlib.Path(path)
        self._path.mkdir(parents=True, exist_ok=True)
        self.path = self._path  # stock LRUCache exposes .path; keep parity
        self._qdir = self._path / "quarantine"
        self._lock_path = self._path / ".ftpc.lock"
        self._lock_timeout_s = float(lock_timeout_s)
        self._mu = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.quarantined = 0
        self.evicted = 0

    # -- key/path hygiene --
    def _entry_path(self, key: str) -> pathlib.Path:
        # jax cache keys are hex digests; defend anyway against separators
        safe = str(key).replace(os.sep, "_").replace("/", "_")
        if not safe:
            raise ValueError("key cannot be empty")
        return self._path / f"{safe}{_SUFFIX}"

    # -- advisory lock --
    @contextlib.contextmanager
    def _flock(self):
        """Advisory exclusive lock on the cache dir's lockfile. Degrades
        to no-lock after the timeout (writes stay atomic regardless)."""
        fd = None
        locked = False
        try:
            try:
                import fcntl

                fd = os.open(
                    self._lock_path, os.O_CREAT | os.O_RDWR, 0o644
                )
                deadline = time.monotonic() + self._lock_timeout_s
                while True:
                    try:
                        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                        locked = True
                        break
                    except OSError:
                        if time.monotonic() >= deadline:
                            logging.warning(
                                "compile cache lock %s held past %.1fs — "
                                "proceeding unlocked (writes stay atomic)",
                                self._lock_path,
                                self._lock_timeout_s,
                            )
                            break
                        time.sleep(0.05)
            except ImportError:  # non-POSIX: atomic rename is the guard
                pass
            yield
        finally:
            if fd is not None:
                if locked:
                    try:
                        import fcntl

                        fcntl.flock(fd, fcntl.LOCK_UN)
                    except Exception:  # noqa: BLE001
                        pass
                os.close(fd)

    # -- integrity --
    @staticmethod
    def _frame(payload: bytes) -> bytes:
        return _MAGIC + hashlib.sha256(payload).digest() + payload

    @staticmethod
    def _verify(blob: bytes) -> Optional[bytes]:
        head = len(_MAGIC) + _HASH_LEN
        if len(blob) < head or not blob.startswith(_MAGIC):
            return None
        digest = blob[len(_MAGIC):head]
        payload = blob[head:]
        if hashlib.sha256(payload).digest() != digest:
            return None
        return payload

    def quarantine_entry(self, key: str) -> None:
        """Move ``key``'s entry into quarantine/. The executable layer
        (executable_cache.py) calls this when an entry passes the byte
        integrity check but fails SEMANTIC verification — a mismatched
        embedded environment fingerprint, or a payload this jax cannot
        deserialize — so the forensics-preserving quarantine discipline
        covers both corruption classes."""
        self._quarantine(
            self._entry_path(key), reason="failed semantic verification"
        )

    def _quarantine(
        self,
        p: pathlib.Path,
        reason: str = "failed integrity verification",
    ) -> None:
        with self._mu:
            self.quarantined += 1
        try:
            self._qdir.mkdir(parents=True, exist_ok=True)
            dest = self._qdir / f"{p.name}.{os.getpid()}.{time.time_ns()}"
            os.replace(p, dest)
            logging.warning(
                "compile cache entry %s %s — quarantined to %s; the "
                "program recompiles", p.name, reason, dest,
            )
        except OSError:
            # a racing process already moved/removed it — that's fine,
            # the entry is gone either way
            logging.warning(
                "compile cache entry %s %s and could not be quarantined "
                "(already removed?)", p.name, reason,
            )

    # -- CacheInterface --
    def get(self, key: str) -> Optional[bytes]:
        p = self._entry_path(key)
        try:
            blob = p.read_bytes()
        except FileNotFoundError:
            with self._mu:
                self.misses += 1
            return None
        except OSError as e:
            logging.warning("compile cache read %s failed: %s", p, e)
            with self._mu:
                self.misses += 1
            return None
        payload = self._verify(blob)
        if payload is None:
            self._quarantine(p)
            with self._mu:
                self.misses += 1
            return None
        with self._mu:
            self.hits += 1
        # refresh the timestamp so size-cap eviction approximates LRU
        # (the stock LRUCache does the same on get)
        with contextlib.suppress(OSError):
            os.utime(p, None)
        return payload

    # -- size cap (jax_compilation_cache_max_size parity) --
    @staticmethod
    def _max_size_bytes() -> int:
        try:
            import jax

            return int(
                getattr(jax.config, "jax_compilation_cache_max_size", -1)
            )
        except Exception:  # noqa: BLE001 — cache is usable without jax
            return -1

    def _evict_if_needed(self, keep: pathlib.Path) -> None:
        """Drop least-recently-used entries until the directory fits the
        jax size cap (<= 0 means unbounded, jax's default). The stock
        LRUCache enforced this cap; a hardened replacement that silently
        ignored it would grow shared dirs without bound. Never evicts the
        entry just written."""
        cap = self._max_size_bytes()
        if cap <= 0:
            return
        entries = []
        for p in self._path.glob(f"*{_SUFFIX}"):
            try:
                st = p.stat()
            except OSError:  # racing process removed it
                continue
            entries.append((st.st_atime, st.st_size, p))
        total = sum(size for _, size, _ in entries)
        for _, size, p in sorted(entries, key=lambda e: e[0]):
            if total <= cap:
                break
            if p == keep:
                continue
            with contextlib.suppress(OSError):
                os.unlink(p)
                total -= size
                with self._mu:
                    self.evicted += 1

    def put(self, key: str, value: bytes) -> bool:
        """Write an entry; returns True only when THIS call persisted it
        (False: a first writer already holds the slot, or the write
        failed — callers reporting export counters must not count those
        as successes). jax's CacheInterface ignores the return value."""
        p = self._entry_path(key)
        blob = self._frame(bytes(value))
        tmp = p.with_name(f".tmp.{os.getpid()}.{p.name}")
        with self._flock():
            if p.exists():
                return False  # first writer wins (stock LRUCache semantics)
            try:
                with open(tmp, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, p)
            except OSError as e:
                logging.warning("compile cache write %s failed: %s", p, e)
                with contextlib.suppress(OSError):
                    os.unlink(tmp)
                return False
            self._evict_if_needed(keep=p)
        with self._mu:
            self.puts += 1
        return True

    def stats(self) -> dict:
        with self._mu:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "puts": self.puts,
                "quarantined": self.quarantined,
                "evicted": self.evicted,
            }

    def summary_row(self, baseline: Optional[dict] = None) -> dict:
        snap = self.stats()
        base = baseline or {}
        return {
            f"compile/persistent_{k}": v - base.get(k, 0)
            for k, v in snap.items()
        }


_INSTALLED: Optional[HardenedFileCache] = None

# jax's own variable for the persistent compilation cache. Where it is
# set, THAT directory is the cache — for jax's stock layer and for both
# of ours — and an explicitly requested path only earns a warning.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# Where it is not set: one fixed, git-ignored directory in the checkout.
# The directory is part of jax's cache key, so a path that moves (temp
# name, pid, uid, time) never hits.
_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def _resolve(requested: str, subdir: str) -> pathlib.Path:
    env = os.environ.get(CACHE_DIR_ENV)
    if not env:
        return pathlib.Path(requested) if requested else _CHECKOUT_CACHE / subdir
    chosen = pathlib.Path(env) / subdir
    if requested and pathlib.Path(requested).resolve() != chosen.resolve():
        logging.warning(
            "compile cache: %s=%s is set and wins — using %s, not the "
            "requested %s", CACHE_DIR_ENV, env, chosen, requested,
        )
    return chosen


def resolve_cache_dir(requested: str = "") -> pathlib.Path:
    """THE compile-cache directory of this process (CLI, chip_smoke.py
    and tests/conftest.py all ask here): ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``requested`` (a deployment's explicit
    ``--compile_cache_dir``), else ``<checkout>/.jax_cache``."""
    return _resolve(requested, "")


def resolve_executable_cache_dir(requested: str = "") -> pathlib.Path:
    """The serialized-executable store's directory, under the same rule:
    ``executables/`` inside ``$JAX_COMPILATION_CACHE_DIR`` when that is
    set, else ``requested``, else ``<checkout>/.jax_cache/executables``."""
    return _resolve(requested, "executables")


def installed_cache() -> Optional[HardenedFileCache]:
    """The process's installed hardened cache, if any."""
    return _INSTALLED


def _bind_jax_cache(path, min_compile_time_secs, cache, initialized) -> None:
    """The one place this package points jax's persistent compilation
    cache somewhere: install and the run-scoped restore both end here."""
    import jax
    from jax._src import compilation_cache as cc

    jax.config.update(
        "jax_compilation_cache_dir", None if path is None else str(path)
    )
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(min_compile_time_secs),
    )
    with cc._cache_initialized_mutex:
        # claim the once-only initialization slot so jax neither replaces
        # the hardened store nor trips its _cache-is-None assertion later
        cc._cache = cache
        cc._cache_initialized = initialized


def install_hardened_cache(
    requested: str = "",
    min_compile_time_secs: float = 2.0,
) -> HardenedFileCache:
    """Enable jax's persistent compilation cache at
    :func:`resolve_cache_dir` ``(requested)`` with the hardened store
    underneath.

    Applies the standard jax config (cache dir + write threshold — the
    conservative >= 2 s default matches tests/conftest.py's
    corruption-clean setting), then installs :class:`HardenedFileCache`
    as the process's cache backend. Idempotent: re-installing over the
    same directory keeps the existing instance (and its counters)."""
    global _INSTALLED
    path = resolve_cache_dir(requested)
    if _INSTALLED is None or str(_INSTALLED.path) != str(path):
        _INSTALLED = HardenedFileCache(path)
    _bind_jax_cache(path, min_compile_time_secs, _INSTALLED, True)
    return _INSTALLED


def install_run_cache(
    requested: str = "", min_compile_time_secs: float = 2.0
):
    """Install a hardened cache for ONE run and return ``(cache,
    restore)``: ``restore()`` reinstates whatever persistent-cache binding
    existed before (the conftest-installed shared store, the stock cache,
    or nothing). Without the restore, a run embedded in a long-lived
    process (CliRunner tests, notebook sweeps) would leave every LATER
    compile in the process pointed at the run's — possibly deleted —
    cache directory."""
    global _INSTALLED
    import jax
    from jax._src import compilation_cache as cc

    with cc._cache_initialized_mutex:
        prev = (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            cc._cache,
            cc._cache_initialized,
        )
    prev_installed = _INSTALLED
    cache = install_hardened_cache(
        requested, min_compile_time_secs=min_compile_time_secs
    )

    def restore() -> None:
        global _INSTALLED
        _bind_jax_cache(*prev)
        _INSTALLED = prev_installed

    return cache, restore
