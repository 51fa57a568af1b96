"""Compile runtime — program dedup, AOT warmup, hardened persistent cache.

In the reference framework (PyTorch eager) compilation cost does not
exist; in this JAX port XLA compilation is the dominant *new* cost
dimension. This package manages it as one first-class layer:

- :mod:`fedml_tpu.compile.program_cache` — in-process
  :class:`ProgramCache`: round/eval/train factories across the algorithm
  families dedupe structurally identical programs onto one jit object
  per canonical digest (:mod:`fedml_tpu.compile.digest`), so N
  algorithms × M test modules compile once per shape signature.
- :mod:`fedml_tpu.compile.warmup` — ``--warmup`` AOT path:
  ``jit(...).lower(...).compile()`` the round/eval/server programs
  before round 0, with ``compile`` telemetry spans and per-program XLA
  cost analysis into summary.json; warmed executables serve the actual
  dispatches, so warm runs are numerically identical to cold runs.
- :mod:`fedml_tpu.compile.persistent` — :class:`HardenedFileCache`, a
  corruption-proof wrapper for jax's persistent compilation cache:
  atomic rename writes, sha256 integrity verification with quarantine
  of corrupt entries, and an advisory file lock (the PR 3
  concurrent-writer incident class).

See docs/COMPILE.md for the keying/integrity model and the
observability contract (``compile/*`` keys in summary.json)."""

from fedml_tpu.compile.executable_cache import (
    ExecutableCache,
    environment_fingerprint,
    install_executable_cache,
    install_run_executable_cache,
    installed_executable_cache,
)
from fedml_tpu.compile.digest import (
    call_signature,
    canonical,
    mesh_fingerprint,
    model_fingerprint,
    program_digest,
)
from fedml_tpu.compile.persistent import (
    HardenedFileCache,
    install_hardened_cache,
    install_run_cache,
    installed_cache,
    resolve_cache_dir,
    resolve_executable_cache_dir,
)
from fedml_tpu.compile.program_cache import (
    CachedProgram,
    ProgramCache,
    get_program_cache,
    hooks_cacheable,
    use_program_cache,
)
from fedml_tpu.compile.warmup import (
    warmup_api,
    warmup_local_train,
    warmup_splitnn,
)

__all__ = [
    "CachedProgram",
    "ExecutableCache",
    "HardenedFileCache",
    "ProgramCache",
    "call_signature",
    "canonical",
    "compile_snapshot",
    "compile_summary_row",
    "environment_fingerprint",
    "get_program_cache",
    "hooks_cacheable",
    "install_executable_cache",
    "install_hardened_cache",
    "install_run_cache",
    "install_run_executable_cache",
    "installed_cache",
    "installed_executable_cache",
    "mesh_fingerprint",
    "model_fingerprint",
    "program_digest",
    "resolve_cache_dir",
    "resolve_executable_cache_dir",
    "use_program_cache",
    "warmup_api",
    "warmup_local_train",
    "warmup_splitnn",
]


def compile_snapshot() -> dict:
    """Point-in-time counters of every compile-cache layer (baseline for
    :func:`compile_summary_row`, so a run embedded in a long-lived
    process reports ITS activity, not the process's lifetime totals)."""
    snap = {"programs": get_program_cache().stats()}
    hard = installed_cache()
    if hard is not None:
        snap["persistent"] = hard.stats()
    execs = installed_executable_cache()
    if execs is not None:
        snap["executables"] = execs.stats()
    return snap


def compile_summary_row(baseline: dict = None) -> dict:
    """Flat ``{"compile/...": value}`` MetricsLogger row combining the
    in-process program cache, the hardened persistent HLO layer, and the
    serialized-executable store (when installed) — summary.json stays
    the single CI oracle."""
    base = baseline or {}
    row = get_program_cache().summary_row(baseline=base.get("programs"))
    hard = installed_cache()
    if hard is not None:
        row.update(hard.summary_row(baseline=base.get("persistent")))
    execs = installed_executable_cache()
    if execs is not None:
        row.update(execs.summary_row(baseline=base.get("executables")))
    return row
