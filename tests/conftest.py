"""Test config: run everything on a virtual 8-device CPU mesh so multi-chip
sharding logic is exercised without TPU hardware (SURVEY §7 / task spec)."""

import os

# Must be set before jax is imported: jax reads JAX_PLATFORMS and XLA_FLAGS
# once, at import / backend init, and honours them by itself.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

# Persistent compilation cache — the HARDENED wrapper (fedml_tpu/compile/
# persistent.py: atomic rename writes, sha256 integrity verification with
# quarantine, advisory file lock), so concurrent pytest processes can no
# longer poison each other's entries (the PR 3 corruption incident class).
#
# Thresholds stay CONSERVATIVE on purpose. The old aggressive config
# (min_entry_size=-1, min_compile_time=0.3) cached every tiny program and
# CORRUPTED THE HEAP on this container's jaxlib+CPU stack: cold-cache suite
# runs flaked ~40% with wrong resume numerics (a restored model evaluating
# at chance), `free(): invalid pointer` / segfaults at exit, and fatal
# "Garbage-collecting" aborts mid-run (the DARTS unrolled trace and the
# jax.profiler TF import were the usual victims — they are just the next
# malloc-heavy phase after the corruption). With the cache fully off the
# same repro loops ran clean 6/6 — but the fast tier then recompiles
# everything and blows the tier-1 time budget. Caching only slow-to-compile
# programs (>= 2 s) keeps the big wins (second-order DARTS,
# attention stacks) with none of the tiny-entry churn that reproduced the
# corruption; detector loops (the resume tests and the abort-prone file
# combo) ran clean under this config. The hardened store uses its own
# .ftpc entry format, so it never misreads stock-format entries that
# share the directory.
#
# WHERE: compile/persistent.resolve_cache_dir — $JAX_COMPILATION_CACHE_DIR
# when set, else <checkout>/.jax_cache (git-ignored). Never a temp path.
from fedml_tpu.compile import install_hardened_cache  # noqa: E402

install_hardened_cache(min_compile_time_secs=2.0)

# Serialized-executable store (fedml_tpu/compile/executable_cache.py),
# session-scoped: every AOT warmup in the suite exports its executable,
# and any later build of the same (program digest, shape class) — another
# test module after a cache reset, a CLI-runner run, a REPEAT pytest
# invocation on this machine — deserializes it instead of recompiling, so
# test modules stop re-paying each other's compiles. Safe by keying: the
# environment fingerprint includes a content hash of the fedml_tpu
# source, so editing ANY .py file invalidates every entry (clean miss,
# recompile) — persisted executables can never go stale against the code.
from fedml_tpu.compile import install_executable_cache  # noqa: E402

# executables/ beside the HLO entries, under the same rule. Entries are
# pickles (a code-trust boundary — see the executable_cache module
# docstring): the store chmods a directory it creates to 0700.
install_executable_cache()


@pytest.fixture(scope="session")
def executable_cache():
    """The session's installed serialized-executable store."""
    from fedml_tpu.compile import installed_executable_cache

    return installed_executable_cache()


@pytest.fixture(scope="session")
def program_cache():
    """THE process-wide ProgramCache (fedml_tpu/compile/program_cache.py)
    — the same registry every round/eval/train factory dedupes through,
    exposed session-scoped so test modules share each other's compiles
    instead of recompiling structurally identical programs."""
    from fedml_tpu.compile import get_program_cache

    return get_program_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test — fast tier deselects with -m 'not slow'",
    )
    config.addinivalue_line(
        "markers",
        "recompile_budget(n): with the recompile_sentinel fixture, fail "
        "the test when more than n XLA backend compiles happen during it "
        "(fedml_tpu/analysis/sentinel.py). Budgets are coarse upper "
        "bounds — every backend compile counts, including small utility "
        "programs — sized to catch per-round recompile storms while "
        "passing standalone runs (where no earlier test pre-built the "
        "shared programs).",
    )


@pytest.fixture
def recompile_sentinel(request):
    """Runtime recompile tripwire (fedml_tpu/analysis/sentinel.py): pair
    with ``@pytest.mark.recompile_budget(n)`` — the test fails when the
    body triggers more than n XLA backend compiles. Without the marker
    the fixture only observes (``sentinel.recompiles()``)."""
    from fedml_tpu.analysis.sentinel import RecompileSentinel

    marker = request.node.get_closest_marker("recompile_budget")
    budget = int(marker.args[0]) if marker and marker.args else None
    sentinel = RecompileSentinel(
        budget=budget, label=request.node.name
    ).start()
    yield sentinel
    sentinel.stop()
    if sentinel.exceeded():
        pytest.fail(sentinel.describe())
