"""Compile runtime (fedml_tpu/compile/): program dedup, digest stability,
AOT warmup numerics parity, and the hardened persistent cache's
corruption-proofing (ISSUE 4 acceptance contract).

The quarantine/recompile tests drive REAL jax compiles through the
hardened store in subprocesses, so a (hypothetical) deserialization fault
can never poison this pytest process — exactly the isolation discipline
the store exists to enforce."""

import json
import logging
import os
import pathlib
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from fedml_tpu.compile import (
    CachedProgram,
    HardenedFileCache,
    ProgramCache,
    call_signature,
    canonical,
    compile_snapshot,
    compile_summary_row,
    get_program_cache,
    install_run_cache,
    installed_cache,
    installed_executable_cache,
    model_fingerprint,
    program_digest,
    resolve_cache_dir,
    resolve_executable_cache_dir,
)
from fedml_tpu.compile.persistent import CACHE_DIR_ENV
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression

_REPO = pathlib.Path(__file__).resolve().parents[1]
# where conftest.py put the session stores (resolved at ITS import)
_SESSION_CACHE_DIR = pathlib.Path(
    os.environ.get(CACHE_DIR_ENV) or _REPO / ".jax_cache"
)


@pytest.fixture(autouse=True)
def _explicit_cache_dirs_are_honoured(monkeypatch):
    """The tests below hand install_* explicit tmp directories; where the
    machine sets JAX_COMPILATION_CACHE_DIR that would (by design) win, so
    clear it for the test body (subprocesses inherit the cleared env)."""
    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)


# ---------------------------------------------------------------------------
# shared fixtures (mirror tests/test_scheduler.py so the ProgramCache
# actually dedupes across the two modules — that sharing IS the feature)
# ---------------------------------------------------------------------------


def _data(num_clients=6, samples=12):
    return synthetic_classification(
        num_clients=num_clients, num_classes=3, feat_shape=(5,),
        samples_per_client=samples, partition_method="homo", seed=9,
    )


def _model():
    return ModelDef(
        module=LogisticRegression(num_classes=3), input_shape=(5,),
        num_classes=3, name="lr",
    )


def _cfg(**fed_kw):
    base = dict(
        client_num_in_total=6, client_num_per_round=3, comm_round=2,
        epochs=1, frequency_of_the_test=1,
    )
    base.update(fed_kw)
    return RunConfig(
        data=DataConfig(batch_size=-1),
        fed=FedConfig(**base),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )


# ---------------------------------------------------------------------------
# digest: canonicalization + cross-process stability
# ---------------------------------------------------------------------------


def test_canonical_abstracts_arrays_to_shape_dtype():
    """Concrete values NEVER enter a digest — two arrays of the same
    shape/dtype canonicalize identically, different shapes differ."""
    a = canonical(np.zeros((2, 3), np.float32))
    b = canonical(np.ones((2, 3), np.float32) * 7)
    c = canonical(np.zeros((2, 4), np.float32))
    assert a == b
    assert a != c
    assert a == {"__aval__": [[2, 3], "float32"]}


def test_canonical_dict_order_independent():
    f1 = {"x": {"b": 2, "a": 1}, "y": [1, 2]}
    f2 = {"y": [1, 2], "x": {"a": 1, "b": 2}}
    assert program_digest(f1) == program_digest(f2)


def test_digest_distinguishes_configs():
    t1 = TrainConfig(lr=0.1)
    t2 = TrainConfig(lr=0.2)
    assert program_digest({"train": t1}) != program_digest({"train": t2})
    assert program_digest({"train": t1}) == program_digest(
        {"train": TrainConfig(lr=0.1)}
    )


def test_digest_stable_across_processes():
    """The plain-field digest (configs, shapes, strings) is the persistent
    keying contract — pin it against a fresh interpreter."""
    fields_src = (
        "{'kind': 'round', 'train': TrainConfig(lr=0.05, momentum=0.9), "
        "'epochs': 2, 'task': 'classification', "
        "'x': np.zeros((4, 8), np.float32)}"
    )
    prog = (
        "import numpy as np\n"
        "from fedml_tpu.config import TrainConfig\n"
        "from fedml_tpu.compile.digest import program_digest\n"
        f"print(program_digest({fields_src}))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        check=True, timeout=120,
    )
    from fedml_tpu.config import TrainConfig as TC

    here = program_digest({
        "kind": "round", "train": TC(lr=0.05, momentum=0.9),
        "epochs": 2, "task": "classification",
        "x": np.zeros((4, 8), np.float32),
    })
    assert out.stdout.strip() == here


# ---------------------------------------------------------------------------
# ProgramCache: hit/miss accounting + factory dedup
# ---------------------------------------------------------------------------


def test_program_cache_hit_miss_accounting():
    pc = ProgramCache()
    built = []

    def builder():
        built.append(1)
        return lambda x: x

    p1 = pc.get_or_build("p", {"k": 1}, builder)
    p2 = pc.get_or_build("p", {"k": 1}, builder)
    p3 = pc.get_or_build("p", {"k": 2}, builder)
    assert p1 is p2 and p1 is not p3
    assert len(built) == 2  # one build per distinct digest
    assert pc.stats()["hits"] == 1
    assert pc.stats()["misses"] == 2
    u = pc.wrap_uncached("opaque", lambda x: x)
    assert isinstance(u, CachedProgram)
    assert pc.stats()["bypassed"] == 1


def test_round_factories_dedupe_onto_one_program(program_cache):
    """Two independently constructed FedAvg round factories over the same
    (model, config) land on ONE CachedProgram — the compile-once-per-shape
    contract. An opaque hook must bypass the registry."""
    from fedml_tpu.algorithms.fedavg import make_fedavg_round

    model, cfg = _model(), _cfg()
    before = program_cache.stats()
    f1 = make_fedavg_round(model, cfg)
    f2 = make_fedavg_round(model, cfg)
    # the dispatch wrappers differ but resolve to the same cached program
    # (vmap mode collapses both may_pad variants onto one skip choice)
    assert f1.variant_for(False) is f2.variant_for(False)
    assert f1.variant_for(True) is f2.variant_for(True)
    after = program_cache.stats()
    assert after["hits"] >= before["hits"] + 1
    f3 = make_fedavg_round(
        model, cfg, post_aggregate=lambda g: g  # opaque hook
    )
    assert f3.variant_for(False) is not f1.variant_for(False)
    assert program_cache.stats()["bypassed"] > before["bypassed"]


def test_eval_factory_dedupes(program_cache):
    from fedml_tpu.train.evaluate import make_eval_fn

    model = _model()
    assert make_eval_fn(model) is make_eval_fn(model)


def test_fedopt_server_step_dedupes_across_vmap_and_transport(program_cache):
    """The vmap API (fedopt.py) and the transport server manager
    (fedavg_transport.py) key the FedOpt server step on the SAME
    (kind, server config, step_builder) fields, so both sides share ONE
    jit object. The probe below issues the transport-side call verbatim
    with a must-not-run builder — if either site's key drifts, the miss
    invokes the builder and the test fails."""
    from fedml_tpu.algorithms.fedopt import FedOptAPI, make_server_step
    from fedml_tpu.config import ServerConfig

    cfg = _cfg()
    api = FedOptAPI(cfg, _data(), _model(), log_fn=lambda *a, **k: None)
    probe = program_cache.get_or_build(
        "server_opt",
        {
            "kind": "fedopt_server_step",
            "server": cfg.server,
            "step_builder": make_server_step,
        },
        lambda: pytest.fail("transport-side key missed the vmap-side program"),
    )
    assert probe is api._server_step
    # a different server config is a different program
    assert probe.digest != program_digest(
        {
            "kind": "fedopt_server_step",
            "server": ServerConfig(server_lr=0.5),
            "step_builder": make_server_step,
        }
    )


def test_model_fingerprint_distinguishes_architectures():
    m1 = _model()
    m2 = ModelDef(
        module=LogisticRegression(num_classes=4), input_shape=(5,),
        num_classes=4, name="lr",
    )
    assert model_fingerprint(m1) != model_fingerprint(m2)
    assert model_fingerprint(m1) == model_fingerprint(_model())


def test_compile_summary_row_is_baseline_relative():
    pc = get_program_cache()
    base = compile_snapshot()
    pc.get_or_build("t", {"unique": "test_compile_summary_row"}, lambda: (lambda x: x))
    row = compile_summary_row(base)
    assert row["compile/cache_misses"] == 1
    assert row["compile/cache_hits"] == 0


# ---------------------------------------------------------------------------
# CachedProgram: AOT warmup surface
# ---------------------------------------------------------------------------


def test_warmup_compiles_and_dispatches_aot():
    import jax
    import jax.numpy as jnp

    pc = ProgramCache()
    prog = pc.wrap_uncached("f", jax.jit(lambda x: jnp.sin(x) + 1))
    x = np.ones((8,), np.float32)
    st = prog.warmup(x)
    assert st["aot_cache_hit"] is False
    assert st["compile_s"] > 0
    assert pc.stats()["compile_s"] == pytest.approx(st["compile_s"])
    # idempotent per signature: the second warmup is a hit
    st2 = prog.warmup(x)
    assert st2["aot_cache_hit"] is True
    # the warmed executable serves the call and matches the jit path
    np.testing.assert_array_equal(
        np.asarray(prog(x)), np.asarray(jax.jit(lambda x: jnp.sin(x) + 1)(x))
    )
    # a different shape class falls back to the ordinary jit path
    y = np.ones((4,), np.float32)
    np.testing.assert_allclose(np.asarray(prog(y)), np.sin(y) + 1, rtol=1e-6)


def test_call_signature_separates_shape_classes():
    a = (np.zeros((2, 3), np.float32),)
    b = (np.zeros((2, 3), np.float32) + 5,)
    c = (np.zeros((3, 2), np.float32),)
    assert call_signature(a) == call_signature(b)
    assert call_signature(a) != call_signature(c)


# ---------------------------------------------------------------------------
# warmup-vs-cold numerics parity (byte-identical round results)
# ---------------------------------------------------------------------------


def _tree_equal(t1, t2):
    import jax

    l1, d1 = jax.tree_util.tree_flatten(t1)
    l2, d2 = jax.tree_util.tree_flatten(t2)
    assert d1 == d2
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_warmup_vs_cold_numerics_parity_vmap():
    """--warmup only lowers/compiles — it executes nothing, consumes no
    RNG, and touches no training state, so warmed runs produce
    byte-identical models (the acceptance-criteria parity clause)."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    data, model = _data(), _model()
    cold = FedAvgAPI(_cfg(), data, model)
    cold.train()
    warm = FedAvgAPI(_cfg(), data, model)
    rows = warm.warmup(log_fn=lambda r: None)
    assert "compile/warmup_s" in rows
    warm.train()
    _tree_equal(cold.global_vars, warm.global_vars)


def test_warmup_vs_cold_numerics_parity_loopback():
    from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation

    data, model = _data(), _model()
    cold = run_loopback_federation(_cfg(), data, model)
    warm = run_loopback_federation(_cfg(), data, model, warmup=True)
    _tree_equal(cold.global_vars, warm.global_vars)


# ---------------------------------------------------------------------------
# HardenedFileCache: integrity, quarantine, atomicity
# ---------------------------------------------------------------------------


def test_hardened_cache_roundtrip(tmp_path):
    c = HardenedFileCache(str(tmp_path))
    assert c.get("k1") is None
    c.put("k1", b"payload-bytes")
    assert c.get("k1") == b"payload-bytes"
    assert c.stats() == {
        "hits": 1, "misses": 1, "puts": 1, "quarantined": 0, "evicted": 0,
    }


def test_hardened_cache_size_cap_evicts_lru(tmp_path, monkeypatch):
    """jax_compilation_cache_max_size parity: the hardened store enforces
    the size cap the stock LRUCache honored, evicting least-recently-used
    entries (never the one just written)."""
    c = HardenedFileCache(str(tmp_path))
    monkeypatch.setattr(
        HardenedFileCache, "_max_size_bytes", staticmethod(lambda: 150)
    )
    c.put("old", b"x" * 60)
    time.sleep(0.05)  # distinct timestamps order the LRU scan
    c.put("mid", b"y" * 60)
    time.sleep(0.05)
    c.put("new", b"z" * 60)  # framed total now exceeds the 150-byte cap
    assert c.get("new") == b"z" * 60
    assert c.get("old") is None  # oldest evicted
    assert c.stats()["evicted"] >= 1
    assert c.stats()["quarantined"] == 0


def test_hardened_cache_first_writer_wins(tmp_path):
    c = HardenedFileCache(str(tmp_path))
    c.put("k", b"first")
    c.put("k", b"second")
    assert c.get("k") == b"first"
    assert c.stats()["puts"] == 1


def test_hardened_cache_quarantines_truncated_entry(tmp_path):
    """A torn/truncated entry returns a MISS (the program recompiles) and
    is moved into quarantine/ — never wrong bytes."""
    c = HardenedFileCache(str(tmp_path))
    c.put("k", b"x" * 256)
    (entry,) = tmp_path.glob("*.ftpc")
    blob = entry.read_bytes()
    entry.write_bytes(blob[: len(blob) // 2])
    assert c.get("k") is None
    assert c.stats()["quarantined"] == 1
    assert not entry.exists()
    assert len(list((tmp_path / "quarantine").iterdir())) == 1
    # the slot is writable again — recompile then hit
    c.put("k", b"y" * 256)
    assert c.get("k") == b"y" * 256


def test_hardened_cache_rejects_bit_rot(tmp_path):
    c = HardenedFileCache(str(tmp_path))
    c.put("k", b"A" * 64)
    (entry,) = tmp_path.glob("*.ftpc")
    blob = bytearray(entry.read_bytes())
    blob[-1] ^= 0xFF  # flip one payload bit
    entry.write_bytes(bytes(blob))
    assert c.get("k") is None
    assert c.stats()["quarantined"] == 1


def test_hardened_cache_ignores_stock_format_files(tmp_path):
    """A directory previously populated by the stock jax cache is treated
    as empty (our entries carry the .ftpc suffix + magic), not misread."""
    (tmp_path / "jit_foo-deadbeef").write_bytes(b"stock cache bytes")
    c = HardenedFileCache(str(tmp_path))
    assert c.get("jit_foo-deadbeef") is None
    assert c.stats()["quarantined"] == 0


# ---------------------------------------------------------------------------
# end-to-end: real jax compiles through the hardened store (subprocesses)
# ---------------------------------------------------------------------------

_E2E_PROG = r"""
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from fedml_tpu.compile import install_hardened_cache
c = install_hardened_cache(sys.argv[1], min_compile_time_secs=0.0)
f = jax.jit(lambda x: jnp.sin(x) @ x.T)
x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64) / 4096.0
r = np.asarray(f(x))
print(json.dumps({"stats": c.stats(), "sum": float(r.sum())}))
"""


def _run_e2e(cache_dir):
    out = subprocess.run(
        [sys.executable, "-c", _E2E_PROG, str(cache_dir)],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_e2e_persistent_cache_hit_and_corruption_recovery(tmp_path):
    """Three fresh processes over one cache dir: (1) cold compile + put;
    (2) integrity-verified hit; (3) after on-disk truncation, the loader
    quarantines and RECOMPILES to the same numerics instead of
    deserializing garbage — the PR 3 incident class, closed."""
    r1 = _run_e2e(tmp_path)
    assert r1["stats"]["puts"] >= 1
    r2 = _run_e2e(tmp_path)
    assert r2["stats"]["hits"] >= 1
    assert r2["sum"] == r1["sum"]
    for p in pathlib.Path(tmp_path).glob("*.ftpc"):
        blob = p.read_bytes()
        p.write_bytes(blob[: len(blob) // 2])
    r3 = _run_e2e(tmp_path)
    assert r3["stats"]["quarantined"] >= 1
    assert r3["stats"]["hits"] == 0
    assert r3["sum"] == r1["sum"]
    assert (pathlib.Path(tmp_path) / "quarantine").exists()


# ---------------------------------------------------------------------------
# session fixture contract
# ---------------------------------------------------------------------------


def test_program_cache_fixture_is_the_global_registry(program_cache):
    assert program_cache is get_program_cache()


def test_install_run_cache_restores_previous_binding(tmp_path):
    """A run-scoped cache install must not hijack later compiles in a
    long-lived process: restore() reinstates the prior binding (here: the
    conftest-installed shared hardened store)."""
    import jax

    prev = installed_cache()
    prev_dir = jax.config.jax_compilation_cache_dir
    cache, restore = install_run_cache(str(tmp_path), min_compile_time_secs=3.0)
    assert installed_cache() is cache
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    restore()
    assert installed_cache() is prev
    assert jax.config.jax_compilation_cache_dir == prev_dir


# ---------------------------------------------------------------------------
# where the caches live (ISSUE 21 E) and what a loaded executable is bound
# to (ISSUE 21 finding 4)
# ---------------------------------------------------------------------------


def test_cache_dir_env_wins_over_an_explicit_request(
    tmp_path, monkeypatch, caplog
):
    """JAX_COMPILATION_CACHE_DIR set -> that directory IS the cache: an
    explicit --compile_cache_dir only earns a warning, jax's config is
    never pointed anywhere else, and the executable store sits inside."""
    import jax

    env_dir, flag_dir = tmp_path / "env", tmp_path / "flag"
    monkeypatch.setenv(CACHE_DIR_ENV, str(env_dir))
    assert resolve_cache_dir() == env_dir
    with caplog.at_level(logging.WARNING):
        assert resolve_cache_dir(str(flag_dir)) == env_dir
    assert CACHE_DIR_ENV in caplog.text and str(flag_dir) in caplog.text
    assert resolve_executable_cache_dir(str(flag_dir)) == env_dir / "executables"
    cache, restore = install_run_cache(str(flag_dir))
    try:
        assert cache.path == env_dir
        assert jax.config.jax_compilation_cache_dir == str(env_dir)
        assert not flag_dir.exists()
    finally:
        restore()


def test_cache_dir_defaults_to_the_checkout():
    """Env unset -> <checkout>/.jax_cache (git-ignored), executables/
    beside the HLO entries; an explicit request is then honoured."""
    assert resolve_cache_dir() == _REPO / ".jax_cache"
    assert resolve_executable_cache_dir() == _REPO / ".jax_cache" / "executables"
    assert resolve_cache_dir("/data/xla") == pathlib.Path("/data/xla")
    assert resolve_executable_cache_dir("/data/xc") == pathlib.Path("/data/xc")
    assert ".jax_cache/" in (_REPO / ".gitignore").read_text().split()


def test_session_stores_live_at_the_resolved_directory():
    """conftest.py's two session stores went through the resolver — not
    /tmp, not a uid- or pid-keyed name."""
    assert installed_cache().path == _SESSION_CACHE_DIR
    assert installed_executable_cache().path == _SESSION_CACHE_DIR / "executables"


def test_one_writer_of_jax_compilation_cache_dir():
    """Exactly one statement in the program, the smoke and the tests
    points jax's cache directory somewhere: the resolver's bind."""
    writer = re.compile(r'update\(\s*"jax_compilation_cache_dir"')
    files = [
        *(_REPO / "fedml_tpu").rglob("*.py"),
        *(_REPO / "tests").glob("*.py"),
        _REPO / "chip_smoke.py",
    ]
    hits = [
        str(f.relative_to(_REPO))
        for f in files
        for _ in writer.finditer(f.read_text())
    ]
    assert hits == ["fedml_tpu/compile/persistent.py"], hits


def _placed_input(placement):
    """A [8, 4] float32 input committed to one virtual device (by index)
    or sharded over all eight ("mesh")."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    assert len(devs) == 8, "conftest.py provides 8 virtual CPU devices"
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 7
    if placement == "mesh":
        return jax.device_put(
            x, NamedSharding(Mesh(np.array(devs), ("d",)), P("d"))
        )
    return jax.device_put(x, devs[placement])


@pytest.mark.parametrize("placement", [0, 3, "mesh"])
def test_executable_cache_second_run_loads_onto_the_compiled_devices(
    tmp_path, placement
):
    """Two runs over one store on the 8-virtual-device platform: run 1
    saves an executable compiled for ONE device (the default one, or a
    pinned tenant's) or for the whole mesh; run 2 — a fresh
    ExecutableCache — loads it and EXECUTES it on exactly those devices.
    jax 0.9's deserialize_and_load binds to every backend device unless
    handed the compiled assignment, which turned the one-device program
    into an 8-shard one ("Expected args to execute_sharded_on_local_
    devices to have 8 shards") and took the suite's second run down."""
    import jax.numpy as jnp

    import jax
    from fedml_tpu.compile.executable_cache import ExecutableCache

    x = _placed_input(placement)
    compiled = jax.jit(lambda v: jnp.sin(v) * 2.0).lower(x).compile()
    sig = call_signature((x,))
    assert ExecutableCache(str(tmp_path)).save("finding-4", sig, compiled)

    second_run = ExecutableCache(str(tmp_path))
    exe = second_run.load("finding-4", sig)
    assert exe is not None and second_run.stats()["hits"] == 1
    out = exe(x)
    assert out.devices() == x.devices()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(compiled(x)))


# ---------------------------------------------------------------------------
# serialized executable cache: zero-cold-start persistence (ISSUE 8)
# ---------------------------------------------------------------------------


def _exec_jit():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda v: jnp.sin(v) @ v.T)


def _exec_prog(digest_key, pc=None):
    pc = pc or ProgramCache()
    return pc.get_or_build("p", {"k": digest_key}, _exec_jit), pc


def test_executable_cache_warmup_roundtrip(tmp_path):
    """Cold warmup compiles + persists; a FRESH program object with the
    same canonical digest warms by DESERIALIZING (compile_s == 0), and
    dispatches byte-identically — the executable on disk IS the one a
    compile would have built."""
    from fedml_tpu.compile import install_run_executable_cache

    x = np.arange(36, dtype=np.float32).reshape(6, 6) / 11
    cache, restore = install_run_executable_cache(str(tmp_path))
    try:
        prog1, _ = _exec_prog("xc-roundtrip")
        st1 = prog1.warmup(x)
        assert st1["compile_s"] > 0 and not st1.get("deserialized")
        assert cache.stats()["puts"] == 1
        r1 = np.asarray(prog1(x))

        prog2, pc2 = _exec_prog("xc-roundtrip")
        st2 = prog2.warmup(x)
        assert st2["deserialized"] is True
        assert st2["compile_s"] == 0.0
        assert st2["deserialize_s"] > 0
        assert pc2.stats()["deserialize_hits"] == 1
        np.testing.assert_array_equal(r1, np.asarray(prog2(x)))
        # summary keys: the ProgramCache row carries the headline counters
        row = pc2.summary_row()
        assert row["compile/deserialize_hits"] == 1
        assert row["compile/deserialize_s"] > 0
    finally:
        restore()


def test_executable_cache_lazy_dispatch_adopts_from_disk(tmp_path):
    """A shape class nobody warmed in THIS process still dispatches with
    zero compiles when a predecessor persisted it: the first call per
    signature probes the store before paying a compile."""
    from fedml_tpu.compile import install_run_executable_cache

    x = np.arange(16, dtype=np.float32).reshape(4, 4) / 7
    cache, restore = install_run_executable_cache(str(tmp_path))
    try:
        prog1, _ = _exec_prog("xc-lazy")
        prog1.warmup(x)
        r1 = np.asarray(prog1(x))
        prog2, pc2 = _exec_prog("xc-lazy")
        r2 = np.asarray(prog2(x))  # no warmup — plain dispatch
        np.testing.assert_array_equal(r1, r2)
        assert pc2.stats()["deserialize_hits"] == 1
        assert prog2._aot  # adopted into the AOT dispatch map
    finally:
        restore()


@pytest.mark.parametrize("corruption", ["truncate", "bit_rot", "env_skew"])
def test_executable_cache_poisoned_entry_quarantined_and_recompiles(
    tmp_path, corruption
):
    """The three poisoning classes of the new on-disk format — torn
    write/truncation, bit rot, and a wrong environment fingerprint
    (version skew / a cache dir copied across machines) — must all
    quarantine the entry and RECOMPILE to identical numerics, never
    deserialize a wrong executable (the acceptance-criteria mirror of
    PR 4's corrupt-entry contract)."""
    import pickle

    from fedml_tpu.compile import install_run_executable_cache

    x = np.arange(25, dtype=np.float32).reshape(5, 5) / 9
    cache, restore = install_run_executable_cache(str(tmp_path))
    try:
        prog1, _ = _exec_prog("xc-poison")
        prog1.warmup(x)
        r1 = np.asarray(prog1(x))
        (entry,) = tmp_path.glob("xc-*.ftpc")
        blob = entry.read_bytes()
        if corruption == "truncate":
            entry.write_bytes(blob[: len(blob) // 2])
        elif corruption == "bit_rot":
            rot = bytearray(blob)
            rot[-1] ^= 0xFF
            entry.write_bytes(bytes(rot))
        else:  # env_skew: valid frame + pickle, mismatched fingerprint
            payload = HardenedFileCache._verify(blob)
            doc = pickle.loads(payload)
            doc["env"] = dict(doc["env"], jaxlib="0.0.0-skew")
            entry.write_bytes(
                HardenedFileCache._frame(
                    pickle.dumps(doc, protocol=pickle.HIGHEST_PROTOCOL)
                )
            )

        prog2, _ = _exec_prog("xc-poison")
        st2 = prog2.warmup(x)
        # the poisoned entry must NOT have been adopted: a real compile
        assert not st2.get("deserialized")
        assert st2["compile_s"] > 0
        np.testing.assert_array_equal(r1, np.asarray(prog2(x)))
        stats = cache.stats()
        assert stats["quarantined"] + stats["store"]["quarantined"] >= 1
        assert (tmp_path / "quarantine").exists()
    finally:
        restore()


def test_environment_fingerprint_pins_version_and_code():
    """The fingerprint carries everything that must match for a persisted
    executable to be safe here — jax/jaxlib versions, backend, topology,
    lowering-relevant flags, and a hash of the package source (a code
    edit must invalidate every entry)."""
    from fedml_tpu.compile import environment_fingerprint

    env = environment_fingerprint()
    for key in ("jax", "jaxlib", "backend", "device_kind", "device_count",
                "threefry_partitionable", "xla_flags", "code"):
        assert key in env, key
    assert len(env["code"]) == 64  # sha256 over the package source
    assert env == environment_fingerprint()  # stable within a process


def test_executable_cache_key_separates_environments(tmp_path):
    """Environment skew lands on a DIFFERENT key — a cache dir shared by
    two jaxlib versions never even reads the other's entries."""
    from fedml_tpu.compile.executable_cache import ExecutableCache

    c1 = ExecutableCache(str(tmp_path))
    c2 = ExecutableCache(str(tmp_path))
    sig = (("treedef"), ((4, 4), "float32"))
    k1 = c1.key_for("d" * 64, sig)
    c2._env_doc = dict(c1._env() or {}, jaxlib="0.0.0-skew")
    assert c2.key_for("d" * 64, sig) != k1
    assert c1.key_for("d" * 64, sig) == k1  # deterministic


def test_wrap_uncached_programs_never_persist(tmp_path):
    """Opaque (bypassed) programs have no canonical digest — they must
    not enter the executable store (an over-merged key would be silent
    wrong numerics, exactly the class the digest discipline exists
    for)."""
    from fedml_tpu.compile import install_run_executable_cache

    x = np.ones((4,), np.float32)
    cache, restore = install_run_executable_cache(str(tmp_path))
    try:
        prog = ProgramCache().wrap_uncached("opaque", _exec_jit())
        prog.warmup(np.ones((2, 2), np.float32))
        _ = prog(np.ones((2, 2), np.float32))
        assert cache.stats()["puts"] == 0
        assert not list(tmp_path.glob("xc-*.ftpc"))
    finally:
        restore()


# ---------------------------------------------------------------------------
# shape-class pre-enumeration: no lazy compiles after round 0 (ISSUE 8)
# ---------------------------------------------------------------------------


def _multiclass_data(sizes=(8, 33, 90)):
    """A partition spanning len(sizes) distinct bucket_steps classes at
    batch_size=8 (steps 1 / 8 / 16 — pinned below)."""
    rng = np.random.default_rng(0)
    from fedml_tpu.data.base import FederatedDataset

    return FederatedDataset(
        name="multiclass",
        client_x=[rng.normal(size=(n, 5)).astype(np.float32) for n in sizes],
        client_y=[rng.integers(0, 3, size=(n,)).astype(np.int32) for n in sizes],
        test_x=rng.normal(size=(20, 5)).astype(np.float32),
        test_y=rng.integers(0, 3, size=(20,)).astype(np.int32),
        num_classes=3,
    )


def _multiclass_cfg():
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=3, client_num_per_round=1, comm_round=8,
            epochs=1, frequency_of_the_test=1,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )


def test_partition_shape_classes_enumerates_singleton_buckets():
    from fedml_tpu.data.base import partition_shape_classes

    classes = partition_shape_classes([8, 33, 90], 8, 1)
    assert set(classes) == {(1, 8), (8, 8), (16, 8)}
    assert classes[(1, 8)] == 0 and classes[(16, 8)] == 2


@pytest.fixture
def warmed_multiclass_api(program_cache):
    """A warmed API over a >=3-shape-class partition, plus a completed
    cold run of the identical config — so every utility program (metric
    packing, RNG folds, the flush concat) is already compiled and the
    recompile budget below measures EXACTLY the lazy shape-bucket
    compiles warmup is supposed to have eliminated."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    data, cfg = _multiclass_data(), _multiclass_cfg()
    model = _model()
    cold = FedAvgAPI(cfg, data, model)
    cold.train()
    # sanity: the round-seeded draws really visit all three classes
    visited = {cold._round_plan(r)[0][0] for r in range(cfg.fed.comm_round)}
    assert visited == {0, 1, 2}, visited
    warm = FedAvgAPI(cfg, data, model)
    rows = warm.warmup(log_fn=lambda r: None)
    # the warmup set was derived from the PARTITION, not round 0's cohort
    for klass in ("s1b8", "s8b8", "s16b8"):
        assert f"compile/round_{klass}_compile_s" in rows, sorted(rows)
    return cold, warm


@pytest.mark.recompile_budget(0)
def test_no_lazy_shape_bucket_compiles_after_warmup(
    warmed_multiclass_api, recompile_sentinel
):
    """ISSUE 8 acceptance: a multi-round run whose client sizes span >= 3
    bucket_steps classes runs with a post-warmup recompile budget of ZERO
    — rounds 1..R never hit a lazy shape-bucket compile (the fixture runs
    before the sentinel starts, so the budget window is exactly
    post-warmup) — and stays byte-identical to the cold run."""
    cold, warm = warmed_multiclass_api
    warm.train()
    _tree_equal(cold.global_vars, warm.global_vars)


def test_warmup_local_train_covers_whole_partition():
    """The transport warmup barrier enumerates every shape class in the
    partition (client_ids=None default), not just round 0's cohort — a
    later round's differently-bucketed client must not race a lazy
    compile against the deadline."""
    from fedml_tpu.compile import warmup_local_train
    from fedml_tpu.algorithms.fedavg_transport import shared_local_train

    data, cfg = _multiclass_data(), _multiclass_cfg()
    model = _model()
    gv = model.init(__import__("jax").random.PRNGKey(0))
    rows = warmup_local_train(
        shared_local_train(model, cfg, "classification"), cfg, data, gv
    )
    labels = {k for k in rows if k.startswith("compile/local_train_s")}
    assert {
        "compile/local_train_s1b8_compile_s",
        "compile/local_train_s8b8_compile_s",
        "compile/local_train_s16b8_compile_s",
    } <= labels, sorted(labels)


# ---------------------------------------------------------------------------
# end-to-end: zero-cold-start across REAL process boundaries (subprocesses)
# ---------------------------------------------------------------------------

_XC_E2E_PROG = r"""
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from fedml_tpu.compile import ProgramCache, install_executable_cache
from fedml_tpu.analysis.sentinel import RecompileSentinel
cache = install_executable_cache(sys.argv[1])
s = RecompileSentinel().start()
pc = ProgramCache()
prog = pc.get_or_build(
    "p", {"k": "xc-e2e"}, lambda: jax.jit(lambda v: jnp.sin(v) @ v.T)
)
x = np.arange(64 * 64, dtype=np.float32).reshape(64, 64) / 4096.0
st = prog.warmup(x)
r = np.asarray(prog(x))
s.stop()
print(json.dumps({
    "stats": cache.stats(), "deserialized": bool(st.get("deserialized")),
    "recompiles": s.recompiles(), "sum": float(r.sum()),
}))
"""


def _run_xc_e2e(cache_dir):
    out = subprocess.run(
        [sys.executable, "-c", _XC_E2E_PROG, str(cache_dir)],
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_e2e_executable_cache_zero_cold_start_and_poison_recovery(tmp_path):
    """Three fresh processes over one executable-cache dir: (1) cold
    warmup compiles + persists; (2) a FRESH PROCESS deserializes instead
    of compiling — zero backend compiles, identical numerics (the
    zero-cold-start contract); (3) after on-disk corruption the loader
    quarantines and recompiles to the same numerics — never a wrong
    executable."""
    r1 = _run_xc_e2e(tmp_path)
    assert r1["stats"]["puts"] >= 1 and not r1["deserialized"]
    assert r1["recompiles"] >= 1
    r2 = _run_xc_e2e(tmp_path)
    assert r2["deserialized"] is True
    assert r2["stats"]["hits"] >= 1
    assert r2["recompiles"] == 0, r2
    assert r2["sum"] == r1["sum"]
    for p in pathlib.Path(tmp_path).glob("xc-*.ftpc"):
        blob = p.read_bytes()
        p.write_bytes(blob[: len(blob) // 2])
    r3 = _run_xc_e2e(tmp_path)
    assert not r3["deserialized"]
    assert r3["stats"]["quarantined"] + r3["stats"]["store"]["quarantined"] >= 1
    assert r3["sum"] == r1["sum"]


def test_class_enumeration_skips_unreachable_classes():
    """A class whose bucket has fewer clients at-or-below it than the
    cohort size can never be a cohort max (sampling without replacement)
    — warmup must not waste compiles and cache entries on it; a
    shrinkable (cohort=1) enumeration keeps it."""
    from fedml_tpu.compile.warmup import _classes_by_population

    counts = [8, 100, 100, 100]
    full, _ = _classes_by_population(counts, 8, 1, cohort=4)
    assert (1, 8) not in dict(full)           # unreachable at cohort 4
    assert len(full) == 1                      # only the 100-sample class
    single, _ = _classes_by_population(counts, 8, 1, cohort=1)
    assert (1, 8) in dict(single)              # reachable as a singleton
