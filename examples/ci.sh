#!/usr/bin/env bash
# CI gate — the TPU-native analog of the reference's shell-script CI
# (CI-script-fedavg.sh / CI-script-framework.sh / CI-install.sh pattern,
# SURVEY §4): lint gate, fast unit tier, end-to-end CLI smoke runs on tiny
# configs, and the federated==centralized oracle. Unlike the reference's
# fire-and-forget background runs (CI-script-framework.sh:16-23 — no exit
# code checked), every step here fails the gate.
set -euo pipefail
cd "$(dirname "$0")/.."

# syntax gate only — pyflakes isn't in this image; the ref's pyflakes gate
# (CI-script-*.sh:6) additionally catches undefined names/unused imports
echo "== syntax gate =="
python -m compileall -q fedml_tpu tests __graft_entry__.py

# fedlint JIT-hazard gate (docs/ANALYSIS.md) — stdlib-only, runs before
# jax starts: zero unsuppressed findings or the gate is red
echo "== static analysis gate (fedlint) =="
python -m fedml_tpu.analysis --fail-on-findings

# protocol-flow + concurrency lint, called out as its OWN gate so a red
# run names the family that broke: the wire-protocol model (every sent
# type handled, no orphan constants, at-least-once handlers deduped,
# request/reply closure) and the threading model (global lock order,
# lock discipline per shared attr, scope-wrapped threads). Same walk,
# same suppressions — this is the all-rules gate above narrowed to the
# seven fedlint-v2 rules (docs/ANALYSIS.md "Protocol-flow rules").
echo "== static analysis gate (fedlint v2: protocol + concurrency) =="
python -m fedml_tpu.analysis --fail-on-findings \
  --rule sent-unhandled --rule dead-msg-type --rule retry-no-dedupe \
  --rule reply-closure \
  --rule lock-order-cycle --rule unlocked-shared-mutation \
  --rule unscoped-thread

# direction check: the gate must still DETECT. Copy the real fedbuff
# manager into a scratch tree, strip its _on_leave dedupe guard (the
# exact bug retry-no-dedupe exists for: an at-least-once redelivery
# double-counting a LEAVE), and require the lint to exit nonzero. A
# silently-vacuous analyzer passes the clean-tree gate forever; this
# keeps it honest. (tests/test_analysis.py pins the same seeded bug at
# unit granularity; this is the shell-level end-to-end of it.)
echo "== static analysis direction check: seeded bug must fail the gate =="
FLINT=$(mktemp -d)
python - "$FLINT" <<'PY'
import pathlib, sys
tmp = pathlib.Path(sys.argv[1])
guard = (
    "            if sender in self._dead_workers:\n"
    "                # duplicate LEAVE (at-least-once delivery) — already\n"
    "                # counted; re-adding would double the leaves tally\n"
    "                return\n"
)
src = pathlib.Path("fedml_tpu/algorithms/fedbuff.py").read_text()
assert guard in src, "fedbuff _on_leave dedupe guard moved — update ci.sh"
for rel, text in (
    ("pkg/algorithms/fedbuff.py", src.replace(guard, "")),
    ("pkg/core/message.py",
     pathlib.Path("fedml_tpu/core/message.py").read_text()),
):
    dest = tmp / rel
    dest.parent.mkdir(parents=True, exist_ok=True)
    dest.write_text(text)
PY
if python -m fedml_tpu.analysis "$FLINT" --rule retry-no-dedupe \
    --fail-on-findings > /dev/null 2>&1; then
  echo "  ERROR: stripped _on_leave dedupe guard was NOT detected"; exit 1
fi
rm -rf "$FLINT"
echo "  direction check ok: seeded retry-no-dedupe bug fails the gate"

export XLA_FLAGS="--xla_force_host_platform_device_count=8"
export JAX_PLATFORMS=cpu

# digest-completeness fuzzer: every registered program factory must split
# its digest whenever a config perturbation changes the lowered program
# (the SCAFFOLD eta_g silent-wrong-numerics class) — abstract lowering
# only, no compiles
echo "== digest-completeness audit =="
python -m fedml_tpu.analysis --digest-audit --fail-on-findings

echo "== fast unit tier =="
python -m pytest tests/ -q -m 'not slow' -x

echo "== CLI smoke: one round per algorithm family (ref CI-script-fedavg.sh:33-39) =="
for algo in fedavg fedopt fedprox fednova scaffold ditto dp_fedavg hierarchical fedavg_robust; do
  python -m fedml_tpu --algorithm "$algo" --model lr --dataset synthetic \
    --client_num_in_total 8 --client_num_per_round 4 --comm_round 1 \
    --epochs 1 --ci > /dev/null
  echo "  $algo ok"
done

echo "== CLI smoke: mesh runtime (8-shard virtual farm) =="
for algo in fedavg fedopt fednova scaffold ditto dp_fedavg fedavg_robust; do
  python -m fedml_tpu --algorithm "$algo" --runtime mesh --model lr \
    --dataset synthetic --client_num_in_total 8 --client_num_per_round 8 \
    --comm_round 1 --epochs 1 --ci > /dev/null
  echo "  mesh/$algo ok"
done
python -m fedml_tpu --algorithm hierarchical --runtime mesh --group_num 2 \
  --group_comm_round 2 --model lr --dataset synthetic \
  --client_num_in_total 8 --client_num_per_round 8 --comm_round 1 --ci > /dev/null
echo "  mesh/hierarchical ok"

echo "== CLI smoke: transport runtimes + compression + server opt =="
python -m fedml_tpu --algorithm fedopt --runtime loopback --model lr \
  --dataset synthetic --client_num_in_total 4 --client_num_per_round 4 \
  --comm_round 1 --ci > /dev/null
python -m fedml_tpu --algorithm fedavg --runtime loopback --compression topk \
  --topk_frac 0.25 --error_feedback --model lr --dataset synthetic \
  --client_num_in_total 4 --client_num_per_round 4 --comm_round 1 --ci > /dev/null
python -m fedml_tpu --algorithm fedavg --runtime loopback --secure_agg \
  --model lr --dataset synthetic --client_num_in_total 4 \
  --client_num_per_round 4 --comm_round 1 --ci > /dev/null
echo "  transport ok"

echo "== quantized-uplink smoke: packed 4-bit byte cut off the comm accounting =="
# ISSUE 14: the int4+error-feedback uplink must cut model-update payload
# bytes >= 4x vs the fp32 arm, READ OFF summary.json's comm/uplink_*
# counters (metered at encode time on real uploads — never asserted from
# codec math), with the final loss tracking the fp32 run (reach@target
# parity is pinned harder in tests/test_compression.py).
UPDIR=$(mktemp -d)
UPCFG="--algorithm fedavg --runtime loopback --model lr --dataset synthetic \
  --client_num_in_total 4 --client_num_per_round 4 --comm_round 8 \
  --batch_size 8 --frequency_of_the_test 8"
python -m fedml_tpu $UPCFG --log_dir "$UPDIR/fp32" \
  --telemetry_dir "$UPDIR/fp32_tel" > /dev/null
python -m fedml_tpu $UPCFG --compression int4 --error_feedback \
  --log_dir "$UPDIR/int4" --telemetry_dir "$UPDIR/int4_tel" > /dev/null
python - "$UPDIR" <<'PY'
import json, sys
fp = json.load(open(f"{sys.argv[1]}/fp32/summary.json"))
q = json.load(open(f"{sys.argv[1]}/int4/summary.json"))
assert fp["comm/uplink_bytes"] == fp["comm/uplink_raw_bytes"] > 0, fp
cut = q["comm/uplink_raw_bytes"] / max(q["comm/uplink_bytes"], 1)
assert cut >= 4.0, (cut, q["comm/uplink_bytes"], q["comm/uplink_raw_bytes"])
assert abs(q["Test/Loss"] - fp["Test/Loss"]) < 0.05, (q["Test/Loss"], fp["Test/Loss"])
print(f"  quantized uplink ok: {cut:.1f}x byte cut "
      f"({int(q['comm/uplink_raw_bytes'])} -> {int(q['comm/uplink_bytes'])} B), "
      f"loss {q['Test/Loss']:.4f} vs fp32 {fp['Test/Loss']:.4f}")
PY
rm -rf "$UPDIR"

echo "== pipelined-round gate: host prep hidden behind the device, byte-identical (docs/ARCHITECTURE.md 'Round pipelining') =="
# ISSUE 17: while round r's program runs on device, the host prepares
# round r+1 and commits at the boundary. Gates read MEASUREMENT, never
# config echoes: flight.json's folded records must carry overlap_s > 0
# (the prepare wall actually overlapped dispatch), summary.json's
# fed/pipeline_rounds counts the rounds prepared ahead, numerics are
# byte-identical to --pipeline off, and measured throughput must not
# regress. The throughput arm is min-of-2 on millisecond rounds (a
# transient load spike on a shared runner can hand serial the win without
# any product defect), so one loss retries; the parity/overlap gates are
# exact every attempt.
PLDIR=$(mktemp -d)
PLCFG="--algorithm fedavg --model lr --dataset synthetic \
  --client_num_in_total 32 --client_num_per_round 8 --comm_round 24 \
  --batch_size 8 --frequency_of_the_test 10000"
for pl_attempt in 1 2; do
  rm -rf "$PLDIR/serial" "$PLDIR/serial_tel" "$PLDIR/pipe" "$PLDIR/pipe_tel"
  python -m fedml_tpu $PLCFG --pipeline off \
    --log_dir "$PLDIR/serial" --telemetry_dir "$PLDIR/serial_tel" > /dev/null
  python -m fedml_tpu $PLCFG --pipeline on \
    --log_dir "$PLDIR/pipe" --telemetry_dir "$PLDIR/pipe_tel" > /dev/null
  if python - "$PLDIR" <<'PY'
import json, sys
d = sys.argv[1]
p = json.load(open(f"{d}/pipe/summary.json"))
s = json.load(open(f"{d}/serial/summary.json"))
sys.exit(0 if p["flight/rounds_per_s"] >= s["flight/rounds_per_s"] else 1)
PY
  then break; fi
  [ "$pl_attempt" = 2 ] || echo "  pipelined arm lost on wall clock once (timing noise?) — retrying"
done
python - "$PLDIR" <<'PY'
import json, sys
d = sys.argv[1]
p = json.load(open(f"{d}/pipe/summary.json"))
s = json.load(open(f"{d}/serial/summary.json"))
# the pipeline really ran (rounds prepared ahead), the serial arm never did
assert p["fed/pipeline_rounds"] > 0, p
assert "fed/pipeline_rounds" not in s, s
# measured overlap off the flight recorder's folded records, not a config echo
fl = json.load(open(f"{d}/pipe_tel/flight.json"))
overlapped = [r for r in fl["records"] if r.get("overlap_s", 0) > 0]
assert overlapped, fl["records"]
assert p["flight/overlap_s"] > 0, p
assert p["flight/pipelined_rounds"] == len(overlapped), p
sfl = json.load(open(f"{d}/serial_tel/flight.json"))
assert not any("overlap_s" in r for r in sfl["records"]), sfl["records"]
# preparing ahead never touches numerics
assert p["Train/Loss"] == s["Train/Loss"], (p["Train/Loss"], s["Train/Loss"])
assert p["Test/Loss"] == s["Test/Loss"], (p["Test/Loss"], s["Test/Loss"])
# throughput floor even after the retry: a pipelined run materially
# slower than serial is a regression, not noise
rps_p, rps_s = p["flight/rounds_per_s"], s["flight/rounds_per_s"]
assert rps_p >= 0.9 * rps_s, (rps_p, rps_s)
print(f"  pipelined rounds ok: {int(p['fed/pipeline_rounds'])} rounds prepared "
      f"ahead, {p['flight/overlap_s']*1e3:.1f} ms host work overlapped, "
      f"{rps_p:.1f} r/s pipelined vs {rps_s:.1f} serial, numerics identical")
PY
rm -rf "$PLDIR"

echo "== quantized-downlink smoke: int8 broadcast byte cut off the comm accounting =="
# The downlink mirror of the uplink gate: --downlink_compression int8
# range-quantizes the model ONCE per round and fans the same payload out
# to the cohort. The cut factor is READ OFF comm/downlink_* (metered at
# broadcast encode time on real sends); the fp32 arm must meter
# payload == raw (ratio exactly 1), and accuracy must track fp32. The lr
# row's int8 scales dilute the ratio, so the floor is 2x here (a model
# that dwarfs its per-leaf scales approaches 4x).
DLDIR=$(mktemp -d)
DLCFG="--algorithm fedavg --runtime loopback --model lr --dataset synthetic \
  --client_num_in_total 4 --client_num_per_round 4 --comm_round 8 \
  --batch_size 8 --frequency_of_the_test 8"
python -m fedml_tpu $DLCFG --log_dir "$DLDIR/fp32" \
  --telemetry_dir "$DLDIR/fp32_tel" > /dev/null
python -m fedml_tpu $DLCFG --downlink_compression int8 \
  --log_dir "$DLDIR/int8" --telemetry_dir "$DLDIR/int8_tel" > /dev/null
python - "$DLDIR" <<'PY'
import json, sys
fp = json.load(open(f"{sys.argv[1]}/fp32/summary.json"))
q = json.load(open(f"{sys.argv[1]}/int8/summary.json"))
assert fp["comm/downlink_bytes"] == fp["comm/downlink_raw_bytes"] > 0, fp
cut = q["comm/downlink_raw_bytes"] / max(q["comm/downlink_bytes"], 1)
assert cut >= 2.0, (cut, q["comm/downlink_bytes"], q["comm/downlink_raw_bytes"])
assert q["comm/downlink_updates"] == fp["comm/downlink_updates"] > 0, (fp, q)
assert abs(q["Test/Loss"] - fp["Test/Loss"]) < 0.05, (q["Test/Loss"], fp["Test/Loss"])
print(f"  quantized downlink ok: {cut:.1f}x byte cut "
      f"({int(q['comm/downlink_raw_bytes'])} -> {int(q['comm/downlink_bytes'])} B "
      f"over {int(q['comm/downlink_updates'])} broadcasts), "
      f"loss {q['Test/Loss']:.4f} vs fp32 {fp['Test/Loss']:.4f}")
PY
rm -rf "$DLDIR"

echo "== CLI smoke: async federation (fedbuff, barrier-free) =="
for rt in loopback shm; do
  python -m fedml_tpu --algorithm fedbuff --runtime "$rt" --model lr \
    --dataset synthetic --client_num_in_total 6 --client_num_per_round 3 \
    --comm_round 2 --async_buffer_k 2 > /dev/null
  echo "  fedbuff/$rt ok"
done

echo "== telemetry smoke: 3-round loopback federation with --telemetry_dir =="
TELDIR=$(mktemp -d)
python -m fedml_tpu --algorithm fedavg --runtime loopback --model lr \
  --dataset synthetic --client_num_in_total 4 --client_num_per_round 4 \
  --comm_round 3 --batch_size 8 --telemetry_dir "$TELDIR" \
  --log_dir "$TELDIR/logs" > /dev/null
python - "$TELDIR" <<'PY'
import json, sys
tdir = sys.argv[1]
doc = json.load(open(f"{tdir}/trace.json"))  # must parse as Chrome trace
spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
rounds = lambda n: sorted(e["args"]["round"] for e in spans if e["name"] == n)
assert rounds("round") == rounds("broadcast") == rounds("aggregate") == [0, 1, 2], \
    {n: rounds(n) for n in ("round", "broadcast", "aggregate")}
health = json.load(open(f"{tdir}/health.json"))
assert sorted(health) == ["0", "1", "2", "3"], health  # all clients seen
assert all(rec["rounds_participated"] == 3 for rec in health.values())
summary = json.load(open(f"{tdir}/logs/summary.json"))
assert summary["telemetry/comm_bytes_sent"] > 0
assert summary["telemetry/comm_bytes_received"] == summary["telemetry/comm_bytes_sent"]
print(f"  telemetry ok: {len(spans)} spans, "
      f"{int(summary['telemetry/comm_messages_sent'])} messages, "
      f"{int(summary['telemetry/comm_bytes_sent'])} bytes")
PY
rm -rf "$TELDIR"

echo "== scheduler smoke: power-of-choice + fault-injected quorum rounds =="
SCHEDDIR=$(mktemp -d)
python -m fedml_tpu --algorithm fedavg --runtime loopback --model lr \
  --dataset synthetic --client_num_in_total 6 --client_num_per_round 3 \
  --comm_round 3 --batch_size 8 --selection power_of_choice \
  --deadline_s 2 --min_clients 2 \
  --fault_plan '{"seed": 1, "clients": {"1": {"dropout_p": 1.0}}}' \
  --log_dir "$SCHEDDIR/logs" --telemetry_dir "$SCHEDDIR" > /dev/null
python - "$SCHEDDIR" <<'PY'
import json, sys
tdir = sys.argv[1]
summary = json.load(open(f"{tdir}/logs/summary.json"))
# summary.json records the selected-client set and the injected faults
assert summary["scheduler/policy"] == "power_of_choice", summary
sel = summary["scheduler/selected"]
assert isinstance(sel, list) and len(sel) == 3, sel
assert summary["faults/dropouts"] >= 1, summary
assert summary["faults/total"] == summary["faults/dropouts"], summary
health = json.load(open(f"{tdir}/health.json"))
dropped = {c: r["faults"] for c, r in health.items() if r.get("faults")}
assert dropped.get("1", {}).get("dropout", 0) >= 1, health
doc = json.load(open(f"{tdir}/trace.json"))
kinds = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
assert {"select", "fault"} <= kinds, kinds
print(f"  scheduler ok: selected {sel}, "
      f"{int(summary['faults/dropouts'])} injected dropouts survived via quorum")
PY
rm -rf "$SCHEDDIR"

echo "== population smoke: 1M-client synthetic federation (docs/POPULATION.md) =="
# The ROADMAP item 1 gate in CI form: a MILLION-client registry runs a
# stateful algorithm (SCAFFOLD, sharded record-major state tier) under a
# non-uniform O(cohort) selection policy (weighted, alias-sampled), a
# few rounds, recompile-budget gated — and steady-state round time must
# be flat in N (within 2x of an identical 100k-client partner run).
python - <<'PY'
import dataclasses, tempfile, time
import numpy as np
from fedml_tpu.algorithms.scaffold import ScaffoldAPI
from fedml_tpu.analysis.sentinel import RecompileSentinel
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model

base = synthetic_classification(
    num_clients=64, num_classes=10, feat_shape=(32,),
    samples_per_client=32, partition_method="hetero", seed=0)

def run(n, warm=10, timed=5):
    data = dataclasses.replace(
        base,
        client_x=[base.client_x[i % 64] for i in range(n)],
        client_y=[base.client_y[i % 64] for i in range(n)])
    cfg = RunConfig(
        data=DataConfig(batch_size=16, device_cache=False),
        fed=FedConfig(
            client_num_in_total=n, client_num_per_round=8,
            comm_round=warm + timed, epochs=1,
            frequency_of_the_test=10_000,
            selection="weighted", state_store="sharded",
            state_dir=tempfile.mkdtemp(prefix=f"fedml_tpu_ci_pop_{n}_")),
        train=TrainConfig(client_optimizer="sgd", lr=0.1), seed=0)
    api = ScaffoldAPI(cfg, data, create_model("lr", "synthetic", (32,), 10))
    assert api._state_mode == "sharded", api._state_mode
    assert api.scheduler._ctx.index is not None  # O(cohort) draws engaged
    # warm rounds cover the partition's lazy shape-bucket compiles (the
    # LDA shards are ragged by design; compile policy is compile/'s
    # subject, not this stage's) — the timed window then runs FRESH
    # rounds: selection + state gather/scatter + prefetch all included
    m = None
    for r in range(warm):
        _, m = api.train_round(r)
    float(np.asarray(m["loss_sum"]))  # sync
    t0 = time.perf_counter()
    for r in range(warm, warm + timed):
        _, m = api.train_round(r)
    float(np.asarray(m["loss_sum"]))
    return api, (time.perf_counter() - t0) / timed

sent = RecompileSentinel(budget=40, label="population_1m").start()
api_1m, s_1m = run(1_000_000)
sent.stop(); sent.check()  # raises on a compile storm
_, s_100k = run(100_000)
ratio = s_1m / s_100k
assert ratio < 2.0, f"1M round time {s_1m:.3f}s not flat in N (100k {s_100k:.3f}s)"
touched = api_1m._c_store.initialized_count()
assert 0 < touched <= 8 * 15, touched     # cohort rows only, never O(N)
print(f"  population ok: 1M clients at {1/s_1m:.1f} r/s fresh-round "
      f"(100k partner {1/s_100k:.1f} r/s, ratio {ratio:.2f} < 2), "
      f"{touched} state rows touched, recompiles within budget")
PY

echo "== compile warmup smoke: AOT warmup + hardened persistent cache (docs/COMPILE.md) =="
# Same config twice over ONE cache dir: the scan-LSTM round compiles
# slowly enough (>= 2 s) to clear the conservative persistence threshold,
# so run 2 must LOAD its compile (persistent hit) and report strictly
# lower measured compile time — and warmup runs are numerically identical.
# The cache stages below need FRESH directories they place themselves; where
# JAX_COMPILATION_CACHE_DIR is set it would win over --compile_cache_dir
# (compile/persistent.resolve_cache_dir), so it is cleared for the rest of
# this script.
unset JAX_COMPILATION_CACHE_DIR
CCDIR=$(mktemp -d); CLOG1=$(mktemp -d); CLOG2=$(mktemp -d)
for log in "$CLOG1" "$CLOG2"; do
  python -m fedml_tpu --algorithm fedavg --model rnn \
    --dataset shakespeare_synth --client_num_in_total 4 \
    --client_num_per_round 2 --comm_round 1 --epochs 1 --batch_size 8 \
    --warmup --compile_cache_dir "$CCDIR" --log_dir "$log" > /dev/null
done
python - "$CLOG1" "$CLOG2" <<'PY'
import json, sys
s1 = json.load(open(f"{sys.argv[1]}/summary.json"))
s2 = json.load(open(f"{sys.argv[2]}/summary.json"))
assert s1["compile/persistent_puts"] >= 1, s1   # cold run persisted a compile
assert s2["compile/persistent_hits"] > 0, s2    # repeat run loaded it
assert s2["compile/persistent_quarantined"] == 0, s2
assert s2["compile/compile_s"] < s1["compile/compile_s"], (
    s1["compile/compile_s"], s2["compile/compile_s"])
assert s1["compile/round_compile_s"] > 0 and s1["compile/cache_misses"] > 0
assert s2["Test/Loss"] == s1["Test/Loss"]       # warmup+cache never change numerics
print(f"  compile ok: warmup compile {s1['compile/compile_s']:.2f}s -> "
      f"{s2['compile/compile_s']:.2f}s with {int(s2['compile/persistent_hits'])} "
      f"persistent hit(s), numerics identical")
PY
rm -rf "$CCDIR" "$CLOG1" "$CLOG2"

echo "== zero-cold-start smoke: two fresh processes, one shared cache dir (docs/COMPILE.md) =="
# North-star config family (femnist-synth CNN), run twice as SEPARATE
# processes over one cache dir carrying both the hardened HLO cache and
# the serialized-executable store. Process 2 must dispatch its ENTIRE run
# with zero XLA compiles — the PR-5 sentinel enforces it for free via
# --recompile_budget 0 (exit 1 on any compile) — with byte-identical
# numerics and strictly lower wall time.
ZCDIR=$(mktemp -d); ZL1=$(mktemp -d); ZL2=$(mktemp -d)
ZCFG="--algorithm fedavg --model cnn --dataset femnist_synth \
  --client_num_in_total 16 --client_num_per_round 2 --comm_round 1 \
  --epochs 1 --batch_size 20 --pad_bucket 4 --frequency_of_the_test 100 \
  --warmup --executable_cache $ZCDIR --compile_cache_dir $ZCDIR \
  --compile_cache_min_s 0"
Z0=$(date +%s.%N)
python -m fedml_tpu $ZCFG --recompile_budget 500 --log_dir "$ZL1" > /dev/null
Z1=$(date +%s.%N)
python -m fedml_tpu $ZCFG --recompile_budget 0 --log_dir "$ZL2" > /dev/null
Z2=$(date +%s.%N)
python - "$ZL1" "$ZL2" "$Z0" "$Z1" "$Z2" <<'PY'
import json, sys
s1 = json.load(open(f"{sys.argv[1]}/summary.json"))
s2 = json.load(open(f"{sys.argv[2]}/summary.json"))
w1 = float(sys.argv[4]) - float(sys.argv[3])
w2 = float(sys.argv[5]) - float(sys.argv[4])
assert s1["compile/recompiles"] > 0, s1          # run 1 really compiled
assert s1["compile/executable_puts"] > 0, s1     # ...and exported executables
assert s2["compile/recompiles"] == 0, s2         # zero cold start (sentinel-verified)
assert s2["compile/deserialize_hits"] > 0, s2    # programs came from disk
assert s2["Train/Loss"] == s1["Train/Loss"]      # warm-from-disk numerics identical
assert s2["Test/Loss"] == s1["Test/Loss"]
assert w2 < w1, (w1, w2)                         # strictly lower wall time
print(f"  zero-cold-start ok: {w1:.1f}s cold -> {w2:.1f}s warm-from-disk, "
      f"{int(s2['compile/deserialize_hits'])} executable(s) deserialized, "
      f"0 recompiles")
PY
rm -rf "$ZCDIR" "$ZL1" "$ZL2"

echo "== CLI smoke: recompile-budget sentinel =="
# a sane budget passes; budget 0 must fail loudly (exit 1) — both
# directions of the tripwire (fedml_tpu/analysis/sentinel.py)
python -m fedml_tpu --algorithm fedavg --model lr --dataset synthetic \
  --client_num_in_total 8 --client_num_per_round 4 --comm_round 2 \
  --epochs 1 --recompile_budget 150 --ci > /dev/null
if python -m fedml_tpu --algorithm fedavg --model lr --dataset synthetic \
  --client_num_in_total 8 --client_num_per_round 4 --comm_round 1 \
  --epochs 1 --recompile_budget 0 --ci > /dev/null 2>&1; then
  echo "  ERROR: --recompile_budget 0 did not fail"; exit 1
fi
echo "  recompile_budget ok"

echo "== chaos: record a fault trace, replay it byte-identically (docs/SCHEDULING.md) =="
# Record: a probabilistically-faulted quorum run — the server health
# registry logs every injected (client, round) fault event with its
# magnitude and --telemetry_dir exports it as fault_trace.json. Replay:
# --fault_plan trace:<that file> re-injects the exact events (scripted,
# not re-sampled), so the faults/* summary rows AND the numerics must be
# byte-identical. ROADMAP 5a: CI replays observed fleets, not
# hand-written JSON.
CHAOS=$(mktemp -d)
CHAOS_CFG="--algorithm fedavg --runtime loopback --model lr \
  --dataset synthetic --client_num_in_total 6 --client_num_per_round 3 \
  --comm_round 4 --batch_size 8 --deadline_s 5 --min_clients 1"
python -m fedml_tpu $CHAOS_CFG \
  --fault_plan '{"seed": 2, "default": {"dropout_p": 0.3}, "clients": {"1": {"slowdown_s": 0.02}}}' \
  --telemetry_dir "$CHAOS/rec" --log_dir "$CHAOS/rec_logs" > /dev/null
python -m fedml_tpu $CHAOS_CFG \
  --fault_plan "trace:$CHAOS/rec/fault_trace.json" \
  --telemetry_dir "$CHAOS/rep" --log_dir "$CHAOS/rep_logs" > /dev/null
python - "$CHAOS" <<'PY'
import json, sys
d = sys.argv[1]
rec = json.load(open(f"{d}/rec_logs/summary.json"))
rep = json.load(open(f"{d}/rep_logs/summary.json"))
fkeys = sorted(k for k in rec if k.startswith("faults/"))
assert fkeys, rec
diff = {k: (rec[k], rep.get(k)) for k in fkeys if rec[k] != rep.get(k)}
assert not diff, f"replayed faults diverged: {diff}"
assert rec["faults/total"] > 0, rec      # the recording run really faulted
assert rep["Test/Loss"] == rec["Test/Loss"]  # same faults -> same numerics
print(f"  trace replay ok: {({k: int(rec[k]) for k in fkeys})} byte-identical")
PY

echo "== chaos: flaky transport — injected send failures, retries survive (docs/OBSERVABILITY.md) =="
# A fault-free run vs the same config under transport chaos
# (--send_fault_p fails attempts before the wire; --send_retries redial
# with deterministic backoff). Gates: retries happened, nothing gave up,
# numerics unchanged.
python -m fedml_tpu $CHAOS_CFG \
  --telemetry_dir "$CHAOS/clean_tel" --log_dir "$CHAOS/clean_logs" > /dev/null
python -m fedml_tpu $CHAOS_CFG \
  --send_retries 6 --send_fault_p 0.25 --send_backoff_s 0.002 \
  --telemetry_dir "$CHAOS/flaky_tel" --log_dir "$CHAOS/flaky_logs" > /dev/null
python - "$CHAOS" <<'PY'
import json, sys
d = sys.argv[1]
clean = json.load(open(f"{d}/clean_logs/summary.json"))
flaky = json.load(open(f"{d}/flaky_logs/summary.json"))
assert flaky["comm/retries"] > 0, flaky
assert flaky["comm/gave_up"] == 0, flaky
assert clean["comm/retries"] == 0, clean
assert flaky["Test/Loss"] == clean["Test/Loss"], (clean, flaky)
print(f"  flaky transport ok: {int(flaky['comm/retries'])} retries, "
      f"0 gave up, numerics identical to fault-free")
PY
rm -rf "$CHAOS"

echo "== serve soak smoke: 3 concurrent tenants, churning fleet, shared executables, self-healing kill (docs/SERVING.md) =="
# Three tenants in ONE process over one device: soak_a and soak_b share a
# model family (soak_b must prove cross-tenant program sharing with
# compile/recompiles == 0 via the sentinel's per-scope attribution),
# soak_c is a distinct family running the sync path. soak_a's FedBuff
# fleet churns (joins/leaves + one refused join at max_workers). soak_d
# is SUPERVISED and killed mid-flight — the supervisor must restore it
# from its rolling checkpoint with final numerics bit-identical to an
# uninterrupted run (the PR-9 kill/resume parity, now driven
# automatically). Gates: >= 1000 rounds total, flat RSS between the warm
# mark and the end, scrapeable per-tenant metrics from one /metrics
# endpoint, tenant-labeled restart counters.
timeout 600 python - <<'PY'
import json, tempfile, threading, time, urllib.request

import jax
import numpy as np

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.serve import FederationServer, FedSession, RestartPolicy

def rss_mb():
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    raise RuntimeError("no VmRSS")

def cfg(steps, workers, k, seed, freq=10**6, total=12):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(client_num_in_total=total, client_num_per_round=workers,
                      comm_round=steps, epochs=1, frequency_of_the_test=freq,
                      async_buffer_k=k),
        train=TrainConfig(client_optimizer="sgd", lr=0.05), seed=seed,
    )

fam = synthetic_classification(num_clients=12, num_classes=4, feat_shape=(16,),
                               samples_per_client=32, partition_method="homo", seed=0)
fam_model = create_model("lr", "synthetic", (16,), 4)
other = synthetic_classification(num_clients=12, num_classes=4, feat_shape=(28,),
                                 samples_per_client=32, partition_method="homo", seed=1)
other_model = create_model("lr", "synthetic", (28,), 4)

# soak_d: the self-healing tenant (a THIRD model family so its reference
# run cannot pre-warm soak_a's programs and void the attribution gate).
# K=1 worker with async_buffer_k=1 keeps the async pipeline sequential,
# so kill/resume parity is exact, not approximate.
heal = synthetic_classification(num_clients=12, num_classes=4, feat_shape=(12,),
                                samples_per_client=32, partition_method="homo", seed=2)
heal_model = create_model("lr", "synthetic", (12,), 4)
# uninterrupted reference, run to completion before the service starts
ref = FedSession(cfg(60, 1, 1, 5), heal, heal_model, algorithm="fedbuff").run()
assert ref.server_steps == 60

srv = FederationServer(prom_port=0)
a = srv.create_session("soak_a", cfg(380, 3, 2, 0), fam, fam_model,
                       algorithm="fedbuff", max_workers=4)
b = srv.create_session("soak_b", cfg(420, 3, 2, 7), fam, fam_model,
                       algorithm="fedbuff", max_workers=4)
c = srv.create_session("soak_c", cfg(250, 2, 0, 3, freq=250),
                       other, other_model, algorithm="fedavg")

killed = {"done": False}
def chaos_kill(row):
    # one-shot mid-flight kill at step 20: the crash surfaces in the
    # server FSM, the supervisor restarts the tenant from its rolling
    # checkpoint, and the continuation must be bit-identical
    if row.get("server_step") == 20 and not killed["done"]:
        killed["done"] = True
        raise RuntimeError("soak chaos kill")

heal_dir = tempfile.mkdtemp(prefix="fedml_soak_heal_")
d = srv.create_session("soak_d", cfg(60, 1, 1, 5), heal, heal_model,
                       algorithm="fedbuff",
                       restart=RestartPolicy(budget=2, backoff_base_s=0.05),
                       checkpoint_path=f"{heal_dir}/ck", checkpoint_every=1,
                       log_fn=chaos_kill)

# soak_a first: the family's compiles are attributed to it; soak_b joins
# once the family is warm and must compile NOTHING
srv.start(names=["soak_a"])
t0 = time.time()
while a.server.server_steps < 60:
    assert time.time() - t0 < 180, "soak_a stalled"
    time.sleep(0.05)
srv.start(names=["soak_b", "soak_c", "soak_d"])

# churn soak_a's fleet. Each transition waits for the server-side
# counter so the sequence is deterministic: the backpressure probe sees
# the fleet exactly AT max_workers, and every cycle's join finds the
# prior leave already processed (live 3 < 4 -> admitted).
def _until(pred, what):
    t1 = time.time()
    while not pred():
        assert time.time() - t1 < 60, f"churn stalled waiting for {what}"
        time.sleep(0.01)

def churn():
    a.add_worker()  # fleet 3 -> 4: admitted, now AT max_workers
    _until(lambda: a.server.joins_accepted >= 1, "probe admission")
    a.add_worker()  # fleet at max_workers=4 -> refused with FINISH
    _until(lambda: a.server.joins_refused >= 1, "backpressure refusal")
    a.remove_worker()  # back to 3 so the cycles oscillate 2<->3 live
    _until(lambda: a.server.leaves >= 1, "probe leave")
    for i in range(12):
        a.remove_worker()
        _until(lambda: a.server.leaves >= i + 2, "cycle leave")
        a.add_worker()
        _until(lambda: a.server.joins_accepted >= i + 2, "cycle admission")
churner = threading.Thread(target=churn, daemon=True)
churner.start()

while not (a.server.server_steps >= 150 and b.server.server_steps >= 50):
    assert time.time() - t0 < 300, "warm mark never reached"
    time.sleep(0.05)
warm_rss = rss_mb()

# per-tenant metrics scrapeable mid-flight from ONE endpoint
body = urllib.request.urlopen(
    f"http://127.0.0.1:{srv.prom_port}/metrics").read().decode()
for t in ("soak_a", "soak_b", "soak_c"):
    assert f'tenant="{t}"' in body, f"missing {t} in /metrics"
assert body.count("# TYPE fedml_comm_messages_sent_total counter") == 1

# live introspection mid-flight (serve/introspect.py), same port as
# /metrics: /status with ADVANCING rounds, /tenants/soak_d showing its
# self-healing restart, /compile, and the k8s-shaped /healthz
def _fetch(path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{srv.prom_port}{path}") as r:
        return r.status, json.loads(r.read().decode())
code, st1 = _fetch("/status")
assert code == 200 and st1["tenant_count"] == 4, st1
assert st1["tenants"]["soak_a"]["state"] == "running", st1
r1 = st1["tenants"]["soak_a"]["rounds_completed"]
_until(lambda: a.server.server_steps > r1 + 1, "/status rounds advancing")
code, st2 = _fetch("/status")
assert st2["tenants"]["soak_a"]["rounds_completed"] > r1, (st1, st2)
assert st2["tenants"]["soak_a"]["device"], st2
_until(lambda: d.restarts >= 1, "soak_d's supervised restart")
code, td = _fetch("/tenants/soak_d")
assert code == 200 and td["status"]["supervisor/restarts"] == 1, td
assert len(td["flight"]["tail"]) >= 1, td
# restarts_total already visible MID-FLIGHT, tenant-labeled
mid = urllib.request.urlopen(
    f"http://127.0.0.1:{srv.prom_port}/metrics").read().decode()
assert any(
    ln.startswith("fedml_session_restarts_total{")
    and 'tenant="soak_d"' in ln and ln.endswith(" 1.0")
    for ln in mid.splitlines()), "soak_d restart not in mid-flight scrape"
code, comp = _fetch("/compile")
assert code == 200 and "programs" in comp, comp
code, hz = _fetch("/healthz")
assert code == 200 and hz["status"] == "ok", hz
print(f"  introspection ok: /status rounds {r1} -> "
      f"{st2['tenants']['soak_a']['rounds_completed']}, soak_d restart "
      f"visible in /tenants + /metrics, /compile + /healthz answering")

churner.join(timeout=120)
results = srv.wait(timeout=420)
end_rss = rss_mb()
final_metrics = srv.render_metrics()
srv.close()

assert all(r["ok"] for r in results.values()), results
# self-healing: the killed tenant recovered (1 restart), reached its
# target, and its final model is bit-identical to never having died
assert killed["done"], "the chaos kill never fired"
assert d.restarts == 1, d.restarts
assert d.server.server_steps == 60
for la, lb in zip(jax.tree_util.tree_leaves(ref.global_vars),
                  jax.tree_util.tree_leaves(d.global_vars)):
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
assert results["soak_d"]["summary"]["supervisor/restarts"] == 1
assert results["soak_d"]["summary"]["supervisor/health"] == "degraded"
# tenant-scoped samples carry tenant= AND device= labels now
assert any(
    ln.startswith("fedml_session_restarts_total{")
    and 'tenant="soak_d"' in ln and 'device="' in ln
    and ln.endswith(" 1.0")
    for ln in final_metrics.splitlines()), "soak_d restarts not labeled"
import shutil
shutil.rmtree(heal_dir, ignore_errors=True)
total_rounds = (a.server.server_steps + b.server.server_steps
                + len(c.history))
assert a.server.server_steps == 380 and b.server.server_steps == 420
assert len(c.history) == 250
assert total_rounds >= 1000, total_rounds
# elastic churn really happened, incl. one backpressure refusal
assert a.server.joins_accepted >= 13, a.server.joins_accepted
assert a.server.leaves >= 13, a.server.leaves
assert a.server.joins_refused >= 1, a.server.joins_refused
# flat memory: no monotonic growth across ~800 post-warm rounds
growth = end_rss - warm_rss
assert growth < 64.0, f"RSS grew {growth:.1f} MB ({warm_rss:.0f} -> {end_rss:.0f})"
# cross-tenant executable sharing PROVEN, not assumed: the second
# same-family tenant triggered zero XLA compiles of its own
assert a.scope.recompiles() > 0, "attribution vacuous: soak_a compiled nothing?"
assert b.scope.recompiles() == 0, b.scope.recompiles()
print(f"  soak ok: {total_rounds} rounds across 3 tenants "
      f"(+60 self-healed in soak_d), "
      f"{a.server.joins_accepted} joins / {a.server.leaves} leaves / "
      f"{a.server.joins_refused} refused, RSS {warm_rss:.0f} -> "
      f"{end_rss:.0f} MB, soak_b recompiles == 0 "
      f"(soak_a paid {a.scope.recompiles()}), soak_d restored "
      f"bit-identical after 1 mid-flight kill")
PY

echo "== serve CLI smoke: multi-tenant spec -> per-tenant summary rows =="
SRVDIR=$(mktemp -d)
cat > "$SRVDIR/spec.json" <<'EOF'
{"tenants": [
  {"name": "cli_sync", "algorithm": "fedavg", "runtime": "loopback",
   "model": "lr", "dataset": "synthetic", "client_num_in_total": 6,
   "client_num_per_round": 3, "comm_round": 3, "batch_size": 8,
   "frequency_of_the_test": 3},
  {"name": "cli_async", "algorithm": "fedbuff", "runtime": "shm",
   "model": "lr", "dataset": "synthetic", "client_num_in_total": 6,
   "client_num_per_round": 2, "comm_round": 4, "batch_size": 8,
   "async_buffer_k": 2, "frequency_of_the_test": 100}
]}
EOF
python -m fedml_tpu serve --spec "$SRVDIR/spec.json" \
  --log_dir "$SRVDIR/logs" > /dev/null
python - "$SRVDIR" <<'PY'
import json, sys
d = sys.argv[1]
agg = json.load(open(f"{d}/logs/summary.json"))
assert agg["tenants/cli_sync/state"] == "done", agg
assert agg["tenants/cli_async/server_steps"] == 4, agg
assert agg["tenants/cli_sync/comm_bytes_sent"] > 0
t = json.load(open(f"{d}/logs/cli_sync/summary.json"))
assert "Test/Acc" in t, t
print("  serve CLI ok: per-tenant rows in one summary.json + full "
      "per-tenant logs")
PY
rm -rf "$SRVDIR"

echo "== serve SLO smoke: breach -> degraded (0 restarts) + --slo_strict exit 4 =="
# An absurd slo_round_s makes every round a breach: without --slo_strict
# the run exits 0 with the breach in slo/* keys and health degraded —
# WITHOUT consuming the restart budget (a breach is a signal, not a
# crash); with --slo_strict the same spec must exit 4 (the CI hook).
SLODIR=$(mktemp -d)
cat > "$SLODIR/spec.json" <<'EOF'
{"tenants": [
  {"name": "slo_t", "algorithm": "fedavg", "runtime": "loopback",
   "model": "lr", "dataset": "synthetic", "client_num_in_total": 6,
   "client_num_per_round": 3, "comm_round": 2, "batch_size": 8,
   "frequency_of_the_test": 100, "slo_round_s": 1e-9,
   "restart_budget": 2}
]}
EOF
python -m fedml_tpu serve --spec "$SLODIR/spec.json" > "$SLODIR/out.json"
python - "$SLODIR" <<'PY'
import json, sys
t = json.load(open(f"{sys.argv[1]}/out.json"))["slo_t"]
assert t["ok"], t                       # breaches never fail the tenant...
assert t["slo/breached"] == 1, t        # ...but they are loudly recorded
assert t["slo/round_s"] >= 1, t
assert t["supervisor/health"] == "degraded", t
assert t["supervisor/restarts"] == 0, t  # degraded WITHOUT burning budget
print(f"  slo ok: {int(t['slo/breaches_total'])} breach(es), health "
      "degraded, 0 restarts burned")
PY
set +e
python -m fedml_tpu serve --spec "$SLODIR/spec.json" --slo_strict > /dev/null 2>&1
SLORC=$?
set -e
if [ "$SLORC" -ne 4 ]; then
  echo "  ERROR: --slo_strict exited $SLORC, expected 4"; exit 1
fi
echo "  slo_strict ok: breaching tenant -> exit 4"
rm -rf "$SLODIR"

echo "== serve control-plane soak: 2 device slices, HTTP add/drain mid-flight, priced admission refusal (docs/SERVING.md 'Admin control plane') =="
# ROADMAP item-2 gate: the WRITE path on the metrics port. Two resident
# tenants pinned to DISTINCT device slices (the 8 forced host CPU
# devices above), a third ADDED mid-flight over HTTP onto the warm
# family's slice — riding the PR-9 sharing gate through the admin path
# (recompiles == 0, admission priced it warm) — a fourth REFUSED at the
# admission door with its priced reason on /status, the long resident
# DRAINED over HTTP, the supervised resident killed once and self-healed
# on its slice (PR-10 gate), per-tenant device= labels carrying the
# slice, a scrape never able to mutate (405/401), flat RSS.
timeout 600 python - <<'PY'
import json, tempfile, time, urllib.error, urllib.request

from fedml_tpu.serve import (AdmissionController, FederationServer, Placer,
                             build_slices)
from fedml_tpu.serve.cli import build_tenant
from fedml_tpu.serve.introspect import render_status

TOKEN = "ci-soak-token"

def rss_mb():
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    raise RuntimeError("no VmRSS")

def spec(name, rounds, pin, **extra):
    # one model family across every tenant, on purpose: the slice-0
    # co-tenants must share executables through the admin add path
    return {"name": name, "comm_round": rounds, "device_slice": pin,
            "client_num_in_total": 8, "client_num_per_round": 4,
            "batch_size": 8, "epochs": 1,
            "frequency_of_the_test": 10**6, **extra}

def _until(pred, what, budget=180):
    t1 = time.time()
    while not pred():
        assert time.time() - t1 < budget, f"stalled waiting for {what}"
        time.sleep(0.02)

slices = build_slices(2)  # cpu:0-3 / cpu:4-7
srv = FederationServer(
    prom_port=0, placer=Placer(slices), admin_token=TOKEN,
    admission=AdmissionController(max_tenants=3),
)
# resident_long: pinned slice 0, runs until DRAINED over HTTP
c0, d0, m0, kw0 = build_tenant(spec("resident_long", 10**6, 0))
long_t = srv.create_session("resident_long", c0, d0, m0, **kw0)
# resident_heal: pinned slice 1, SUPERVISED, killed once mid-flight
killed = {"done": False}
def chaos(row):
    if row.get("round") == 30 and "t_s" in row and not killed["done"]:
        killed["done"] = True
        raise RuntimeError("control-plane chaos kill")
heal_dir = tempfile.mkdtemp(prefix="fedml_cp_heal_")
c1, d1, m1, kw1 = build_tenant(spec(
    "resident_heal", 120, 1, restart_budget=2, restart_backoff_s=0.05,
    checkpoint_path=f"{heal_dir}/ck", checkpoint_every=1))
heal_t = srv.create_session("resident_heal", c1, d1, m1,
                            restart=kw1.pop("restart"), log_fn=chaos, **kw1)
assert long_t.device_slice is slices[0]
assert heal_t.device_slice is slices[1]
srv.start()
port = srv.prom_port

def req(path, method="GET", body=None, token=None):
    data = json.dumps(body).encode() if isinstance(body, dict) else body
    r = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                               method=method)
    if token:
        r.add_header("Authorization", f"Bearer {token}")
    try:
        with urllib.request.urlopen(r, timeout=30) as resp:
            return resp.status, json.loads(resp.read().decode() or "{}")
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read().decode())
        except ValueError:
            return e.code, {}

_until(lambda: long_t.server is not None and long_t.server.round_idx >= 40,
       "resident_long warm")
warm_rss = rss_mb()

# distinct slices visible per tenant on ONE /metrics endpoint
body = urllib.request.urlopen(
    f"http://127.0.0.1:{port}/metrics").read().decode()
for name, sl in (("resident_long", slices[0]), ("resident_heal", slices[1])):
    assert any(f'tenant="{name}"' in ln and f'device="{sl.label}"' in ln
               for ln in body.splitlines()), f"{name} not on {sl.label}"

# a scrape can never mutate: GET on a write route is 405, a write
# without (or with a bad) bearer token is 401
assert req("/tenants")[0] == 405
assert req("/tenants", "POST", spec("sneak", 2, 0))[0] == 401
assert req("/tenants", "POST", spec("sneak", 2, 0), token="wrong")[0] == 401

# live ADD over HTTP onto the warm family's slice: admission must have
# priced it WARM (measured digest probe), and the tenant must adopt the
# co-tenant's executables — zero compiles attributed to it
code, doc = req("/tenants", "POST", spec("hot_add", 40, 0), token=TOKEN)
assert code == 201, doc
assert doc["device"] == slices[0].label, doc
assert doc["admission"]["priced"]["warm_in_process"] is True, doc
hot = srv.session("hot_add")
hot.wait(180)
assert hot.state == "done"
assert hot.scope.recompiles() == 0, hot.scope.recompiles()

# the admission door: tenant 4 of max_tenants=3 -> 409 with the priced
# reason, visible afterwards on /status and in fedml_admission_total
code, doc = req("/tenants", "POST", spec("too_many", 2, 1), token=TOKEN)
assert code == 409 and "max_tenants=3" in doc["error"], doc
code, st = req("/status")
assert code == 200 and st["admission"]["refused"] >= 1, st
ref_d = [d for d in st["admission"]["decisions"] if d["tenant"] == "too_many"]
assert ref_d and ref_d[-1]["decision"] == "refuse", st["admission"]
assert "max_tenants=3" in ref_d[-1]["reason"]
assert st["placement"][slices[0].label]["tenants"] == [
    "hot_add", "resident_long"], st["placement"]
# the status CLI's table reflects placement + the decision log
table = render_status(st)
assert "placement:" in table and "admission:" in table, table
assert slices[0].label in table and "refuse" in table, table

# DRAIN the long resident over HTTP mid-flight: open round completes
drained_at = long_t.server.round_idx
code, doc = req("/tenants/resident_long/drain", "POST", b"", token=TOKEN)
assert code == 202, doc
_until(lambda: heal_t.restarts >= 1, "resident_heal's supervised restart")
results = srv.wait(timeout=300)
end_rss = rss_mb()
final = srv.render_metrics()
srv.close()

assert all(r["ok"] for r in results.values()), results
assert killed["done"] and heal_t.restarts == 1
assert results["resident_heal"]["summary"]["supervisor/restarts"] == 1
assert results["resident_heal"]["summary"]["round"] == 120  # healed to target
assert results["resident_long"]["summary"]["round"] >= drained_at
assert 'fedml_admission_total{decision="refuse"} 1.0' in final
assert 'fedml_admission_total{decision="admit"} 3.0' in final
growth = end_rss - warm_rss
assert growth < 64.0, f"RSS grew {growth:.1f} MB ({warm_rss:.0f} -> {end_rss:.0f})"
import shutil
shutil.rmtree(heal_dir, ignore_errors=True)
print(f"  control plane ok: slices {slices[0].label}/{slices[1].label}, "
      f"hot_add admitted warm (0 recompiles) + finished, too_many refused "
      f"({ref_d[-1]['reason']!r}), resident_long drained at round "
      f"{drained_at}, resident_heal self-healed on its slice, RSS "
      f"{warm_rss:.0f} -> {end_rss:.0f} MB")
PY

echo "== wire-fleet observability smoke: 8-client gRPC fleet, beacons + trace merge (docs/OBSERVABILITY.md) =="
# Federation-wide wire telemetry, end to end on a REAL multi-process
# fleet with transport chaos: (1) the merged cross-process trace is
# valid — every client's local_train span nests under the server's
# same-round span after clock alignment; (2) /fleet serves live
# per-tier percentiles mid-run; (3) beacon overhead stays <= 1% of the
# metered uplink payload; (4) numerics are byte-identical to a
# beacons-off reference run (observability is free of the math).
WFDIR=$(mktemp -d)
WF_PLAN='{"seed": 5, "num_clients": 8, "profiles": {"tier_a": {"slowdown_s": 0.01}, "tier_b": {"slowdown_s": 0.03}}, "fleet": {"tier_a": 0.5, "tier_b": 0.5}}'
WF_PROM=19464
wf_common=(--algorithm fedavg --runtime grpc --model lr --dataset synthetic
  --client_num_in_total 8 --client_num_per_round 8 --comm_round 2
  --batch_size 16 --epochs 1 --lr 0.1 --seed 3
  --frequency_of_the_test 10000
  --fault_plan "$WF_PLAN"
  --send_retries 6 --send_fault_p 0.25 --send_backoff_s 0.002)

run_wf_fleet() {  # $1 = out dir, $2 = base port, $3 = server prom port
  # (0 = none); remaining flags go to EVERY rank (clients attach the
  # beacons, so --no_beacons must reach them) — only the server gets
  # --prom_port + --checkpoint_path via cli_rank0_args. The 9 ranks run
  # through the SAME fleet launcher (mode="cli") that drives the
  # 1000-process gate below — one code path for 8 and 1000
  # (fedml_tpu/fleet/, docs/FLEET.md); "{rank}" in cli_args expands to
  # each process's rank so every rank keeps its own --log_dir.
  local dir=$1 port=$2 prom=$3; shift 3
  local rank0=(--checkpoint_path "$dir/ck")
  if [ "$prom" != 0 ]; then rank0+=(--prom_port "$prom"); fi
  python - "$dir" "$port" "${#rank0[@]}" "${rank0[@]}" \
      "${wf_common[@]}" "$@" <<'PY'
import json, os, sys
out, port, n0 = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
rank0, common = sys.argv[4:4 + n0], sys.argv[4 + n0:]
os.makedirs(out, exist_ok=True)
json.dump({
    "population": 8,
    "mode": "cli",
    "base_port": port,
    "run_deadline_s": 420.0,
    "cli_args": common + [
        "--base_port", str(port),
        "--telemetry_dir", f"{out}/telemetry",
        "--log_dir", f"{out}/rank{{rank}}",
    ],
    "cli_rank0_args": rank0,
}, open(f"{out}/fleet_spec.json", "w"))
PY
  python -m fedml_tpu fleet --spec "$dir/fleet_spec.json" \
    --out_dir "$dir/fleet" > /dev/null
}

# capture /fleet DURING the run — the exporter dies with the server, so
# a live per-tier snapshot is proof the route served mid-federation
python - "$WFDIR" "$WF_PROM" <<'PY' &
import json, sys, time, urllib.request
out, port = sys.argv[1], int(sys.argv[2])
deadline = time.time() + 240
while time.time() < deadline:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet", timeout=2
        ) as r:
            doc = json.loads(r.read().decode())
        live = {
            t: m for t, m in doc.get("tiers", {}).items()
            if m.get("metrics", {}).get("train_s", {}).get("count", 0) > 0
        }
        if doc.get("beacons", 0) >= 2 and len(live) >= 2:
            json.dump(doc, open(f"{out}/fleet.json", "w"))
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.1)
sys.exit(1)
PY
WF_POLL=$!
run_wf_fleet "$WFDIR/on" 19500 "$WF_PROM"
wait "$WF_POLL"  # red unless a live 2-tier /fleet snapshot was captured
run_wf_fleet "$WFDIR/off" 19520 0 --no_beacons

python -m fedml_tpu trace merge "$WFDIR/on/telemetry" \
  -o "$WFDIR/federation_trace.json" --check > "$WFDIR/merge_report.json"

python - "$WFDIR" <<'PY'
import glob, json, sys
import numpy as np
d = sys.argv[1]
# (1) merged-trace validity: 9 ranks, zero nesting violations
report = json.load(open(f"{d}/merge_report.json"))
assert report["violations"] == [], report["violations"]
assert len(report["ranks"]) == 9, report["ranks"]
# (2) live /fleet: both DeviceProfile tiers served non-empty percentiles
fleet = json.load(open(f"{d}/fleet.json"))
for tier in ("tier_a", "tier_b"):
    m = fleet["tiers"][tier]["metrics"]["train_s"]
    assert m["count"] > 0 and m["p50"] > 0, (tier, m)
# (3) beacon overhead <= 1% of the metered uplink payload (client ranks)
up = bc = 0
for p in glob.glob(f"{d}/on/rank*/summary.json"):
    s = json.load(open(p))
    up += s.get("comm/uplink_bytes", 0)
    bc += s.get("comm/beacon_bytes", 0)
assert up > 0 and bc > 0, (up, bc)
frac = bc / up
assert frac <= 0.01, f"beacon overhead {frac:.4%} > 1%"
off_bc = sum(
    json.load(open(p)).get("comm/beacon_bytes", 0)
    for p in glob.glob(f"{d}/off/rank*/summary.json")
)
assert off_bc == 0, off_bc
# (4) numerics byte-identical beacons on vs off (npz zip timestamps
# differ run to run, so compare the LOADED arrays, not the files)
with np.load(f"{d}/on/ck.npz") as a, np.load(f"{d}/off/ck.npz") as b:
    keys = sorted(k for k in a.files if k != "__meta__")
    assert keys == sorted(k for k in b.files if k != "__meta__")
    for k in keys:
        assert a[k].tobytes() == b[k].tobytes(), f"numerics differ at {k}"
print(f"  wire-fleet ok: {report['events']} merged events over "
      f"{len(report['ranks'])} ranks, clock offsets "
      f"{report['clock_offsets_us']}, fleet beacons {fleet['beacons']} "
      f"across {len(fleet['tiers'])} tiers, beacon overhead {frac:.4%}, "
      f"{len(keys)} checkpoint arrays byte-identical beacons on/off")
PY
rm -rf "$WFDIR"

echo "== wire-fleet scale gate: ${FLEET_N:-1000}-process churn fleet against one tenant (docs/FLEET.md) =="
# The fleet gate (ISSUE 18): ≥1000 OS-process gRPC clients churn through
# one server-only tenant to completion — seed-deterministic join/leave
# waves through the admission door, transport chaos on every send, door
# refusals under wave pressure priced LIVE on /status, the server
# executor's thread count ASSERTED against its configured bound, zero
# stuck ranks. Demand (rounds × buffer_k = 98% of the population's
# one-assignment supply) is sized so every rank must cycle through the
# tenant: spawned >= FLEET_N is part of the gate. Door pressure is
# STRUCTURAL, not a race: an 8 s device-profile slowdown makes every
# admitted member hold its seat for seconds while max_live keeps spare
# clients spawned and knocking, so max_workers (< the live wave) must
# refuse continuously; refused ranks requeue at the launcher and land
# later — the door sheds load without shrinking the population's
# assignment supply.
FGDIR=$(mktemp -d)
FLEET_N=${FLEET_N:-1000}
FG_PROM=19468
python - "$FGDIR" "$FLEET_N" <<'PY'
import json, sys
out, n = sys.argv[1], int(sys.argv[2])
json.dump({
    "population": n,
    "max_live": 64,
    # seats < the live wave at any scale (56 at n=1000, n//4 small-n)
    "max_workers": min(56, max(2, n // 4)),
    "rounds": max(2, (n * 98) // (100 * 4)),
    "async_buffer_k": 4,
    "assignments": [1, 1],       # every rank: one assignment, then leave
    # custom lingering tier: the 8 s slowdown is what keeps seats
    # occupied long enough that the door MUST refuse the spare wave;
    # dropout stays 0 so the supply==population math is exact
    "fault_plan": json.dumps({
        "seed": 0,
        "profiles": {"edge_slow": {"slowdown_s": 8.0}},
        "fleet": {"edge_slow": 1.0},
        "num_clients": n,
    }, sort_keys=True),
    "send_fault_p": 0.02,
    "send_retries": 6,
    "seed": 0,
    "base_port": 21000,
    "grpc_max_workers": 16,
    "orphan_deadline_s": 120.0,
    "client_deadline_s": 300.0,
    "run_deadline_s": 780.0,
}, open(f"{out}/spec.json", "w"), indent=2)
PY
# capture /status DURING the run — refusal pricing must be live ops
# surface, not a post-mortem file
python - "$FGDIR" "$FG_PROM" <<'PY' &
import json, sys, time, urllib.request
out, port = sys.argv[1], int(sys.argv[2])
deadline = time.time() + 700
while time.time() < deadline:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=2
        ) as r:
            doc = json.loads(r.read().decode())
        brief = doc.get("tenants", {}).get("fleet", {})
        if brief.get("joins_refused", 0) >= 1:
            json.dump(doc, open(f"{out}/status.json", "w"))
            sys.exit(0)
    except Exception:
        pass
    time.sleep(0.5)
sys.exit(1)
PY
FG_POLL=$!
python -m fedml_tpu fleet --spec "$FGDIR/spec.json" --out_dir "$FGDIR/run" \
  --prom_port "$FG_PROM" --json > "$FGDIR/stats.json"
wait "$FG_POLL"  # red unless /status priced >=1 door refusal mid-run
python - "$FGDIR" "$FLEET_N" <<'PY'
import json, sys
d, n = sys.argv[1], int(sys.argv[2])
s = json.load(open(f"{d}/stats.json"))
assert s["ok"], s
assert s["spawned"] >= n, (s["spawned"], n)
assert s["stuck"] == 0 and s["errors"] == 0 and s["orphaned"] == 0, s
# thread bound: asserted, not eyeballed — the launcher sampled the live
# grpc-comm executor threads for the whole run
assert s["thread_bound_ok"], s
assert s["grpc_threads_max"] <= s["grpc_executor_workers"] == 16, s
assert s["joins_refused"] >= 1, s
st = json.load(open(f"{d}/status.json"))["tenants"]["fleet"]
assert st["joins_refused"] >= 1, st
assert "comm/refused" in st and "comm/send_refused" in st, st
print(f"  fleet gate ok: {s['spawned']} processes over max_live "
      f"{s['max_live']}, {s['server_steps']} server steps, "
      f"{s['joins_accepted']} joins (+{s['joins_refused']} refused, "
      f"priced live on /status), {s['leaves']} leaves, "
      f"{s['fault_events']} fault events, threads "
      f"{s['grpc_threads_max']}<={s['grpc_executor_workers']}, "
      f"{s['joined_per_s']}/s over {s['elapsed_s']}s")
PY

# determinism leg: a recorded fleet FaultTrace replays byte-identically
# through the SAME launcher (sync transport: the deterministic cohort —
# fedbuff round assignment is timing-dependent by design, so the replay
# guarantee lives where rounds are, docs/FLEET.md)
python - "$FGDIR" <<'PY'
import json, sys
out = sys.argv[1]
base = {
    "population": 8, "algorithm": "fedavg", "rounds": 2, "seed": 5,
    "fault_plan": json.dumps({
        "seed": 5, "default": {"slowdown_s": 0.05, "flaky_upload_p": 0.7},
    }, sort_keys=True),
    "run_deadline_s": 240.0,
}
json.dump({**base, "base_port": 21200}, open(f"{out}/rec.json", "w"))
json.dump({**base, "base_port": 21220}, open(f"{out}/rep.json", "w"))
PY
python -m fedml_tpu fleet --spec "$FGDIR/rec.json" --out_dir "$FGDIR/rec" > /dev/null
python - "$FGDIR" <<'PY'
import json, sys
out = sys.argv[1]
doc = json.load(open(f"{out}/rep.json"))
doc["fault_plan"] = f"trace:{out}/rec/fault_trace.json"
json.dump(doc, open(f"{out}/rep.json", "w"))
PY
python -m fedml_tpu fleet --spec "$FGDIR/rep.json" --out_dir "$FGDIR/rep" > /dev/null
cmp "$FGDIR/rec/fault_trace.json" "$FGDIR/rep/fault_trace.json" \
  || { echo "FAULT TRACE REPLAY DIVERGED"; exit 1; }
echo "  fault-trace replay byte-identical ($(wc -c < "$FGDIR/rec/fault_trace.json") bytes)"
rm -rf "$FGDIR"

echo "== splitfed gate: split tenant co-resident with a horizontal tenant, mid-flight kill + self-heal, metered activation cut (docs/SPLITFED.md) =="
# ROADMAP item-5 gate. One process, one device, two tenant families:
# "horiz" (fedavg) and "split_a" (SplitNN relay ring over the boundary
# transport) run concurrently under ONE recompile budget. split_a is
# SUPERVISED and killed mid-flight (round 2) — the supervisor restores
# it from its rolling checkpoint and the final model must be
# bit-identical to an uninterrupted reference run, int8 activation
# compression and all. (Stateless int8 on purpose: error-feedback
# residuals are in-memory per-stream state, not checkpointed — a
# restart would replay rounds against zeroed accumulators. The
# error-feedback accuracy contract is pinned in tests/test_splitfed.py
# instead.) The activation-wire cut factor
# is READ OFF the tenant's summary comm accounting (on_uplink /
# on_downlink at codec time), never asserted from codec math. The split
# family is pre-warmed by the reference run, so the co-resident split
# tenant must trigger ZERO XLA compiles of its own (the soak stage's
# cross-tenant sharing gate, now for boundary programs).
timeout 600 python - <<'PY'
import json

import jax
import numpy as np

from fedml_tpu.analysis.sentinel import (
    RecompileSentinel,
    ensure_backend_listener,
)
from fedml_tpu.config import (
    CommConfig,
    DataConfig,
    FedConfig,
    RunConfig,
    TrainConfig,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.serve import FederationServer, RestartPolicy, FedSession

def cfg(rounds, workers, total, seed, comm=None, feat=(10,)):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(client_num_in_total=total, client_num_per_round=workers,
                      comm_round=rounds, epochs=1,
                      frequency_of_the_test=10**6),
        train=TrainConfig(client_optimizer="sgd", lr=0.1, momentum=0.9,
                          wd=5e-4),
        comm=comm if comm is not None else CommConfig(),
        seed=seed,
    )

wire = CommConfig(activation_compression="int8")
split_data = synthetic_classification(
    num_clients=8, num_classes=3, feat_shape=(10,), samples_per_client=24,
    partition_method="homo", seed=0)
horiz_data = synthetic_classification(
    num_clients=8, num_classes=4, feat_shape=(16,), samples_per_client=24,
    partition_method="homo", seed=1)
horiz_model = create_model("lr", "synthetic", (16,), 4)

ensure_backend_listener()
# uninterrupted split reference, --warmup AOT path included: every
# boundary/fused program is compiled HERE, before the service starts
ref = FedSession(cfg(6, 4, 8, 11, comm=wire), split_data, None,
                 algorithm="split_nn", warmup=True).run()
assert ref.round_idx == 6, ref.round_idx

killed = {"done": False}
def chaos_kill(row):
    if row.get("round") == 2 and "t_s" in row and not killed["done"]:
        killed["done"] = True
        raise RuntimeError("splitfed chaos kill")

import tempfile
ck_dir = tempfile.mkdtemp(prefix="fedml_splitfed_ci_")
with RecompileSentinel(budget=24, label="splitfed-service") as sent:
    srv = FederationServer()
    horiz = srv.create_session("horiz", cfg(40, 2, 8, 3), horiz_data,
                               horiz_model, algorithm="fedavg")
    split = srv.create_session(
        "split_a", cfg(6, 4, 8, 11, comm=wire), split_data, None,
        algorithm="split_nn",
        restart=RestartPolicy(budget=2, backoff_base_s=0.05),
        checkpoint_path=f"{ck_dir}/ck", checkpoint_every=1,
        log_fn=chaos_kill)
    srv.start()
    results = srv.wait(timeout=420)
    srv.close()
sent.check()  # the whole co-resident service fit the recompile budget

assert all(r["ok"] for r in results.values()), results
# mid-flight kill + self-heal with bit parity to never having died
assert killed["done"], "the chaos kill never fired"
assert split.restarts == 1, split.restarts
assert results["split_a"]["summary"]["supervisor/restarts"] == 1
for la, lb in zip(jax.tree_util.tree_leaves(ref.global_vars),
                  jax.tree_util.tree_leaves(split.global_vars)):
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
assert len(horiz.history) == 40, len(horiz.history)

# cut factor off the summary row (the serve analog of summary.json):
# int8 on float32 activations must show >= 3x in BOTH directions
summary = json.loads(json.dumps(results["split_a"]["summary"]))
up = summary["comm/uplink_raw_bytes"] / summary["comm/uplink_payload_bytes"]
down = (summary["comm/downlink_raw_bytes"]
        / summary["comm/downlink_payload_bytes"])
assert summary["comm/uplink_updates"] > 0, summary
assert up >= 3.0, f"uplink cut {up:.2f}x < 3x"
assert down >= 3.0, f"downlink cut {down:.2f}x < 3x"

# co-residency program sharing: the split family was warmed by the
# reference run, so the split tenant itself compiled NOTHING — even
# across its supervised restart
assert split.scope.recompiles() == 0, sent.describe()

import shutil
shutil.rmtree(ck_dir, ignore_errors=True)
print(f"  splitfed ok: split tenant healed bit-identical after 1 kill "
      f"co-resident with {len(horiz.history)} fedavg rounds, activation "
      f"cut {up:.1f}x up / {down:.1f}x down off the comm accounting, "
      f"split-tenant recompiles == 0 "
      f"(service paid {sent.recompiles()} within budget 24)")
PY

echo "== multichip dryrun (DP/SP/TP/EP/PP) =="
python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

echo "CI GREEN"
