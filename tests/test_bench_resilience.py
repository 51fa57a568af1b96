"""The benchmark's record must survive pathology — round 4 lost its ENTIRE
perf record when the driver's timeout killed bench.py before its single
end-of-run print (rc=124, nothing parsed). The r5 design is
pinned here: a compact (<1800 char) record line is flushed to stdout after
EVERY section and the full detail file is atomically rewritten alongside,
so no kill — budget gate, SIGTERM, watchdog, or raw SIGKILL — can erase
completed sections. The driver parses the LAST LINE of a ~2000-char output
tail; these tests parse the same way."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(budget, tiny=None, sleep=None, detail=None, wd_frac=None,
         sleep_only=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the bench initialises its backend in-process
    env["FEDML_TPU_BENCH_BUDGET_S"] = str(budget)
    if tiny:
        env["FEDML_TPU_BENCH_TINY"] = "1"
    if sleep is not None:
        env["FEDML_TPU_BENCH_TINY_SLEEP"] = str(sleep)
    if detail:
        env["FEDML_TPU_BENCH_DETAIL"] = detail
    if wd_frac is not None:
        env["FEDML_TPU_BENCH_WATCHDOG_FRAC"] = str(wd_frac)
    if sleep_only:
        env["FEDML_TPU_BENCH_TINY_SLEEP_ONLY"] = "1"
    return env


def _last_record(stdout: str) -> dict:
    """Parse exactly the way the driver does: last line of the tail."""
    lines = [l for l in stdout.strip().splitlines() if l.startswith("{")]
    assert lines, stdout[-2000:]
    assert len(lines[-1]) < 1800, "compact line must fit the driver's tail"
    return json.loads(lines[-1])


@pytest.mark.slow
def test_bench_exhausted_budget_still_emits_parseable_record(tmp_path):
    """FEDML_TPU_BENCH_BUDGET_S=1: every section (including the mandatory
    throughput rows) skips via the budget gate, and the LAST stdout line
    is still a parseable compact record naming every skip."""
    detail = str(tmp_path / "detail.json")
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True, text=True, timeout=300,
        # wd_frac=200 keeps the watchdog (budget*200 = 200 s) out of this
        # test's way: the subject is the per-section budget gate
        env=_env(budget=1, detail=detail, wd_frac=200), cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = _last_record(out.stdout)
    assert rec["metric"] == "femnist_cnn_fedavg_rounds_per_sec"
    assert rec["value"] is None
    assert rec["error"] == "all throughput sections failed"
    assert rec["partial"] is False
    assert rec["expected_deviations"] == []  # skips are not deviations
    for k, v in rec["sections"].items():
        assert v.startswith("skip:"), (k, v)
    # the detail file carries the same degraded evidence, with no
    # fabricated measurement claims
    det = json.load(open(detail))
    for row in det["hard_accuracy"]["synthetic11"]:
        assert "skipped" in row


@pytest.mark.slow
def test_bench_survives_sigkill_mid_run(tmp_path):
    """THE round-4 failure mode, pinned: kill -9 the
    bench mid-flight; everything completed before the kill must already
    be on stdout (compact line) and in the detail file."""
    detail = str(tmp_path / "detail.json")
    p = subprocess.Popen(
        [sys.executable, "bench.py"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=_env(budget=3600, tiny=True, sleep=600, detail=detail), cwd=REPO,
    )
    lines = []
    try:
        deadline = time.time() + 280
        while time.time() < deadline:
            line = p.stdout.readline()
            if not line:
                break
            lines.append(line)
            rec = json.loads(line)
            if "r/s" in rec["sections"]["north_star"]:
                break  # first real section completed & flushed
        else:
            pytest.fail("north_star section never completed")
        p.kill()  # SIGKILL — no handler can run
    finally:
        if p.poll() is None:
            p.kill()
        p.wait()
    assert lines, "no incremental emission before the kill"
    rec = json.loads(lines[-1])
    assert "r/s" in rec["sections"]["north_star"]
    assert rec["value"] is not None  # headline already assembled
    det = json.load(open(detail))
    assert "rounds_per_sec" in det["north_star"]


@pytest.mark.slow
def test_bench_sigterm_finalizes_record(tmp_path):
    """The driver's `timeout` sends SIGTERM before SIGKILL — the handler
    must finalize and exit promptly with the record as the last line."""
    detail = str(tmp_path / "detail.json")
    p = subprocess.Popen(
        [sys.executable, "bench.py"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=_env(budget=3600, tiny=True, sleep=600, detail=detail), cwd=REPO,
    )
    try:
        time.sleep(12)  # backend init / early first section
        p.send_signal(signal.SIGTERM)
        out, _ = p.communicate(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    rec = _last_record(out)
    assert rec["partial"] is True
    assert "SIGTERM" in rec.get("finalize_note", "")


@pytest.mark.slow
def test_bench_watchdog_fires_before_driver_timeout(tmp_path):
    """A section that hangs past the whole budget cannot take the record
    with it: the watchdog thread finalizes at 92% of the budget and
    os._exit's — even though the main thread is still asleep."""
    detail = str(tmp_path / "detail.json")
    t0 = time.time()
    # budget 120: the section gate admits the sleeper (start_deadline =
    # 0.92*120-60 = 50s > backend init) and the watchdog fires at 110s,
    # mid-sleep — the exact hang-past-the-budget scenario
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True, text=True, timeout=280,
        env=_env(budget=120, tiny=True, sleep=600, detail=detail,
                 sleep_only=True), cwd=REPO,
    )
    # exited on its own (well before the sleeper's 600 s), record intact
    assert time.time() - t0 < 240
    rec = _last_record(out.stdout)
    assert rec["partial"] is True
    assert "watchdog" in rec.get("finalize_note", "")
