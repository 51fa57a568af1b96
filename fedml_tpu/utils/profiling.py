"""Profiling subsystem — MFU, device-time slopes, and jax.profiler
traces.

SURVEY §5 assigns this slot jax.profiler + per-round host metrics; the
reference has only ad-hoc timers (`time.perf_counter` around aggregation,
FedAVGAggregator.py:4,78; JSON-size log per message, message.py:77-78; the
TRPC latency sweep, trpc_comm_manager.py:146-211). Here MFU = achieved/peak
over the published per-chip peaks, and a trace directory flag captures a
full device timeline viewable in TensorBoard/Perfetto.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Public per-chip peak dense-matmul throughput (FLOP/s). Keyed by substring
# of jax.Device.device_kind. bf16 is the MXU-native dtype; fp32 on TPU runs
# through the MXU at reduced rate (~1/8 via passes) — we track the bf16 and
# fp32 peaks separately so MFU is honest for both policies.
_PEAKS = {
    "v2": {"bfloat16": 45e12, "float32": 11e12},
    "v3": {"bfloat16": 123e12, "float32": 30e12},
    "v4": {"bfloat16": 275e12, "float32": 34e12},
    "v5 lite": {"bfloat16": 197e12, "float32": 25e12},
    "v5e": {"bfloat16": 197e12, "float32": 25e12},
    "v5p": {"bfloat16": 459e12, "float32": 57e12},
    "v6 lite": {"bfloat16": 918e12, "float32": 115e12},
    "v6e": {"bfloat16": 918e12, "float32": 115e12},
}


def device_peak_flops(dtype: str = "bfloat16", device=None) -> Optional[float]:
    """Per-chip peak FLOP/s of ``device`` (default: the first device).

    None off the TPU — a CPU run has no device peak, so its MFU is "not
    measured". On platform ``tpu`` a ``device_kind`` (or dtype) missing
    from the table is an error, not a default: add the chip's published
    peak to ``_PEAKS`` rather than reporting a utilization against a
    guess."""
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for key, peaks in _PEAKS.items():
        if key in kind:
            return peaks[dtype]
    raise ValueError(
        f"no published peak FLOP/s for TPU device_kind "
        f"{device.device_kind!r} in fedml_tpu.utils.profiling._PEAKS"
    )


def mfu(
    flops_per_call: Optional[float],
    calls_per_sec: float,
    dtype: str = "bfloat16",
    n_devices: int = 1,
) -> Optional[float]:
    """Model FLOPs Utilization: achieved FLOP/s over aggregate peak."""
    peak = device_peak_flops(dtype)
    if not flops_per_call or not peak:
        return None
    return (flops_per_call * calls_per_sec) / (peak * n_devices)


def scan_slope_seconds(step_fn, init_carry, k1: int = 1, k2: int = 5, reps: int = 5):
    """Device seconds for ONE ``step_fn(carry) -> carry`` call, with the
    per-program costs cancelled: jit a program that runs the step K times
    inside a lax.scan, wall-time it at K=k1 and K=k2, and take the slope
    (t2 - t1)/(k2 - k1). Per-program costs — dispatch latency, argument
    upload, the device->host fetch of the result — appear once per
    program and cancel in the slope, so the result is device execution
    time.

    Noise discipline: a shared chip can show bimodal throughput windows
    (~2× swings lasting seconds), so each rep measures its (k1, k2) PAIR
    back-to-back and contributes one slope; the result is the MEDIAN
    positive per-pair slope. Pooling best-of times across reps can pair a
    fast-mode t(k1) with a slow-mode t(k2) and report a 2×-off slope;
    taking the min positive slope instead selects exactly the pairs where
    the mode flipped mid-pair (slow t(k1), fast t(k2) → spuriously tiny
    slope). The median discards both tails."""

    def rep(c, k_arr):
        def body(c, _):
            return step_fn(c), jnp.float32(0)

        c, _ = jax.lax.scan(body, c, k_arr)
        return c

    jrep = jax.jit(rep)

    def fetch(c):
        np.asarray(jax.tree_util.tree_leaves(c)[0])

    def timed(k):
        t0 = time.perf_counter()
        fetch(jrep(init_carry, jnp.arange(k)))
        return time.perf_counter() - t0

    for k in (k1, k2):  # compile both shapes outside the timing
        fetch(jrep(init_carry, jnp.arange(k)))
    slopes = []
    for _ in range(reps + 3):  # a few retries when pairs straddle a switch
        slope = (timed(k2) - timed(k1)) / (k2 - k1)
        if slope > 0:
            slopes.append(slope)
        if len(slopes) >= reps:
            break
    if not slopes:
        # pathological: no pair produced a positive slope. Fall back to
        # whole-program time at k2 — an OVERestimate (includes the
        # per-program dispatch/fetch overhead the slope would cancel) but
        # always positive, never a negative-MFU artifact.
        return timed(k2) / k2
    slopes.sort()
    return slopes[len(slopes) // 2]


def span_annotation(name: str, round_idx=None):
    """The tracer's ``annotate`` hook (``telemetry/spans.Tracer.annotate``):
    a host span mirrored into the jax profiler's trace as ``fedml.<name>``,
    carrying the round it works for. While no profile runs an annotation is
    a no-op costing well under a microsecond, so nothing switches it; a
    profile taken with ``--profile_dir`` holds the program's spans on its
    host plane beside the device ops, on the profiler's one clock."""
    if round_idx is None:
        return jax.profiler.TraceAnnotation("fedml." + name)
    return jax.profiler.TraceAnnotation("fedml." + name, round=round_idx)


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Capture a jax.profiler device trace into ``log_dir`` (TensorBoard /
    Perfetto format). No-op when log_dir is falsy, so call sites can pass
    the CLI flag straight through."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield
