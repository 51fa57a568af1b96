"""Analytic FLOPs counter (utils/flops.py) vs hand-computed counts, and the
scan-slope device timer (utils/profiling.py). These utilities back every MFU
number the benchmark publishes (XLA's cost model undercounted
8-24x and silently deflated all round-2 MFU claims), so they get oracle
tests of their own."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.utils import profiling
from fedml_tpu.utils.flops import fn_flops


def test_dense_matmul_count():
    a = jnp.zeros((32, 64))
    b = jnp.zeros((64, 128))
    assert fn_flops(jnp.dot, a, b) == 2 * 32 * 64 * 128


def test_batched_dot_general_count():
    a = jnp.zeros((4, 8, 16))
    b = jnp.zeros((4, 16, 32))
    got = fn_flops(lambda x, y: jnp.einsum("bij,bjk->bik", x, y), a, b)
    assert got == 2 * 4 * 8 * 16 * 32


def test_conv_count_nhwc():
    # SAME-padded 3x3 conv: out spatial = in spatial
    x = jnp.zeros((2, 8, 8, 3))
    w = jnp.zeros((3, 3, 3, 16))

    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )

    assert fn_flops(conv, x, w) == 2 * 2 * 8 * 8 * 16 * 3 * 3 * 3


def test_grad_includes_backward():
    """The jaxpr of the gradient carries the real backward primitives —
    for y = sum(x @ w), fwd is one matmul and bwd adds the dW matmul (dx
    is not needed: x is not differentiated)."""
    x = jnp.zeros((16, 32))
    w = jnp.zeros((32, 8))

    def loss(w):
        return jnp.sum(x @ w)

    fwd = 2 * 16 * 32 * 8
    got = fn_flops(jax.grad(loss), w)
    # grad-of-matmul w.r.t. w: x^T @ dy — same shape product as fwd
    assert got == 2 * fwd or got == fwd  # value_and_grad may share the fwd


def test_scan_multiplies_by_length():
    a = jnp.zeros((8, 8))

    def f(a):
        def body(c, _):
            return c @ a, None

        out, _ = jax.lax.scan(body, a, None, length=10)
        return out

    assert fn_flops(f, a) == 10 * 2 * 8 * 8 * 8


def test_while_counts_once_and_warns():
    def f(x):
        def cond(c):
            return c[0, 0] < 100.0

        def body(c):
            return c @ c

        return jax.lax.while_loop(cond, body, x)

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = fn_flops(f, jnp.zeros((8, 8)))
    assert got == 2 * 8 * 8 * 8
    assert any("ONE iteration" in str(x.message) for x in w)


def test_cond_takes_max_branch():
    a = jnp.zeros((8, 8))
    b = jnp.zeros((8, 128))

    def f(pred, a, b):
        return jax.lax.cond(
            pred,
            lambda: (a @ a)[0, 0],
            lambda: (b @ b.T)[0, 0],
        )

    got = fn_flops(f, True, a, b)
    assert got == 2 * 8 * 128 * 8  # the bigger branch


def test_vmap_batches_count():
    a = jnp.zeros((5, 8, 16))
    b = jnp.zeros((16, 4))
    got = fn_flops(jax.vmap(lambda x: x @ b), a)
    assert got == 2 * 5 * 8 * 16 * 4


def test_jitted_fn_is_descended_into():
    a = jnp.zeros((8, 8))
    assert fn_flops(jax.jit(lambda x: x @ x), a) == 2 * 8 * 8 * 8


def test_scan_slope_seconds_runs_and_is_positive():
    w = jnp.eye(64)

    def step(c):
        return jnp.tanh(c @ w)

    sec = profiling.scan_slope_seconds(step, jnp.ones((64, 64)), k1=1, k2=8)
    # slope of a tiny op can jitter near zero on a fast backend, but must
    # be finite and not absurd
    assert np.isfinite(sec)
    assert sec < 1.0


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize(
    "platform,kind,dtype,expect",
    [
        ("tpu", "TPU v5 lite", "bfloat16", 197e12),  # what a v5e reports
        ("tpu", "TPU v5 lite", "float32", 25e12),
        ("cpu", "cpu", "bfloat16", None),  # no device peak off the TPU
        ("tpu", "TPU v9 mega", "bfloat16", ValueError),  # unknown = error
    ],
)
def test_device_peak_flops_table(monkeypatch, platform, kind, dtype, expect):
    """Peaks are keyed by device_kind; a TPU missing from the table is an
    error (never None, never an environment override), and a CPU has no
    device peak so its MFU stays unmeasured."""
    monkeypatch.setenv("FEDML_TPU_PEAK_FLOPS", "1e15")  # retired: ignored
    dev = _FakeDevice(platform, kind)
    if expect is ValueError:
        with pytest.raises(ValueError, match="v9 mega"):
            profiling.device_peak_flops(dtype, device=dev)
    else:
        assert profiling.device_peak_flops(dtype, device=dev) == expect
