"""Native fastpack library: builds with g++, matches the numpy fallback
bit-for-bit, and the integrated paths (stack_clients, Message.to_bytes)
produce identical results with and without it."""

import numpy as np
import pytest

from fedml_tpu import native


def test_native_builds():
    # the image bakes g++, so the native path must actually build here
    assert native.available()


def test_gather_rows_matches_numpy():
    rng = np.random.default_rng(0)
    src = rng.normal(size=(100, 7, 3)).astype(np.float32)
    order = rng.permutation(100)[:60]
    out_native = np.zeros((60, 7, 3), np.float32)
    native.gather_rows(src, order, out_native)
    np.testing.assert_array_equal(out_native, src[order])
    # int labels too
    srci = rng.integers(0, 50, size=(33,)).astype(np.int32)
    outi = np.zeros((10,), np.int32)
    native.gather_rows(srci, np.arange(10), outi)
    np.testing.assert_array_equal(outi, srci[:10])


def test_gather_rows_noncontiguous_fallback():
    rng = np.random.default_rng(1)
    src = rng.normal(size=(20, 4)).astype(np.float32)
    out = np.zeros((40, 4), np.float32)[::2]  # non-contiguous destination
    native.gather_rows(src, np.arange(20), out)
    np.testing.assert_array_equal(out, src)


def test_concat_buffers():
    bufs = [bytes([i]) * (i * 100 + 1) for i in range(10)]
    assert native.concat_buffers(bufs, header=b"HDR") == b"HDR" + b"".join(bufs)
    assert native.concat_buffers([], header=b"X") == b"X"


def test_message_roundtrip_uses_native(monkeypatch):
    from fedml_tpu.core.message import Message

    m = Message("t", 0, 1)
    tree = {"w": np.arange(1000, dtype=np.float32)}
    m.add_params("params", tree)
    wire_native = m.to_bytes()
    # force fallback and compare byte-for-byte
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", True)
    wire_fallback = m.to_bytes()
    assert wire_native == wire_fallback
    out = Message.from_bytes(wire_native)
    np.testing.assert_array_equal(out.get("params")["w"], tree["w"])


def test_failed_build_is_logged_once_and_loudly(monkeypatch, tmp_path, caplog):
    """No toolchain (or a broken source) -> the numpy route, announced at
    ERROR with the compiler's own words, once per process — never a
    silent slowdown."""
    import logging

    bad = tmp_path / "broken.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native, "_SRC", str(bad))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "build" / "libfastpack.so"))
    with caplog.at_level(logging.ERROR):
        assert not native.available()
        assert not native.available()  # second call: no rebuild, no second log
    errors = [r for r in caplog.records if r.levelno == logging.ERROR]
    assert len(errors) == 1
    msg = errors[0].getMessage()
    assert "UNAVAILABLE" in msg and "g++ said" in msg and "numpy route" in msg
    # and the numpy route still answers
    out = np.zeros((2, 3), np.float32)
    native.gather_rows(np.ones((4, 3), np.float32), np.array([0, 3]), out)
    assert out.sum() == 6
