"""Shared-memory local transport — the TRPC-equivalent backend.

The reference's fourth wire, Torch-RPC/TensorPipe
(fedml_core/distributed/communication/trpc/trpc_comm_manager.py:25,
``_init_torch_rpc_tp``:85-106, send via ``rpc.rpc_sync``:114 into a singleton
servicer, trpc_server.py:8-41), exists for one reason: a zero-copy tensor
path between processes that share a host — no JSON, no sockets for the bulk
bytes. The TPU-native analog keeps that reason and drops the RPC framework:

- **bulk path**: the sender assembles the binary wire image (core/message.py)
  directly into a POSIX ``SharedMemory`` segment — one copy total; the
  receiver maps the segment and decodes with ``copy=False``, so tensors alias
  the shared pages — zero receive-side copies.
- **control path**: a tiny pickled ``{"shm": name, "nbytes": n}`` record over
  a per-rank ``multiprocessing.connection`` UNIX socket (the moral
  equivalent of TRPC's ``worker{rank}`` naming scheme,
  trpc_comm_manager.py:85-106).

Same Observer contract as every other backend, so it slots into
``run_federation`` unchanged. Inline latency benchmark parity
(trpc_comm_manager.py:146-211) lives in tests/test_shm_comm.py.

Lifetime contract for ``zero_copy=True``: decoded arrays are valid only
inside the observer callback (the segment is unlinked when it returns) —
copy anything you retain. The default (``zero_copy=False``) copies on decode
and has no such footgun."""

from __future__ import annotations

import os
import sys
import threading
import traceback
from multiprocessing import connection, shared_memory
from typing import Optional

from fedml_tpu.core.comm import BaseCommManager
from fedml_tpu.core.message import Message, write_wire_parts

_FAMILY = "AF_UNIX"
# Bytes of a socket path that ``sockaddr_un.sun_path`` holds before its NUL
# (108 on Linux, 104 on the BSDs and macOS).
_SUN_PATH_MAX = 107 if sys.platform.startswith("linux") else 103


def _addr(sock_dir: str, rank: int, namespace: str = "") -> str:
    ns = f"{namespace}_" if namespace else ""
    return os.path.join(sock_dir, f"fedml_shm_{ns}{rank}.sock")


class ShmCommManager(BaseCommManager):
    """One per participant; ``rank`` names this endpoint (server = 0,
    ref FedAvgAPI.py:14-27 process model).

    ``namespace`` prefixes every socket name so two concurrent
    federations sharing one ``sock_dir`` (co-tenant sessions in one
    service process, fedml_tpu/serve/) cannot collide: without it the
    second session's rank-N constructor unlinks-and-rebinds the first
    session's live rank-N socket and the two fleets cross-deliver. All
    participants of one federation must use the SAME namespace (the
    session's comm factory owns it). "" keeps the legacy socket names
    byte-identical."""

    def __init__(
        self,
        rank: int,
        sock_dir: str,
        zero_copy: bool = False,
        namespace: str = "",
    ):
        super().__init__()
        self.rank = int(rank)
        self.sock_dir = sock_dir
        self.zero_copy = zero_copy
        self.namespace = str(namespace)
        addr = _addr(sock_dir, self.rank, self.namespace)
        n = len(os.fsencode(addr))
        if n > _SUN_PATH_MAX:
            # bind() would say only "AF_UNIX path too long"
            raise ValueError(
                f"shm socket path {addr!r} is {n} bytes, over the "
                f"{_SUN_PATH_MAX} that a UNIX socket address holds: use a "
                "shorter sock_dir or namespace"
            )
        if os.path.exists(addr):  # stale socket from a crashed run
            os.unlink(addr)
        # backlog: the default (1) makes a K-client broadcast race the
        # receive loop's accept — a sender connecting while the listener is
        # busy decoding gets BlockingIOError(EAGAIN) and takes the whole
        # federation down; size it to a realistic worker fan-in instead
        self._listener = connection.Listener(addr, family=_FAMILY, backlog=64)
        self._stopped = threading.Event()
        self._loop_running = False

    # -- send: one copy (wire image → shared pages) --
    def _send(self, msg: Message) -> None:
        # serialize exactly once: size and write come from the same parts
        header, buffers = msg.to_wire_parts()
        size = len(header) + sum(int(b.nbytes) for b in buffers)
        seg = shared_memory.SharedMemory(create=True, size=max(size, 1))
        try:
            written = write_wire_parts(seg.buf, header, buffers)
            with connection.Client(
                _addr(self.sock_dir, msg.get_receiver_id(), self.namespace),
                family=_FAMILY,
            ) as conn:
                conn.send({"shm": seg.name, "nbytes": written})
        except BaseException:
            seg.unlink()  # nobody will ever map it
            raise
        finally:
            seg.close()  # receiver owns the segment now

    # -- receive loop: map, decode (optionally aliasing), notify, unlink --
    def handle_receive_message(self) -> None:
        self._loop_running = True
        self._loop_thread = threading.current_thread()
        try:
            while not self._stopped.is_set():
                try:
                    with self._listener.accept() as conn:
                        rec = conn.recv()
                except (OSError, EOFError):
                    if self._stopped.is_set():
                        break  # stop() closed the listener under accept()
                    raise
                if rec.get("stop"):
                    break
                self._consume(rec, notify=True)
        finally:
            self._loop_running = False
            self._drain_and_close()

    def _consume(self, rec: dict, notify: bool) -> None:
        seg = shared_memory.SharedMemory(name=rec["shm"])
        msg = view = None
        try:
            try:
                if notify:
                    view = seg.buf[: rec["nbytes"]]
                    msg = Message.from_bytes(view, copy=not self.zero_copy)
                    self.notify(msg)
            except BaseException as e:
                # the in-flight traceback's frames (notify → observer) hold
                # ``msg`` and would keep the mapping exported, turning the
                # handler's exception into a masking BufferError at close();
                # clear frame locals, keep file/line info
                traceback.clear_frames(e.__traceback__)
                raise
            finally:
                del msg, view  # release buffer refs before close()
        finally:
            seg.close()
            try:
                seg.unlink()
            except FileNotFoundError:
                pass

    def _drain_and_close(self) -> None:
        """Unlink segments from sends that landed in the stop window, then
        close the listener (receive-loop thread owns this teardown)."""
        sock = getattr(getattr(self._listener, "_listener", None), "_socket", None)
        if sock is not None:
            try:
                sock.settimeout(0.05)
                while True:
                    with self._listener.accept() as conn:
                        rec = conn.recv()
                    if not rec.get("stop"):
                        self._consume(rec, notify=False)
            except (OSError, EOFError):
                pass
        try:
            self._listener.close()
        except OSError:
            pass
        addr = _addr(self.sock_dir, self.rank, self.namespace)
        try:
            os.unlink(addr)
        except OSError:
            pass

    def stop_receive_message(self) -> None:
        already = self._stopped.is_set()
        self._stopped.set()
        if (
            self._loop_running
            and threading.current_thread() is getattr(self, "_loop_thread", None)
        ):
            # Reentrant stop — called from inside a handler, i.e. ON the
            # receive-loop thread (an async server finishing from its own
            # upload handler, fedbuff._flush). The flag alone suffices: the
            # loop re-checks _stopped before its next accept(), and the
            # loop's finally owns teardown. The self-connect wake below
            # would DEADLOCK here: with peers still connecting, the
            # backlog-1 listener is full and the only accept()-er is this
            # very thread.
            return
        if not self._loop_running:
            # no receive loop to drain (never started, or already exited):
            # tear down here instead of queueing a stop record nobody reads
            if not already:
                self._drain_and_close()
            return
        try:
            with connection.Client(
                _addr(self.sock_dir, self.rank, self.namespace),
                family=_FAMILY,
            ) as conn:
                conn.send({"stop": True})
        except (ConnectionError, FileNotFoundError, OSError):
            pass  # loop exited between the check and the connect; it drains
