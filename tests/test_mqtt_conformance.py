"""MQTT 3.1.1 wire conformance for the from-scratch broker: the
reference's backend ran against real paho
(mqtt_comm_manager.py:14-123); paho is not installable here (no egress),
so interop is proven at the layer that matters — the WIRE:

1. committed byte-level fixtures (tests/golden/mqtt311_paho_session.json,
   the exact bytes paho-mqtt 1.6.x emits for a canonical session, each
   step citing its normative OASIS spec section) are replayed against a
   live MiniMqttBroker TCP socket and the broker's responses asserted
   byte-for-byte;
2. a FOREIGN wire client — implemented in this file purely from the spec,
   sharing zero code with core/mqtt_broker.py — completes a two-party
   federation against the broker, talking to the in-house
   MqttCommManager on the other side (binary Message envelopes through
   real TCP MQTT).

If paho ever lands in the image, point MqttCommManager at the broker
host/port and it takes the real-paho path automatically
(core/mqtt_comm.py:88-118); these fixtures stay as the regression floor.
"""

import json
import os
import socket
import struct
import threading
import time

import numpy as np
import pytest

from fedml_tpu.core.mqtt_broker import MiniMqttBroker

GOLDEN = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "golden", "mqtt311_paho_session.json",
)


def _recv_exact(sock, n, timeout=10.0):
    sock.settimeout(timeout)
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return buf


def _recv_packet_bytes(sock):
    """One whole MQTT control packet, raw — reimplemented from MQTT-3.1.1
    §2.2 here (no imports from the broker module: the reader that checks
    the broker must not BE the broker)."""
    head = _recv_exact(sock, 1)
    mult, rl, n = 1, 0, 0
    while True:
        b = _recv_exact(sock, 1)
        head += b
        rl += (b[0] & 0x7F) * mult
        mult *= 128
        n += 1
        if not b[0] & 0x80:
            break
        if n > 4:
            raise ValueError("malformed remaining length")
    return head + (_recv_exact(sock, rl) if rl else b"")


def test_paho_session_fixtures_replay_byte_exact():
    fix = json.load(open(GOLDEN))
    broker = MiniMqttBroker()
    try:
        s = socket.create_connection(("127.0.0.1", broker.port))
        for step in fix["session"]:
            raw = bytes.fromhex(step["hex"])
            if step["dir"] == "c2s":
                s.sendall(raw)
            else:
                got = _recv_packet_bytes(s)
                assert got == raw, (
                    f"{step['name']} ({step['spec']}): broker sent "
                    f"{got.hex()}, spec/paho stream expects {raw.hex()}"
                )
        s.close()
    finally:
        broker.close()


def test_multibyte_remaining_length_roundtrip():
    """§2.2.3: payloads past 127 bytes need the varint continuation bit —
    a framing bug here corrupts every real model exchange (the fixture
    pins 321 -> C1 02)."""
    fix = json.load(open(GOLDEN))["multibyte_remaining_length"]
    topic = fix["publish_topic"]
    payload = bytes(range(256)) * 2
    payload = payload[: fix["payload_len"]]
    body = struct.pack("!H", len(topic)) + topic.encode() + payload
    assert len(body) == 321
    header = bytes.fromhex(fix["header_hex"])

    broker = MiniMqttBroker()
    try:
        sub = socket.create_connection(("127.0.0.1", broker.port))
        sub.sendall(bytes.fromhex("101500044d5154540402003c00097061686f2d74657374"))
        assert _recv_packet_bytes(sub)[:1] == b"\x20"
        tb = struct.pack("!H", len(topic)) + topic.encode()
        sub.sendall(b"\x82" + bytes([2 + len(tb) + 1]) + b"\x00\x01" + tb + b"\x00")
        assert _recv_packet_bytes(sub)[:1] == b"\x90"

        pub = socket.create_connection(("127.0.0.1", broker.port))
        # CONNECT, client-id "pub2": remaining length 10 + (2+4) = 0x10
        pub.sendall(bytes.fromhex("101000044d5154540402003c000470756232"))
        assert _recv_packet_bytes(pub)[:1] == b"\x20"
        pub.sendall(header + body)
        got = _recv_packet_bytes(sub)
        assert got == header + body  # identical multibyte-varint framing back
        pub.close()
        sub.close()
    finally:
        broker.close()


class _ForeignWireClient:
    """Spec-only MQTT 3.1.1 QoS-0 client: hand-rolled frames, zero shared
    code with core/mqtt_broker.MiniMqttClient (different structure on
    purpose — it exists to catch bugs both in-house endpoints would share)."""

    def __init__(self, host, port, client_id, on_message):
        self._sock = socket.create_connection((host, port))
        cid = client_id.encode()
        var = b"\x00\x04MQTT\x04\x02\x00\x3c" + struct.pack("!H", len(cid)) + cid
        self._sock.sendall(b"\x10" + self._varint(len(var)) + var)
        ack = _recv_packet_bytes(self._sock)
        assert ack == b"\x20\x02\x00\x00", ack.hex()
        self._on_message = on_message
        self._pid = 0
        threading.Thread(target=self._reader, daemon=True).start()

    @staticmethod
    def _varint(n):
        out = bytearray()
        while True:
            d = n % 128
            n //= 128
            out.append(d | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def subscribe(self, topic):
        self._pid += 1
        t = topic.encode()
        body = (
            struct.pack("!H", self._pid)
            + struct.pack("!H", len(t)) + t + b"\x00"
        )
        self._sock.sendall(b"\x82" + self._varint(len(body)) + body)

    def publish(self, topic, payload):
        t = topic.encode()
        body = struct.pack("!H", len(t)) + t + bytes(payload)
        self._sock.sendall(b"\x30" + self._varint(len(body)) + body)

    def _reader(self):
        try:
            while True:
                pkt = _recv_packet_bytes(self._sock)
                if pkt[0] >> 4 == 3:  # PUBLISH
                    # re-parse the remaining-length to find the body start
                    i = 1
                    while pkt[i] & 0x80:
                        i += 1
                    body = pkt[i + 1:]
                    tlen = struct.unpack("!H", body[:2])[0]
                    self._on_message(body[2:2 + tlen].decode(), body[2 + tlen:])
        except (ConnectionError, OSError, socket.timeout):
            pass

    def close(self):
        try:
            self._sock.sendall(b"\xe0\x00")
            self._sock.close()
        except OSError:
            pass


def test_foreign_wire_client_federates_with_inhouse_manager():
    """The interop proof: the in-house MqttCommManager (server side) and
    the spec-only foreign client (client side) complete a two-round
    model exchange through the broker over real TCP — binary Message
    envelopes, dtype-exact both ways."""
    from fedml_tpu.core.comm import Observer
    from fedml_tpu.core.message import Message
    from fedml_tpu.core.mqtt_comm import MqttCommManager

    broker = MiniMqttBroker()
    got_server = []

    class _Srv(Observer):
        def receive_message(self, t, m):
            got_server.append(m)

    try:
        server = MqttCommManager(0, host="127.0.0.1", port=broker.port)
        server.add_observer(_Srv())
        rx = threading.Thread(
            target=server.handle_receive_message, daemon=True
        )
        rx.start()

        got_client = []
        client = _ForeignWireClient(
            "127.0.0.1", broker.port, "foreign-client",
            on_message=lambda t, p: got_client.append(
                Message.from_bytes(p)
            ),
        )
        client.subscribe("fedml_tpu/to_1")
        time.sleep(0.2)  # both SUBSCRIBEs in flight before any publish

        w = np.arange(6, dtype=np.float32).reshape(2, 3)
        for rnd in range(2):
            # server -> client: broadcast the "global model"
            m = Message("sync", 0, 1)
            m.add_params("round", rnd)
            m.add_params("w", w * (rnd + 1))
            server.send_message(m)
            deadline = time.time() + 10
            while len(got_client) < rnd + 1 and time.time() < deadline:
                time.sleep(0.01)
            assert len(got_client) == rnd + 1, "client missed the broadcast"
            rx_msg = got_client[-1]
            np.testing.assert_array_equal(rx_msg.get("w"), w * (rnd + 1))

            # client -> server: upload a delta through the FOREIGN stack
            up = Message("upload", 1, 0)
            up.add_params("round", rnd)
            up.add_params("delta", rx_msg.get("w") + 1.0)
            client.publish("fedml_tpu/to_0", up.to_bytes())
            while len(got_server) < rnd + 1 and time.time() < deadline:
                time.sleep(0.01)
            assert len(got_server) == rnd + 1, "server missed the upload"
            np.testing.assert_array_equal(
                got_server[-1].get("delta"), w * (rnd + 1) + 1.0
            )
        client.close()
        server.stop_receive_message()
        rx.join(timeout=5)
    finally:
        broker.close()
