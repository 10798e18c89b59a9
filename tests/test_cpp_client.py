"""C++ frontend: the native thin client (native/client/) against a live
cluster — the C++-API-parity row (the reference's cpp/src/ray/api.cc
driver surface; here tasks execute in the cluster's Python workers, with
bytes in / bytes out across the language boundary like the reference's
XLANG buffer convention)."""

import os
import subprocess
import sys

import pytest

import ray_memory_management_tpu as rmt


@pytest.fixture(scope="module")
def rmt_demo_binary(cpp_client_dir):
    return os.path.join(cpp_client_dir, "rmt_demo")


class TestCppClient:
    def test_demo_end_to_end(self, rmt_demo_binary):
        """Connect (mutual HMAC auth + version-checked ping), round-trip
        an object, invoke a cluster-registered function, wait, fetch."""
        from ray_memory_management_tpu.client.server import (
            ClusterServer, register_named_function, unregister_named_function)

        def cpp_transform(a: bytes, b: bytes) -> bytes:
            return (a + b).upper()

        rmt.init(num_cpus=2)
        server = None
        try:
            register_named_function("cpp_transform", cpp_transform)
            server = ClusterServer()
            host, port = server.address
            rc = subprocess.run(
                [rmt_demo_binary, host, str(port)], capture_output=True,
                text=True, timeout=240)
            assert rc.returncode == 0, (rc.stdout, rc.stderr)
            out = rc.stdout
            assert "CONNECTED" in out
            assert "GET roundtrip=ok" in out
            assert "DUPGET ok" in out
            assert "NAMED registered=yes" in out
            assert "WAIT ready=1 not_ready=0" in out
            assert "RESULT ABCDEF" in out
            assert "DEMO OK" in out
        finally:
            unregister_named_function("cpp_transform")
            if server is not None:
                server.close()
            rmt.shutdown()

    def test_bad_authkey_rejected(self, rmt_demo_binary):
        """A wrong authkey must fail the HMAC handshake, not hang or
        half-connect."""
        from ray_memory_management_tpu.client.server import ClusterServer

        rmt.init(num_cpus=2)
        server = None
        try:
            server = ClusterServer()
            host, port = server.address
            rc = subprocess.run(
                [rmt_demo_binary, host, str(port), "wrong-key"],
                capture_output=True, text=True, timeout=120)
            assert rc.returncode != 0
            assert "DEMO FAILED" in rc.stderr
            # the failed handshake must not kill the accept loop: a
            # well-keyed client connects fine afterwards
            from ray_memory_management_tpu.client.client import (
                ClientBackend)

            backend = ClientBackend(host, port)
            backend.close()
        finally:
            if server is not None:
                server.close()
            rmt.shutdown()

    def test_get_bytes_rejects_rich_values(self):
        """The raw-bytes boundary is typed: fetching a non-bytes value
        through get_bytes raises a clear error instead of handing the
        frontend an undecodable pickle."""
        from multiprocessing.connection import Client

        from ray_memory_management_tpu import serialization as ser
        from ray_memory_management_tpu.client.server import ClusterServer

        rmt.init(num_cpus=2)
        server = None
        try:
            server = ClusterServer()
            host, port = server.address
            oid = rmt.put({"rich": "value"})
            conn = Client((host, port), family="AF_INET",
                          authkey=b"rmt-client")
            # versioned handshake first: unversioned verbs are refused
            from ray_memory_management_tpu.config import (
                WIRE_PROTOCOL_VERSION,
            )

            conn.send({"type": "ping", "proto": WIRE_PROTOCOL_VERSION,
                       "req_id": 0})
            assert conn.recv()["error"] is None
            conn.send({"type": "get_bytes", "oids": [oid.binary()],
                       "req_id": 1, "timeout": 30})
            reply = conn.recv()
            assert reply["error"] is not None
            assert "non-bytes" in str(ser.loads(reply["error"]))
            conn.close()
        finally:
            if server is not None:
                server.close()
            rmt.shutdown()
