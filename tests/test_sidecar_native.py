"""The sidecar's process boundary exercised from OUTSIDE Python.

Reference: ``pkg/scheduler/extender.go`` (HTTPExtender) is the integration
precedent — a scheduler written in another language reaches the TPU engine
over the wire. ``native/sidecar_client.c`` speaks the actual protocol
(gRPC/HTTP2 via libcurl, 5-byte frames, hand-rolled msgpack codec): the
proof that sidecar/proto.py needs no Python on the consumer side.
"""

import os
import shutil
import subprocess

import pytest

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")


@pytest.fixture(scope="module")
def client_bin(tmp_path_factory):
    """Built from sidecar_client.c on every run, into a per-run directory:
    a binary left in native/ (it is git-ignored, so it rides along in
    copies of the tree) is never what gets tested."""
    cc = shutil.which("gcc") or shutil.which("cc")
    if cc is None:
        pytest.skip("no C compiler")
    out = str(tmp_path_factory.mktemp("native") / "sidecar_client")
    subprocess.run([cc, "-O2", "-Wall", "-std=c11", "-o", out,
                    os.path.join(NATIVE_DIR, "sidecar_client.c"), "-ldl"],
                   check=True, capture_output=True)
    return out


def test_native_client_full_protocol(client_bin):
    """PushSnapshot -> Schedule (100x100) -> PushDelta bind -> STALE
    rejection -> second wave -> ordered node/pod deletes, all from C."""
    from kubernetes_tpu.sidecar import SidecarServer
    srv = SidecarServer().start()
    try:
        p = subprocess.run([client_bin, srv.address, "100", "100"],
                           capture_output=True, text=True, timeout=180)
        assert p.returncode == 0, (p.stdout, p.stderr)
        assert "ALL CHECKS PASSED" in p.stdout
        assert "STALE (server at 2)" in p.stdout
        assert "100/100 pods placed" in p.stdout
    finally:
        srv.stop()
