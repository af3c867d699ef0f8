"""Multi-host execution proof: 2 OS processes x 4 virtual CPU devices
form one 8-way jax.distributed mesh running the sharded session SPMD,
and the wire output is bit-identical to a single-process run — the
evidence that parallel/seqmesh.py's mesh spans hosts (SURVEY.md §2.3
cross-node backend; reference analog: multiple Kafka Streams instances
joining one consumer group, KProcessor.java:59-60)."""

import hashlib
import os
import socket
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_mesh_bit_exact():
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    outs = [os.path.join(_HERE, f"_mh_out_{i}.txt") for i in range(2)]
    procs = []
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    for i in range(2):
        if os.path.exists(outs[i]):
            os.unlink(outs[i])
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(_HERE, "distributed_worker.py"),
             coord, "2", str(i), outs[i]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    results = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed worker timed out")
        results.append((p.returncode, out, err))
    for rc, out, err in results:
        assert rc == 0, f"worker failed rc={rc}\n{err[-3000:]}"

    # single-process golden (8 virtual devices in THIS process — the
    # conftest already forces that topology), from the SAME
    # session/stream definition the workers use
    from tests.distributed_worker import build_session_and_stream

    ses, msgs = build_session_and_stream()
    golden = ses.process_wire(msgs)
    blob = "\n".join(l for ls in golden for l in ls).encode()
    want = f"{hashlib.sha256(blob).hexdigest()} {len(blob)}"

    for i in range(2):
        with open(outs[i]) as f:
            got = f.read().strip()
        assert got == want, f"worker {i} stream differs from golden"
        os.unlink(outs[i])
