"""Native TCP host-communicator tests: build the C++ library and run real
multi-process collectives on localhost — the reference tested its MPI plane
with ``mpiexec -n 2..4`` (SURVEY.md section 4); this is the same coverage
with OS processes + TCP instead of MPI ranks."""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
WORKER = REPO / "tests" / "native_worker.py"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_native_lib_builds():
    from chainermn_tpu.native import lib_path

    assert lib_path().exists()


@pytest.mark.parametrize("size", [2, 4])
def test_multiprocess_collectives(size):
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)  # workers import the checkout only
    procs = [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(r), str(size), coord],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=str(REPO),
        )
        for r in range(size)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"WORKER_OK {r}" in out


# ----------------------------------------------------------------------
# TcpGroupComm units (ISSUE 8 satellite): the router's health checks
# lean on split()/probe() — pin nested rank translation and probe
# boundedness WITHOUT sockets, against a scripted parent (the real
# multi-process forms run in native_worker.py above).
# ----------------------------------------------------------------------

from collections import deque

from chainermn_tpu.native.tcp_comm import TcpGroupComm


class _ScriptedParent:
    """Single-process stand-in for the p2p plane: records send
    destinations, serves queued receives, probe reads the queue —
    never blocks, so a probe that WOULD hang fails the test instantly
    instead."""

    def __init__(self, rank, size):
        self.rank, self.size = rank, size
        self.sent = []
        self.inbox = {}

    def send_obj(self, obj, dest):
        self.sent.append((dest, obj))

    def recv_obj(self, source):
        q = self.inbox.get(source)
        if not q:
            raise LookupError(f"nothing queued from {source}")
        return q.popleft()

    def probe(self, source):
        return bool(self.inbox.get(source))


def test_group_comm_nested_split_translation():
    """``members`` always refers to the IMMEDIATE parent's rank space
    and translation composes: a nested group's send lands on the right
    WORLD rank after two hops."""
    parent = _ScriptedParent(rank=4, size=6)
    g = TcpGroupComm(parent, [1, 2, 4])
    assert (g.rank, g.size) == (2, 3)
    gg = TcpGroupComm(g, [0, 2])  # g-rank space: world ranks 1 and 4
    assert (gg.rank, gg.size) == (1, 2)
    gg.send_obj("hello", 0)
    assert parent.sent == [(1, "hello")]  # two-level translation
    parent.inbox[1] = deque(["reply"])
    assert gg.probe(0) is True
    assert gg.recv_obj(0) == "reply"
    # three levels deep: a singleton still addresses itself correctly
    ggg = TcpGroupComm(gg, [1])
    assert (ggg.rank, ggg.size) == (0, 1)
    ggg.send_obj("self", 0)
    assert parent.sent[-1] == (4, "self")


def test_group_comm_probe_silent_peer_is_bounded():
    """probe() of a peer that never sends returns False immediately,
    every time — a bounded poll, never a hang (the router's health
    check contract)."""
    import time

    parent = _ScriptedParent(rank=0, size=4)
    g = TcpGroupComm(parent, [0, 2])
    t0 = time.perf_counter()
    for _ in range(100):
        assert g.probe(1) is False
    assert time.perf_counter() - t0 < 1.0
    # a message appearing flips it without consuming
    parent.inbox[2] = deque(["late"])
    assert g.probe(1) is True
    assert g.probe(1) is True  # non-consuming, like MPI_Iprobe
    assert g.recv_obj(1) == "late"
    assert g.probe(1) is False


def test_group_comm_rejects_nonmember_constructor():
    parent = _ScriptedParent(rank=3, size=4)
    with pytest.raises(ValueError, match="not in its own split group"):
        TcpGroupComm(parent, [0, 1])
