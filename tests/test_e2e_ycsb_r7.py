"""Seven interposed apps serving YCSB's hash records, end to end at a
toy ring: 1.2 KB inserts that fragment into three log entries, fifty
mixed reads and updates on few keys from five concurrent clients, and
all SEVEN apps' records held to the plain reference
(``perfbench/reference/ycsb_register.py``). And the quorum of seven:
three followers cut off, the leader still commits; four, it does not."""

import os
import socket
import subprocess
import threading
import time

import pytest

from perfbench.generators.resp_ycsb import (
    INSERT, READ, Keys, Stream, key_of, request_line, field_of)
from perfbench.reference import ycsb_register as ref
from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.sim import SimCluster

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
R = 7
CFG = LogConfig(n_slots=1024, slot_bytes=512, window_slots=64,
                batch_slots=64)
MIX = dict(connections=5, recordcount=20, fieldcount=10, fieldlength=100,
           readproportion=0.5, zipfian_constant=0.99)
MIXED_OPS = 50


def free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def spawn_apps(apps, ports, workdir):
    """One toyserver under the shim a replica, appended to ``apps`` as
    it starts (the caller's ``finally`` kills what is there)."""
    for r, port in enumerate(ports):
        env = dict(os.environ,
                   LD_PRELOAD=os.path.join(NATIVE, "interpose.so"),
                   RP_PROXY_SOCK=os.path.join(str(workdir),
                                              f"proxy{r}.sock"))
        apps.append(subprocess.Popen(
            [os.path.join(NATIVE, "toyserver"), str(port)], env=env,
            stderr=subprocess.DEVNULL))
    time.sleep(0.3)                     # let the apps bind


@pytest.fixture()
def group(tmp_path):
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)
    ports = free_ports(R)
    apps, driver = [], None
    try:
        driver = ClusterDriver(
            CFG, R, workdir=str(tmp_path), app_ports=ports, fanout="psum",
            timeout_cfg=TimeoutConfig(elec_timeout_low=1.0,
                                      elec_timeout_high=2.0))
        spawn_apps(apps, ports, tmp_path)
        driver.run(period=0.002)
        deadline = time.time() + 120
        while driver.leader() < 0 and time.time() < deadline:
            time.sleep(0.05)
        assert driver.leader() >= 0, "no leader elected"
        yield driver, ports
    finally:
        if driver is not None:
            driver.stop()
        for a in apps:
            a.kill()
            a.wait()


class Client:
    def __init__(self, port):
        self.s = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.f = self.s.makefile("rb")

    def ask(self, line: bytes) -> bytes:
        self.s.sendall(line)
        return self.f.readline().rstrip(b"\n")


def test_seven_apps_hold_what_the_reference_admits(group):
    driver, ports = group
    lead = driver.leader()
    keys = Keys(MIX["recordcount"], MIX["zipfian_constant"])
    writes, reads, lock = [], [], threading.Lock()
    loaded = threading.Barrier(MIX["connections"])
    errors = []

    def client(conn):
        try:
            c = Client(ports[lead])
            stream = Stream(MIX, 7, conn, keys)

            def do(op):
                kind, rec, _field, vals = op
                line = request_line(kind, rec, vals)
                t_req = time.monotonic()
                reply = c.ask(line)
                t_rep = time.monotonic()
                with lock:
                    if kind == READ:
                        reads.append(ref.Read(
                            key_of(rec), ref.parse_record(reply), t_req,
                            t_rep))
                    else:
                        assert reply == b"+OK", reply
                        if kind == INSERT:
                            assert len(line) > 2 * CFG.slot_bytes
                        writes.extend(
                            ref.Write(key_of(rec), field_of(j), v, t_req,
                                      t_rep, ref.ACKED)
                            for j, v in vals.items())
            while (op := stream.next_insert()) is not None:
                do(op)
            loaded.wait(60)
            for _ in range(MIXED_OPS // MIX["connections"]):
                do(stream.next_mixed())
        except BaseException as exc:  # noqa: BLE001 — told to the test
            errors.append(exc)
            loaded.abort()

    frags0 = driver.obs.metrics.snapshot()["counters"].get(
        "intake_fragments_total", 0)
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(MIX["connections"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    assert driver.leader() == lead and driver.loop_error is None

    counters = driver.obs.metrics.snapshot()["counters"]
    n_ops = MIX["recordcount"] + MIXED_OPS
    # an insert is three entries, everything else one: the operations,
    # and each connection's CONNECT (and CLOSE, where it was seen yet)
    entries = counters["intake_fragments_total"] - frags0
    assert 0 < (entries - n_ops - 2 * MIX["recordcount"]
                ) <= 2 * MIX["connections"]
    assert counters["intake_payload_bytes_total"] > 1100 * MIX["recordcount"]

    # a marker through the leader's app: a follower that shows it has
    # replayed everything before it
    conns = [Client(p) for p in ports]
    assert conns[lead].ask(b"SET marker 1\n") == b"+OK"
    deadline = time.time() + 30
    behind = set(range(R))
    while behind and time.time() < deadline:
        behind = {r for r in behind
                  if conns[r].ask(b"GET marker\n") != b"1"}
        time.sleep(0.05)
    assert not behind

    regs = ref.Registers(writes)
    assert len(reads) + len(writes) > 0
    for r in reads:
        assert regs.read_faults(r) == []
    answers = []
    for conn in conns:
        assert int(conn.ask(b"COUNT\n")) == MIX["recordcount"] + 1
        recs = [conn.ask(b"HGETALL %s\n" % key_of(rec))
                for rec in range(MIX["recordcount"])]
        for rec, line in enumerate(recs):
            assert regs.record_faults(key_of(rec),
                                      ref.parse_record(line)) == []
        answers.append(recs)
    assert all(a == answers[0] for a in answers)        # all seven alike
    assert counters["replay_followers_total"] > 0
    assert counters["replay_reply_bytes_total"] > 0
    assert sum(rt.replay.order_timeouts for rt in driver.runtimes) == 0


@pytest.mark.parametrize("cut,commits", [(3, True), (4, False)])
def test_commit_needs_four_of_seven(cut, commits):
    c = SimCluster(LogConfig(n_slots=64, slot_bytes=32, window_slots=16,
                             batch_slots=8), R)
    c.run_until_elected(0)
    c.step()
    base = int(c.last["commit"][0])
    alive = list(range(R - cut))
    c.partition([alive] + [[r] for r in range(R - cut, R)])
    c.submit(0, b"SET k v")
    for _ in range(3):
        res = c.step()
    assert res["end"][0] == base + 1
    assert res["commit"][0] == (base + 1 if commits else base)
    assert all(res["commit"][r] == base for r in range(R - cut, R))
