"""Three consensus groups on three replicas, each replica's interposed
app leading one group and following the other two, served by
``ShardedClusterDriver`` end to end at a toy ring: a seeded YCSB-A
stream (1.2 KB inserts of three log entries, then reads and one-field
updates on few keys) routed, key by key, to the app of the replica
that leads the key's group, and all THREE apps' records held, field by
field, to the plain reference
(``perfbench/reference/ycsb_register_cluster.py``).

The client contract the driver's module text states is kept: every
writer of a key reaches it through its group's leader's app."""

import contextlib
import subprocess
import threading
import time

import pytest

from perfbench.generators.resp_ycsb import (
    INSERT, READ, Keys, Stream, field_of, key_of, request_line)
from perfbench.reference import ycsb_register as ref
from perfbench.reference.ycsb_register_cluster import ClusterRegisters
from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.runtime.sharded_driver import (
    ShardedClusterDriver, key_prefix_of)
from tests.test_e2e_ycsb_r7 import NATIVE, Client, free_ports, spawn_apps

R = G = 3
CFG = LogConfig(n_slots=1024, slot_bytes=512, window_slots=64,
                batch_slots=64)
RECORDS = 30


@contextlib.contextmanager
def served_cluster(workdir, **kw):
    """-> (driver, ports): three apps under the shim behind a running
    ``ShardedClusterDriver(CFG, 3, 3, fanout="psum", **kw)``, group g
    led by replica g."""
    subprocess.run(["make", "-C", NATIVE], check=True, capture_output=True)
    ports = free_ports(R)
    apps, driver = [], None
    try:
        driver = ShardedClusterDriver(CFG, R, G, workdir=str(workdir),
                                      app_ports=ports, fanout="psum", **kw)
        spawn_apps(apps, ports, workdir)
        assert driver.cluster.place_leaders("round_robin") == [0, 1, 2]
        driver.run(period=0.002)
        deadline = time.time() + 120
        while driver.leaders() != [0, 1, 2] and time.time() < deadline:
            time.sleep(0.05)
        assert driver.leaders() == [0, 1, 2]
        yield driver, ports
    finally:
        if driver is not None:
            driver.stop()
        for a in apps:
            a.kill()
            a.wait()


@pytest.fixture()
def cluster(tmp_path):
    with served_cluster(tmp_path) as up:
        yield up


def group_of(driver, key: bytes) -> int:
    return driver.router.group_of(key_prefix_of(b"HGETALL " + key))


def key_in_group(driver, stem: bytes, g: int) -> bytes:
    return next(k for k in (b"%s%d" % (stem, i) for i in range(1000))
                if group_of(driver, k) == g)


def serve_mix(driver, ports, case: str) -> dict:
    """The load and then the mix, every operation through the app of
    the replica that leads its key's group; -> what was acknowledged
    (``writes``, ``reads``), the routing ``table``, ``keys`` and the
    completions by group."""
    leaders = driver.leaders()
    table = {key_of(rec): group_of(driver, key_of(rec))
             for rec in range(RECORDS)}
    assert set(table.values()) == set(range(G))
    if case == "hot_group":
        # five threads over all keys, a connection a (thread, group);
        # so skewed that the hottest record's group takes most of it
        mix = dict(connections=5, recordcount=RECORDS, fieldcount=10,
                   fieldlength=100, readproportion=0.5,
                   zipfian_constant=2.0)
        per_thread, mine = 40, None
    else:
        # one thread a group, each on its own group's keys alone
        mix = dict(connections=G, recordcount=RECORDS, fieldcount=10,
                   fieldlength=100, readproportion=0.5,
                   zipfian_constant=0.99)
        per_thread, mine = 60, (lambda conn, rec: table[key_of(rec)] == conn)
    keys = Keys(mix["recordcount"], mix["zipfian_constant"])
    writes, reads, lock = [], [], threading.Lock()
    done_by_group = [0] * G
    loaded = threading.Barrier(mix["connections"])
    errors = []

    def client(conn):
        try:
            # opened at start; each carries keys of its one group only
            conns = [Client(ports[leaders[g]]) for g in range(G)]
            stream = Stream(mix, 11, conn, keys)

            def do(op):
                kind, rec, _field, vals = op
                g = table[key_of(rec)]
                line = request_line(kind, rec, vals)
                t_req = time.monotonic()
                reply = conns[g].ask(line)
                t_rep = time.monotonic()
                with lock:
                    done_by_group[g] += 1
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
            n = 0
            while n < per_thread:
                op = stream.next_mixed()
                if mine is None or mine(conn, op[1]):
                    do(op)
                    n += 1
        except BaseException as exc:  # noqa: BLE001 — told to the test
            errors.append(exc)
            loaded.abort()

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(mix["connections"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    assert driver.leaders() == leaders and driver.loop_error is None
    n_ops = RECORDS + per_thread * mix["connections"]
    assert sum(done_by_group) == n_ops and all(done_by_group)
    return dict(table=table, keys=keys, writes=writes, reads=reads,
                done_by_group=done_by_group, n_ops=n_ops)


def apps_against_reference(driver, ports, served: dict) -> list:
    """A marker through EACH group's leader's app, seen on every app
    (an app that shows group g's has replayed everything g's log holds
    before it; the connection's first key pins it to the group it is
    to ride); then every app's ``COUNT`` and records, held to the plain
    reference of what was acknowledged: -> an app a row,
    ``dict(count, faults (by group), records)``."""
    leaders, table = driver.leaders(), served["table"]
    conns = [Client(p) for p in ports]
    markers = [key_in_group(driver, b"marker", g) for g in range(G)]
    for g in range(G):
        assert conns[leaders[g]].ask(b"SET %s 1\n" % markers[g]) == b"+OK"
    deadline = time.time() + 30
    behind = {(r, g) for r in range(R) for g in range(G)}
    while behind and time.time() < deadline:
        behind = {(r, g) for r, g in behind
                  if conns[r].ask(b"GET %s\n" % markers[g]) != b"1"}
        time.sleep(0.05)
    assert not behind

    regs = ClusterRegisters(served["writes"], table, G)
    assert sum(regs.records_per_group()) == RECORDS and not regs.strays
    assert served["reads"] and served["writes"]
    for r in served["reads"]:
        assert regs.read_faults(r) == []
    apps = []
    for conn in conns:
        recs = {key: conn.ask(b"HGETALL %s\n" % key) for key in table}
        apps.append(dict(
            count=int(conn.ask(b"COUNT\n")) - G,       # the markers
            faults=regs.app_faults({k: ref.parse_record(v)
                                    for k, v in recs.items()}),
            records=recs))
    return apps


@pytest.mark.parametrize("case", ["hot_group", "thread_per_group"])
def test_three_apps_hold_what_the_reference_admits(cluster, case):
    driver, ports = cluster
    served = serve_mix(driver, ports, case)
    done_by_group = served["done_by_group"]
    if case == "hot_group":
        hot = served["table"][key_of(served["keys"].by_rank[0])]
        assert done_by_group[hot] > served["n_ops"] / 2, (
            hot, done_by_group)

    apps = apps_against_reference(driver, ports, served)
    for app in apps:
        assert app["count"] == RECORDS
        assert app["faults"] == [[] for _ in range(G)]
    assert all(a["records"] == apps[0]["records"] for a in apps)

    counters = driver.obs.metrics.snapshot()["counters"]
    # every replica's app followed two groups, none out of order
    assert counters["replay_followers_total"] > 0
    assert counters["replay_reply_bytes_total"] > 0
    assert sum(rt.replay.order_timeouts for rt in driver.runtimes) == 0
    assert not any(rt.app_dirty for rt in driver.runtimes)
    # an acknowledgement a group, counted where it was released
    acks = [counters["group_acks_total{group=%d}" % g] for g in range(G)]
    assert all(a >= d for a, d in zip(acks, done_by_group)), (
        acks, done_by_group)
    assert counters["group_appends_total"] > 0
