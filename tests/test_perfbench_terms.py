"""Every probe term a ``perfbench/metrics/*.json`` names is a key the
program really exports.

A probe reader (``perfbench/readers/_terms.py``) returns ``None`` for a
term the deployment's probe lacks, and the metric silently leaves the
result line: a renamed phase or counter would just vanish. This drives a
toy ``ClusterDriver``, takes the benchmark's own
``DriverDeployment.probe`` of it and checks each file's terms against
its keys; and a toy ``ShardedClusterDriver`` under the cluster kind's
probe, for the metrics that a cell of that kind lists.
"""

import glob
import json
import os
import socket
import struct

import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.runtime.driver import ClusterDriver

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRIC_FILES = sorted(glob.glob(
    os.path.join(ROOT, "perfbench", "metrics", "*.json")))
# the kinds served by a ``ShardedClusterDriver``
CLUSTER_KINDS = ("interposed_app_cluster", "interposed_app_cluster_mesh")


def files_by_kind():
    """-> (metric files a cell of the cluster kind lists, those any
    other cell lists or none does): a term is due from the driver that
    serves the cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind_of = {}
    for cfg in bench["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            kind_of[cfg["name"]] = json.load(f)["deployment"]
    clustered = {w["name"] for w in bench["workloads"]
                 if kind_of[w["config"]] in CLUSTER_KINDS}
    lists = {m["name"]: set(m.get("workloads", ()))
             for m in bench["end_to_end"] + bench["per_layer"]}
    cluster, single = [], []
    for path in METRIC_FILES:
        cells = lists.get(os.path.basename(path)[:-len(".json")], set())
        if cells & clustered:
            cluster.append(path)
        if not cells or cells - clustered:
            single.append(path)
    return cluster, single


CLUSTER_FILES, SINGLE_FILES = files_by_kind()


def ids(paths):
    return [os.path.basename(p)[:-len(".json")] for p in paths]
PROBE_PREFIXES = ("phase.", "counter.", "bench.")
CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)  # manual


def probe_terms(spec: dict) -> list:
    """The strings of a metric file that name a probe key, whichever
    reader kind reads them (``num``/``den`` of probe_ratio, ``keys`` of
    probe_delta)."""
    found = []
    for value in spec.values():
        for item in (value if isinstance(value, list) else [value]):
            if isinstance(item, str) and item.startswith(PROBE_PREFIXES):
                found.append(item)
    return found


def say_hello(workdir: str) -> None:
    """A shim's HELLO up replica 0's link, as an interposed app's first
    word: the link threads count what they take in."""
    with socket.socket(socket.AF_UNIX) as link:
        link.connect(os.path.join(workdir, "proxy0.sock"))
        link.sendall(struct.pack("<BIiIB", 1, 1, 0, 1, 1))
        assert link.recv(8, socket.MSG_WAITALL) == struct.pack("<Ii", 1, 0)


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    from perfbench.deployments._driver_common import (
        DriverDeployment, SpanAcc)
    workdir = str(tmp_path_factory.mktemp("terms"))
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, workdir=workdir)
    try:
        say_hello(workdir)
        d.runtimes[0].timer._deadline = 0.0     # replica 0 times out
        d.step()
        assert d.leader() == 0
        handler = d._make_handler(0)
        conn = (0 << 24) | 1
        handler(int(EntryType.CONNECT), conn, b"")
        ev = handler(int(EntryType.SEND), conn, b"SET k v\n")
        for _ in range(20):
            d.step()
            if ev.done.is_set():
                break
        assert ev.done.is_set() and ev.status == 0
        dep = DriverDeployment.__new__(DriverDeployment)
        dep.driver = d
        dep.bench_spans = {"replay_fetch": SpanAcc()}
        return dep.probe()
    finally:
        d.stop()


@pytest.fixture(scope="module")
def sharded_probe(tmp_path_factory):
    """The cluster kind's probe of a toy ``ShardedClusterDriver`` that
    served one operation a group."""
    from perfbench.deployments import interposed_app_cluster
    from perfbench.deployments._driver_common import SpanAcc
    from rdma_paxos_tpu.runtime.sharded_driver import ShardedClusterDriver
    workdir = str(tmp_path_factory.mktemp("terms_sharded"))
    d = ShardedClusterDriver(CFG, 3, 3, workdir=workdir)
    try:
        say_hello(workdir)
        assert d.cluster.place_leaders("round_robin") == [0, 1, 2]
        d.step()
        evs = []
        for r in range(3):      # a key of group r through replica r
            handler = d._make_handler(r)
            conn = (r << 24) | 1
            key = next(k for k in (b"k%d" % i for i in range(100))
                       if d.router.group_of(k) == r)
            assert handler(int(EntryType.CONNECT), conn, b"") == 0
            evs.append(handler(int(EntryType.SEND), conn,
                               b"SET %s v\n" % key))
        for _ in range(20):
            d.step()
            if all(ev.done.is_set() for ev in evs):
                break
        assert all(ev.done.is_set() and ev.status == 0 for ev in evs)
        dep = interposed_app_cluster.Deployment.__new__(
            interposed_app_cluster.Deployment)
        dep.driver = d
        dep.bench_spans = {"replay_fetch": SpanAcc()}
        return dep.probe()
    finally:
        d.stop()


def test_metric_files_were_found():
    assert len(METRIC_FILES) >= 20
    assert len(CLUSTER_FILES) >= 20 and len(SINGLE_FILES) >= 20
    assert set(CLUSTER_FILES) | set(SINGLE_FILES) == set(METRIC_FILES)


def names_only_exported_terms(probe, path):
    with open(path) as f:
        spec = json.load(f)
    missing = [t for t in probe_terms(spec) if t not in probe]
    assert not missing, (
        f"{os.path.basename(path)} reads {missing}, which the program's "
        f"probe does not export: the metric would vanish from the "
        f"result line")


@pytest.mark.parametrize("path", SINGLE_FILES, ids=ids(SINGLE_FILES))
def test_metric_file_names_only_exported_terms(probe, path):
    names_only_exported_terms(probe, path)


@pytest.mark.parametrize("path", CLUSTER_FILES, ids=ids(CLUSTER_FILES))
def test_metric_file_names_only_terms_the_sharded_driver_exports(
        sharded_probe, path):
    names_only_exported_terms(sharded_probe, path)


def test_cluster_probe_exports_each_group_acks(sharded_probe):
    assert all(sharded_probe[f"counter.group_acks_total.g{g}"] >= 1
               for g in range(3))


def test_scope_reader_walks_a_recorded_tpu_capture(tmp_path):
    """``trace_scope_time`` reads the ``.xplane.pb`` wire format itself
    (``ProfileData`` hides the event metadata that carries the scope):
    on the capture recorded on a v5e it finds the anchors and the scope
    paths, and no more device time than the harness's own reduction."""
    import gzip

    from perfbench.harness import trace
    from perfbench.readers import trace_scope_time as reader
    tests = os.path.join(ROOT, "perfbench", "tests")
    raw = tmp_path / "small.xplane.pb"
    with gzip.open(os.path.join(tests, "small.xplane.pb.gz"), "rb") as f:
        raw.write_bytes(f.read())
    by_device = reader.scoped_seconds(str(raw))
    assert list(by_device) == ["/device:TPU:0"]
    scopes = by_device["/device:TPU:0"]
    assert "jit(replica_step)/vmap()/scatter:" in scopes
    with open(os.path.join(tests, "small.anchors.json")) as f:
        reduced = trace.reduce_trace(
            os.path.join(tests, "small.xplane.pb.gz"), json.load(f))
    busy = reduced["devices"]["/device:TPU:0"]["busy_s"]
    assert 0.5 * busy < sum(scopes.values()) <= busy
    # a program compiled without the scopes gives nothing to read
    import re
    assert not [s for s in scopes if re.search(r"[/(]append[/)]", s)]
