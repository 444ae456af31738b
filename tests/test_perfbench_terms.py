"""Every probe term a ``perfbench/metrics/*.json`` names is a key the
program really exports.

A probe reader (``perfbench/readers/_terms.py``) returns ``None`` for a
term the deployment's probe lacks, and the metric silently leaves the
result line: a renamed phase or counter would just vanish. This drives a
toy ``ClusterDriver``, takes the benchmark's own
``DriverDeployment.probe`` of it and checks each file's terms against
its keys.
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


@pytest.fixture(scope="module")
def probe(tmp_path_factory):
    from perfbench.deployments._driver_common import (
        DriverDeployment, SpanAcc)
    workdir = str(tmp_path_factory.mktemp("terms"))
    d = ClusterDriver(CFG, 3, timeout_cfg=TO, workdir=workdir)
    try:
        # a shim's HELLO up replica 0's link, as an interposed app's
        # first word: the link threads count what they take in
        with socket.socket(socket.AF_UNIX) as link:
            link.connect(os.path.join(workdir, "proxy0.sock"))
            link.sendall(struct.pack("<BIiIB", 1, 1, 0, 1, 1))
            assert link.recv(8, socket.MSG_WAITALL) == struct.pack(
                "<Ii", 1, 0)
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


def test_metric_files_were_found():
    assert len(METRIC_FILES) >= 20


@pytest.mark.parametrize(
    "path", METRIC_FILES,
    ids=[os.path.basename(p)[:-len(".json")] for p in METRIC_FILES])
def test_metric_file_names_only_exported_terms(probe, path):
    with open(path) as f:
        spec = json.load(f)
    missing = [t for t in probe_terms(spec) if t not in probe]
    assert not missing, (
        f"{os.path.basename(path)} reads {missing}, which the program's "
        f"probe does not export: the metric would vanish from the "
        f"result line")


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
