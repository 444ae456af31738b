"""True multi-process deployment: 3 OS processes (one replica each, the
reference's one-process-per-machine topology) coordinate via
jax.distributed; election, replication, commit, and per-host window fetch
all cross real process boundaries through gloo collectives."""

import os
import subprocess
import sys

WORKER = r"""
import os, sys
pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)    # 1 device per process
import jax
import numpy as np
from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.runtime.host import HostReplicaDriver

cfg = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
hd = HostReplicaDriver(cfg, process_id=pid, num_processes=n,
                       coordinator="127.0.0.1:%s" % port)

# step 1: host 0's election timer fires
res = hd.step(timeout_fired=(pid == 0))
assert res["term"] == 1, res
if pid == 0:
    assert res["role"] == 3, res     # LEADER
    assert res["became_leader"] == 1

# step 2: host 0 submits a client entry
batch = ([(int(EntryType.SEND), (0 << 24) | 1, 1, b"mh-write")]
         if pid == 0 else [])
res = hd.step(batch=batch, apply_done=int(res["commit"]))
if pid == 0:
    assert res["commit"] == 2, res

# step 3: lazy commit reaches every host
res = hd.step(apply_done=int(res["commit"]))
assert res["commit"] == 2, res

# every host reads the committed entry from its own replica's log
from rdma_paxos_tpu.consensus.log import M_LEN
wd, wm = hd.fetch_local_window(1)
payload = wd[0].astype("<i4").tobytes()[:int(wm[0, M_LEN])]
assert payload == b"mh-write", payload
print("HOST%d OK commit=%d leader=%d" % (pid, res["commit"],
                                         res["leader_id"]), flush=True)
"""



def test_three_process_cluster(tmp_path):
    port = str(9250 + (os.getpid() % 40))
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), "3", port],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(3)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=170)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"host {i} failed:\n{out}"
        assert f"HOST{i} OK commit=2 leader=0" in out, out


SCAN_WORKER = r"""
import os, sys
pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)    # 1 device per process
import jax
import numpy as np
from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.runtime.host import HostReplicaDriver
from rdma_paxos_tpu.runtime import hostpath

cfg = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
hd = HostReplicaDriver(cfg, process_id=pid, num_processes=n,
                       coordinator="127.0.0.1:%s" % port)

res = hd.step(timeout_fired=(pid == 0))
assert res["term"] == 1, res

# K=2 scan: host 0 feeds one batch per fused step; every host calls
# the SAME collective in the same iteration (lock-step contract)
batches = ([[(int(EntryType.SEND), (0 << 24) | 1, 1, b"sc-one")],
            [(int(EntryType.SEND), (0 << 24) | 1, 2, b"sc-two")]]
           if pid == 0 else [])
res, rows = hd.step_scan(2, batches, apply_done=int(res["commit"]))
# one more (empty) scan so the lazy commit reaches every host; rows
# are staged at apply_done=1 — the committed client entries arrive in
# the SAME dispatch, no fetch_local_window needed
res, (wd, wm) = hd.step_scan(2, [], apply_done=1)
commit = int(res["commit"])
assert commit == 3, res
batch = hostpath.decode_batch(wm, wd, commit - 1)
assert [t[3] for t in batch.tuples()] == [b"sc-one", b"sc-two"], (
    batch.tuples())
assert int(res["accepted"]) == 0          # nothing submitted this scan
print("HOST%d SCAN OK commit=%d leader=%d" % (pid, commit,
                                              int(res["leader_id"])),
      flush=True)
"""


def test_three_process_scan_tier(tmp_path):
    """The K-window scan tier across REAL process boundaries: fused
    steps + the consolidated readback + each host's replay window
    staged inside the one collective dispatch."""
    port = str(9450 + (os.getpid() % 40))
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get(
        "PYTHONPATH", "")
    script = tmp_path / "scan_worker.py"
    script.write_text(SCAN_WORKER)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), "3", port],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(3)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=170)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"host {i} failed:\n{out}"
        assert f"HOST{i} SCAN OK commit=3 leader=0" in out, out


REBASE_WORKER = r"""
import os, sys
pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)    # 1 device per process
import jax
import numpy as np
from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType, M_LEN
from rdma_paxos_tpu.runtime.host import HostReplicaDriver

# tiny threshold so a short stream crosses the i32-rollover boundary
cfg = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8,
                rebase_threshold=100)
hd = HostReplicaDriver(cfg, process_id=pid, num_processes=n,
                       coordinator="127.0.0.1:%s" % port)

res = hd.step(timeout_fired=(pid == 0))
assert res["role"] == (3 if pid == 0 else 1)
applied = 0
seq = 0
rebases = 0
sent = 0
TOTAL = 160
# every host runs the SAME loop; host 0 feeds batches. The gathered
# rebase_delta is identical on every host, so all apply the SAME
# subtraction in the same iteration — the NodeDaemon discipline.
for _ in range(220):
    batch = []
    if pid == 0:
        for _ in range(8):
            if sent < TOTAL:
                seq += 1; sent += 1
                batch.append((int(EntryType.SEND), (0 << 24) | 1, seq,
                              b"rb%05d" % seq))
    res = hd.step(batch=batch, apply_done=applied)
    applied = int(res["commit"])
    rd = int(res["rebase_delta"])
    if rd > 0:
        hd.rebase(rd)
        applied -= rd
        rebases += 1
assert rebases >= 1, "no rollover happened"
assert int(res["end"]) < cfg.rebase_threshold
# the last committed entry is readable at its POST-rollover index on
# every host's local shard
wd, wm = hd.fetch_local_window(int(res["commit"]) - 1)
payload = wd[0].astype("<i4").tobytes()[:int(wm[0, M_LEN])]
assert payload == b"rb%05d" % TOTAL, payload
print("HOST%d REBASE OK rebases=%d end=%d" % (pid, rebases,
                                              int(res["end"])), flush=True)
"""


def test_three_process_rebase(tmp_path):
    port = str(9350 + (os.getpid() % 40))
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    script = tmp_path / "rebase_worker.py"
    script.write_text(REBASE_WORKER)
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(i), "3", port],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i in range(3)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=170)
        outs.append(out.decode())
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"host {i} failed:\n{out}"
        assert f"HOST{i} REBASE OK" in out, out
