"""Elastic multi-host: generation-based world rebuild (SURVEY §3.5's
join/recovery chain re-homed to a DCN control plane).

The headline scenario is the reference's ``reconf_bench.sh`` AddServer
story made real: a 3-host cluster loses a host, keeps serving as 2, the
host restarts, rejoins via the donor snapshot (consensus row + stable
store), and serves the FULL replicated history — plus new writes."""

import json
import os
import socket
import subprocess
import threading
import time

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import EntryType, M_TYPE
from rdma_paxos_tpu.consensus.membership import MembershipManager
from rdma_paxos_tpu.consensus.snapshot import export_row, genesis_row
from rdma_paxos_tpu.consensus.state import ConfigState, Role
from rdma_paxos_tpu.runtime.sim import SimCluster

NATIVE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)


# ---------------------------------------------------------------------------
# unit level: the genesis transform
# ---------------------------------------------------------------------------

def test_export_and_genesis_row():
    c = SimCluster(CFG, 8, group_size=3)
    mm = MembershipManager(c)
    c.run_until_elected(0)
    c.submit(0, b"payload-1")
    c.step()
    mm.change(0, 0b1111)            # leave a CONFIG entry in the log
    c.submit(0, b"payload-2")
    c.step()
    c.step()

    row = export_row(c.state, 0)
    assert int(row["commit"]) >= 4
    sw = CFG.slot_words
    assert (row["log_buf"][:, sw + M_TYPE]
            == int(EntryType.CONFIG)).any(), "precondition: CONFIG present"

    g = genesis_row(row, group_mask=0b11, epoch=9, n_replicas=2,
                    term=int(row["term"]) + 5)
    # CONFIG entries neutralized; old-world masks cannot resurface
    assert not (g["log_buf"][:, sw + M_TYPE]
                == int(EntryType.CONFIG)).any()
    # log content otherwise carried verbatim
    assert int(g["end"]) == int(row["end"])
    assert int(g["commit"]) == int(row["commit"])
    # new-world config installed as live AND committed checkpoint
    for k in ("bitmask_old", "bitmask_new", "ccfg_old", "ccfg_new"):
        assert int(g[k]) == 0b11
    assert int(g["epoch"]) == 9 and int(g["ccfg_epoch"]) == 9
    # fresh elections: term past every survivor, votes cleared
    assert int(g["term"]) == int(row["term"]) + 6
    assert int(g["voted_for"]) == -1 and int(g["voted_term"]) == 0
    assert int(g["role"]) == int(Role.FOLLOWER)
    assert g["vote_rec_term"].shape == (2,)
    # the original row is untouched
    assert (row["log_buf"][:, sw + M_TYPE]
            == int(EntryType.CONFIG)).any()


def test_genesis_boot_in_sim():
    """A cluster rebuilt from a genesis row elects and serves — and the
    carried log replays the full history on every member."""
    c = SimCluster(CFG, 3)
    c.run_until_elected(0)
    for i in range(5):
        c.submit(0, b"hist-%d" % i)
        c.step()
    c.step()
    donor = export_row(c.state, 0)
    g = genesis_row(donor, group_mask=0b11, epoch=1, n_replicas=2)

    import jax.numpy as jnp
    import jax
    c2 = SimCluster(CFG, 2)
    # install the genesis row on every replica of the new world
    leaves = {}
    import dataclasses
    from rdma_paxos_tpu.consensus.log import pad_rows
    from rdma_paxos_tpu.consensus.state import ReplicaState
    for f in dataclasses.fields(ReplicaState):
        if f.name == "log":
            continue
        cur = getattr(c2.state, f.name)
        leaves[f.name] = jnp.broadcast_to(
            jnp.asarray(np.asarray(g[f.name]).astype(cur.dtype)),
            cur.shape)
    leaves["log"] = dataclasses.replace(c2.state.log, buf=jnp.broadcast_to(
        jnp.asarray(pad_rows(g["log_buf"])), c2.state.log.buf.shape))
    c2.state = ReplicaState(**leaves)
    c2.run_until_elected(1)
    c2.submit(1, b"new-world")
    c2.step()
    c2.step()
    for r in range(2):
        stream = [p for (_, _, _, p) in c2.replayed[r]]
        assert stream == [b"hist-%d" % i for i in range(5)] + \
            [b"new-world"], stream


# ---------------------------------------------------------------------------
# unit level: controller cut safety
# ---------------------------------------------------------------------------

def test_controller_unusable_survivors_cannot_justify_cut():
    """A cut must wait until donor-ELIGIBLE survivors alone include a
    majority of the previous world. Scenario: commit acked by leader +
    a follower that then wedges (usable=0); leader dies; the remaining
    follower lags. The wedged follower is the only surviving holder of
    the committed entry, so cutting with the laggard as donor would
    silently drop an acked write — the controller must refuse until a
    provably complete donor set registers."""
    from rdma_paxos_tpu.runtime.elastic import GroupController
    ctl = GroupController(expect=3, settle=0.0)
    try:
        full = dict(term=5, last_log_term=5, end=10, commit=10,
                    apply=10, applied=10, leader=1, usable=1)
        for h in range(3):
            ctl._handle({"op": "register", "host": h,
                         "addr": "127.0.0.1:1", "meta": None})
        assert ctl._spec is not None and ctl._spec["gen"] == 1
        ctl._handle({"op": "fail", "host": 1, "gen": 1})
        wedged = dict(full, leader=0, usable=0)
        laggard = dict(full, leader=0, end=5, commit=5, apply=5,
                       applied=5)
        ctl._handle({"op": "register", "host": 1,
                     "addr": "127.0.0.1:1", "meta": wedged})
        ctl._handle({"op": "register", "host": 2,
                     "addr": "127.0.0.1:1", "meta": laggard})
        r = ctl._handle({"op": "poll", "host": 2})
        # supervisors ignore spec gens they already ran; the check is
        # that no NEW generation was cut from this survivor set
        assert r["gen"] == 1, (
            "cut proceeded with 1 donor-eligible survivor of 3 — the "
            "wedged follower's committed entries would be dropped")
        # the dead leader returns with its complete log: two eligible
        # survivors now overlap the previous world -> cut, donor = the
        # most up-to-date ELIGIBLE host
        ctl._handle({"op": "register", "host": 0,
                     "addr": "127.0.0.1:1", "meta": dict(full)})
        r = ctl._handle({"op": "poll", "host": 0})
        assert r.get("ok") and r["gen"] == 2
        assert r["donor"] == 0
    finally:
        ctl.close()


def test_controller_all_meta_less_survivors_cut_fresh_world():
    """When EVERY surviving registration is meta-less (all disks lost),
    nothing is recoverable anywhere: the controller must cut a fresh
    world (donor -1) rather than deadlock waiting for an eligible donor
    that can never appear."""
    from rdma_paxos_tpu.runtime.elastic import GroupController
    ctl = GroupController(expect=3, settle=0.0)
    try:
        for h in range(3):
            ctl._handle({"op": "register", "host": h,
                         "addr": "127.0.0.1:1", "meta": None})
        assert ctl._spec is not None and ctl._spec["gen"] == 1
        ctl._handle({"op": "fail", "host": 0, "gen": 1})
        for h in range(3):
            ctl._handle({"op": "register", "host": h,
                         "addr": "127.0.0.1:1", "meta": None})
        r = ctl._handle({"op": "poll", "host": 0})
        assert r.get("ok") and r["gen"] == 2, r
        assert r["donor"] == -1
        # oversized host ids are refused at the door (the proxy layer
        # cannot encode them) — they must never enter a generation
        r = ctl._handle({"op": "register", "host": 128,
                         "addr": "127.0.0.1:1", "meta": None})
        assert "error" in r
    finally:
        ctl.close()


# ---------------------------------------------------------------------------
# full multi-process scenario
# ---------------------------------------------------------------------------

# all offsets share one residue class mod 300 so two pytest processes
# (different pids) can never collide on each other's host ports; the
# 17000+ base clears every other test file's range
_BASE = 17000 + (os.getpid() % 300)
APP_PORTS = {0: _BASE, 1: _BASE + 300, 2: _BASE + 600,
             3: _BASE + 900}

CFG_JSON = json.dumps({
    "log": {"n_slots": 256, "slot_bytes": 64, "window_slots": 32,
            "batch_slots": 16},
    "timing": {"elec_timeout_low": 0.4, "elec_timeout_high": 0.9},
})


def _kv(port, line, timeout=5.0):
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    f = s.makefile("rb")
    s.sendall(line)
    out = f.readline().strip()
    s.close()
    return out


def _wait_kv(port, key, want, timeout=60.0):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = _kv(port, b"GET %s\n" % key)
            if last == want:
                return last
        except OSError:
            pass
        time.sleep(0.3)
    return last


def _dump_meta(workdir, h):
    from rdma_paxos_tpu.runtime.elastic import read_rowdump
    d = read_rowdump(workdir, h)
    return d[1] if d is not None else None


def _wait_leader(dirs, hosts, gen, timeout=240.0):
    """Wait until some member's fresh dump (of this generation) claims
    leadership; returns its host id."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for h in hosts:
            m = _dump_meta(dirs[h], h)
            if m and m.get("gen") == gen and m.get("leader"):
                return h
        time.sleep(0.3)
    raise AssertionError(f"no leader dump for gen {gen}")


def _replicated_set(dirs, hosts, key, val, timeout=240.0):
    """Write ``key=val`` through whichever member currently leads and
    wait until every OTHER member's app serves it — retrying across
    leadership moves and generation churn (both are legitimate elastic
    behavior the test must ride out)."""
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        # freshest leadership claim wins; fall back to trying everyone
        order = sorted(
            hosts,
            key=lambda h: -(_dump_meta(dirs[h], h) or {}).get("leader", 0))
        for h in order:
            try:
                if _kv(APP_PORTS[h],
                       b"SET %s %s\n" % (key, val)) != b"+OK":
                    continue
            except OSError:
                continue
            ok = True
            for o in hosts:
                if o == h:
                    continue
                last = _wait_kv(APP_PORTS[o], key, val, timeout=25)
                if last != val:
                    ok = False
                    break
            if ok:
                return h
        time.sleep(0.5)
    raise AssertionError(
        f"write {key!r} never replicated to all of {hosts} "
        f"(last observed {last!r})")


def _wait_gen(ctl, g, timeout=240.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        with ctl._lock:
            if ctl._spec is not None and ctl._spec["gen"] >= g:
                return dict(ctl._spec)
        time.sleep(0.2)
    raise AssertionError(f"generation {g} never cut")


def _wait_member(ctl, host, after_gen, timeout=240.0):
    """Wait (across generation churn) for a generation that includes
    ``host``; returns its spec."""
    spec = _wait_gen(ctl, after_gen + 1)
    deadline = time.time() + timeout
    while host not in [m["host"] for m in spec["members"]]:
        assert time.time() < deadline, f"host {host} never admitted"
        spec = _wait_gen(ctl, spec["gen"] + 1)
    return spec


@pytest.fixture(scope="module")
def built_native():
    subprocess.run(["make", "-C", NATIVE], check=True,
                   capture_output=True)


def test_elastic_loss_restart_rejoin(tmp_path, built_native):
    from rdma_paxos_tpu.runtime.elastic import (ElasticSupervisor,
                                                GroupController)
    # barrier_timeout must exceed a generation's FIRST round, which
    # includes cold XLA compiles (~20-40s on a loaded CPU host); the
    # compile cache is machine-stable so later runs are warm
    ctl = GroupController(expect=3, settle=1.2, barrier_timeout=90.0)
    dirs = {h: str(tmp_path / f"h{h}") for h in range(3)}
    # tests opt into the CPU backend EXPLICITLY (workers never default
    # to CPU — a silent CPU fallback on a TPU deployment was an advisor
    # finding); the compile cache is the one conftest exports
    wenv = {"JAX_PLATFORMS": "cpu"}

    def mk_sup(h):
        sup = ElasticSupervisor(
            host_id=h, controller=f"127.0.0.1:{ctl.port}",
            workdir=dirs[h], app_port=APP_PORTS[h],
            round_iters=12, cfg_json=CFG_JSON, worker_env=wenv)
        t = threading.Thread(target=sup.run, daemon=True)
        t.start()
        return sup

    sups = {h: mk_sup(h) for h in range(3)}
    try:
        # ---- generation 1: 3 hosts, write replicates ----
        spec1 = _wait_gen(ctl, 1)
        assert [m["host"] for m in spec1["members"]] == [0, 1, 2]
        lead = _wait_leader(dirs, [0, 1, 2], 1)
        lead = _replicated_set(dirs, [0, 1, 2], b"era", b"first")

        # ---- kill a non-leader host hard (worker dies mid-world) ----
        victim = next(h for h in range(3) if h != lead)
        sups[victim].stop()
        spec2 = _wait_gen(ctl, 2)
        survivors = [m["host"] for m in spec2["members"]]
        assert victim not in survivors and len(survivors) == 2

        # ---- generation 2: survivors still serve and replicate ----
        _wait_leader(dirs, survivors, spec2["gen"])
        _replicated_set(dirs, survivors, b"during", b"outage")

        # ---- restart the victim: it must rejoin via snapshot ----
        sups[victim] = mk_sup(victim)
        spec3 = _wait_member(ctl, victim, spec2["gen"])
        gen3 = spec3["gen"]

        # the rejoined host serves the FULL history: the gen-1 write it
        # saw before dying AND the gen-2 write it completely missed
        assert _wait_kv(APP_PORTS[victim], b"era", b"first",
                        timeout=240) == b"first"
        assert _wait_kv(APP_PORTS[victim], b"during", b"outage") == \
            b"outage", "rejoined host missed the write from its outage"

        # ---- and the rebuilt world replicates new writes everywhere ----
        members3 = [m["host"] for m in spec3["members"]]
        _wait_leader(dirs, members3, gen3)
        _replicated_set(dirs, members3, b"back", b"three")

        # ---- a BRAND-NEW host joins the running group (the reference's
        # AddServer: a server never seen before is admitted and
        # snapshot-recovers the full history, reconf_bench.sh:153) ----
        dirs[3] = str(tmp_path / "h3")
        sups[3] = mk_sup(3)
        spec4 = _wait_member(ctl, 3, gen3)
        # the joiner serves history it never witnessed...
        assert _wait_kv(APP_PORTS[3], b"era", b"first",
                        timeout=240) == b"first"
        assert _wait_kv(APP_PORTS[3], b"back", b"three") == b"three"
        # ...and participates in new replication
        members4 = [m["host"] for m in spec4["members"]]
        _wait_leader(dirs, members4, spec4["gen"])
        _replicated_set(dirs, members4, b"four", b"hosts")
    finally:
        for sup in sups.values():
            sup.stop()
        ctl.close()


def test_leader_sigkill_under_speculative_load(tmp_path, built_native):
    """The reference's RemoveLeader scenario (reconf_bench.sh:96-123) at
    FULL stack depth with speculative clients in flight: SIGKILL the
    LEADER's worker mid-drain while a pipelined spec-mode client is
    streaming SETs. Asserts:

    * output commit — every reply the client READ corresponds to an
      entry that survives on the new world (acked => committed =>
      durable across the leader's death);
    * the dead host's diverged speculative app is discarded and a FRESH
      app is rebuilt from the committed store (quarantine discipline at
      generation granularity: new app pid, full history served);
    * the rebuilt world replicates new writes everywhere.
    """
    from rdma_paxos_tpu.runtime.elastic import (ElasticSupervisor,
                                                GroupController)
    ctl = GroupController(expect=3, settle=1.2, barrier_timeout=90.0)
    dirs = {h: str(tmp_path / f"h{h}") for h in range(3)}
    wenv = {"JAX_PLATFORMS": "cpu"}

    def mk_sup(h):
        sup = ElasticSupervisor(
            host_id=h, controller=f"127.0.0.1:{ctl.port}",
            # long drain rounds: this test pushes a deep pipelined
            # backlog, and the worker must not stall it on control
            # beats (the default 12-iteration rounds are tuned for the
            # churn-heavy rejoin test above)
            workdir=dirs[h], app_port=APP_PORTS[h],
            round_iters=100, cfg_json=CFG_JSON, worker_env=wenv)
        t = threading.Thread(target=sup.run, daemon=True)
        t.start()
        return sup

    sups = {h: mk_sup(h) for h in range(3)}
    try:
        spec1 = _wait_gen(ctl, 1)
        assert [m["host"] for m in spec1["members"]] == [0, 1, 2]
        lead = _wait_leader(dirs, [0, 1, 2], 1)
        old_app_pid = sups[lead]._app.pid if sups[lead]._app else None

        # pipelined speculative client: stream N SETs in one blob; the
        # spec shim lets the app execute ahead while replies are held
        # until commit
        N = 40000
        s = socket.create_connection(("127.0.0.1", APP_PORTS[lead]),
                                     timeout=20)

        # CONTINUOUS writer thread: keeps the submit backlog deep for
        # the whole window so the kill provably lands with speculative
        # input in flight (a single pre-sent blob can fully commit
        # before the signal arrives — replies flush in large batches)
        def writer():
            try:
                for i in range(N):
                    s.sendall(b"SET kq%05d v%05d\n" % (i, i))
            except OSError:
                pass              # severed by the kill — expected
        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        s.settimeout(10)
        got = b""
        while got.count(b"\n") < 2000:
            chunk = s.recv(65536)
            assert chunk, "connection died before the kill"
            got += chunk

        # ---- SIGKILL the leader's WORKER mid-burst ----
        assert sups[lead]._child is not None
        sups[lead]._child.kill()

        # drain whatever replies still arrive until the shim severs
        try:
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                got += chunk
        except OSError:
            pass
        s.close()
        wt.join(timeout=30)
        acked = got.count(b"\n")
        assert 0 < acked < N, (
            f"kill did not land mid-burst (acked={acked}/{N})")

        # ---- the survivors cut a new generation without the leader ----
        spec2 = _wait_gen(ctl, spec1["gen"] + 1)
        survivors = [m["host"] for m in spec2["members"]]
        # (the supervisor auto-re-registers the dead host, so it may
        # already be back in spec2 — what matters is the group serves)
        serving = [h for h in survivors]
        _wait_leader(dirs, serving, spec2["gen"])

        # ---- output commit: every ACKED reply's entry survives ----
        # acks release in connection order, so the acked set is exactly
        # the prefix kq0000..kq{acked-1}
        check = next(h for h in serving if h != lead) \
            if any(h != lead for h in serving) else serving[0]
        assert _wait_kv(APP_PORTS[check], b"kq%05d" % (acked - 1),
                        b"v%05d" % (acked - 1), timeout=240) == \
            b"v%05d" % (acked - 1), "last acked write lost"
        # spot-check the whole acked prefix in one connection
        sc = socket.create_connection(("127.0.0.1", APP_PORTS[check]),
                                      timeout=20)
        fc = sc.makefile("rb")
        for i in range(0, acked, max(1, acked // 50)):
            sc.sendall(b"GET kq%05d\n" % i)
            assert fc.readline().strip() == b"v%05d" % i, f"kq{i} lost"
        sc.close()

        # ---- the dead host rejoins with a FRESH app rebuilt from the
        # committed store (the generation-level quarantine) ----
        spec3 = _wait_member(ctl, lead, spec2["gen"] - 1)
        assert _wait_kv(APP_PORTS[lead], b"kq%05d" % (acked - 1),
                        b"v%05d" % (acked - 1), timeout=240) == \
            b"v%05d" % (acked - 1), "rejoined host missing acked write"
        new_app_pid = sups[lead]._app.pid if sups[lead]._app else None
        assert new_app_pid is not None and new_app_pid != old_app_pid, \
            "speculative app was not replaced after the kill"

        # ---- and the rebuilt world replicates new writes ----
        members3 = [m["host"] for m in spec3["members"]]
        _replicated_set(dirs, members3, b"post", b"kill")
    finally:
        for sup in sups.values():
            sup.stop()
        ctl.close()
