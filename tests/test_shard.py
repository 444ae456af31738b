"""Sharded multi-group consensus (rdma_paxos_tpu.shard): router
unit/edge/golden contracts plus the subsystem's acceptance properties:

* G=1 ``ShardedCluster`` is BIT-IDENTICAL to ``SimCluster`` on a
  recorded workload (election, traffic, partition + failover, heal) —
  single-group is the G=1 special case, not a parallel code path;
* a homogeneous G=4 cluster runs every group through exactly ONE
  compiled step program (shared runtime cache; no per-group compiles),
  and ``prewarm()`` tiers are shared across clusters and group counts;
* crashing ONE group's leader leaves the other groups' commit
  frontiers strictly advancing (fault isolation), with the existing
  I1–I5 invariants checked per group (shard nemesis);
* routed KVS sessions keep per-group dedup sequence numbers and
  survive a single-group leader failover with exactly-once applies;
* per-group observability: ``...{group=g}`` metric series, the
  ``(group, term, index)`` span correlation key, and the router
  serialized into the health document.
"""

import json
import os

import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.obs import Observability
from rdma_paxos_tpu.runtime.sim import STEP_CACHE, SimCluster
from rdma_paxos_tpu.shard import (
    KeyRouter, RangeRule, ShardedCluster, ShardedKVS)
from rdma_paxos_tpu.shard.chaos import ShardNemesisRunner
from rdma_paxos_tpu.shard.router import canon_key, ring_hash

CFG = LogConfig(n_slots=128, slot_bytes=128, window_slots=32,
                batch_slots=16)
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "router_map.json")


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def test_router_edge_cases():
    r = KeyRouter(4)
    # empty key is a legal key with a stable home
    g_empty = r.group_of(b"")
    assert 0 <= g_empty < 4
    assert r.group_of("") == g_empty
    # unicode str keys canonicalize to their UTF-8 bytes
    assert r.group_of("ключ") == r.group_of("ключ".encode("utf-8"))
    assert r.group_of("鍵") == r.group_of("鍵".encode("utf-8"))
    # long keys route fine and deterministically
    long_key = b"x" * 65536
    assert r.group_of(long_key) == r.group_of(bytearray(long_key))
    # non-key types are rejected loudly
    with pytest.raises(TypeError):
        r.group_of(42)
    # determinism across independently built routers (same params)
    r2 = KeyRouter(4)
    for i in range(200):
        k = b"edge%d" % i
        assert r.group_of(k) == r2.group_of(k)


def test_router_balance_is_reasonable():
    r = KeyRouter(4)
    counts = [0] * 4
    for i in range(4000):
        counts[r.group_of(b"key%d" % i)] += 1
    # hash-ring balance: no group starved or hot beyond ~2x fair share
    assert min(counts) > 400 and max(counts) < 2000, counts


def test_router_range_override_precedence():
    # narrow rule listed first wins over the broad rule and the ring
    r = KeyRouter(4, overrides=[("user:vip", "user:viq", 3),
                                ("user:", "user;", 1)])
    assert r.group_of(b"user:vip42") == 3      # narrow first match
    assert r.group_of(b"user:alice") == 1      # broad rule
    assert r.group_of(b"user:vio") == 1        # below the narrow lo
    # outside every override: ring routing, consistent with a
    # no-override router (overrides never perturb the ring)
    bare = KeyRouter(4)
    assert r.group_of(b"other:key") == bare.group_of(b"other:key")
    # hi=None is unbounded
    r2 = KeyRouter(4, overrides=[RangeRule(b"zz", None, 2)])
    assert r2.group_of(b"zzz-anything") == 2
    # invalid rules are rejected at construction
    with pytest.raises(ValueError, match="empty range"):
        KeyRouter(4, overrides=[("b", "a", 0)])
    with pytest.raises(ValueError, match="out of range"):
        KeyRouter(4, overrides=[("a", "b", 7)])


def test_router_golden_mapping_stable_across_restarts():
    """The golden file pins the exact mapping a previous process
    computed — a rebuilt router (fresh process, fresh ring) must agree
    key for key, and its serialized form must checksum-match."""
    with open(GOLDEN) as f:
        doc = json.load(f)
    router = KeyRouter.from_dict(doc["router"])
    rebuilt = KeyRouter(doc["router"]["n_groups"],
                        vnodes=doc["router"]["vnodes"],
                        overrides=[RangeRule.from_dict(o)
                                   for o in doc["router"]["overrides"]])
    assert (router.to_dict()["ring_checksum"]
            == doc["router"]["ring_checksum"])
    for key, want in doc["mapping"].items():
        assert router.group_of(key) == want, key
        assert rebuilt.group_of(key) == want, key


def test_router_serialization_roundtrip_and_tamper_guard():
    r = KeyRouter(8, overrides=[("a", "b", 4)])
    d = r.to_dict()
    r2 = KeyRouter.from_dict(d)
    for i in range(100):
        assert r.group_of(b"rt%d" % i) == r2.group_of(b"rt%d" % i)
    bad = dict(d, ring_checksum=d["ring_checksum"] ^ 1)
    with pytest.raises(ValueError, match="checksum mismatch"):
        KeyRouter.from_dict(bad)
    with pytest.raises(ValueError, match="unknown router"):
        KeyRouter.from_dict(dict(d, hash="md5"))


def test_ring_hash_is_pure_bytes_arithmetic():
    # restart/process-independence reduces to this: the hash is a pure
    # function of the bytes with pinned constants
    assert ring_hash(b"") == ring_hash(b"")
    assert canon_key("k") == b"k"
    assert ring_hash(b"group:0:vnode:0") != ring_hash(b"group:1:vnode:0")


# ---------------------------------------------------------------------------
# G=1 ≡ SimCluster (bit-identical on a recorded workload)
# ---------------------------------------------------------------------------

def _recorded_workload():
    """(events, timeouts) per step: elections, traffic bursts, a
    partition with failover, heal, post-heal traffic."""
    steps = []
    steps.append((["tmo0"], []))
    for t in range(1, 30):
        ev = []
        tmo = []
        if t in (3, 4, 7, 12, 20):
            ev += [("sub", 0, b"p%d-%d" % (t, i)) for i in range(5)]
        if t == 9:
            ev.append(("part", [[0], [1, 2]]))
            tmo = [1]
        if t == 15:
            ev.append(("heal",))
        if t in (16, 21):
            ev += [("sub", 1, b"q%d-%d" % (t, i)) for i in range(3)]
        steps.append((ev, tmo))
    return steps


def test_g1_bit_identical_to_simcluster():
    sim = SimCluster(CFG, 3)
    sh = ShardedCluster(CFG, 3, 1)
    keys = ("term", "role", "leader_id", "voted_term", "voted_for",
            "head", "apply", "commit", "end", "hb_seen",
            "became_leader", "acked", "accepted", "peer_acked",
            "leadership_verified", "rebase_delta")
    for ev, tmo in _recorded_workload():
        if ev == ["tmo0"]:
            ev, tmo = [], [0]
        for e in ev:
            if e[0] == "sub":
                sim.submit(e[1], e[2])
                sh.submit(0, e[1], e[2])
            elif e[0] == "part":
                sim.partition(e[1])
                sh.partition(0, e[1])
            elif e[0] == "heal":
                sim.heal()
                sh.heal()
        a = sim.step(timeouts=tmo)
        b = sh.step(timeouts={0: tmo} if tmo else ())
        for k in keys:
            assert np.array_equal(a[k], np.asarray(b[k][0])), k
    assert sim.replayed == sh.replayed[0]
    assert (sim.applied == sh.applied[0]).all()
    assert sim.leader() == sh.leader(0)


# ---------------------------------------------------------------------------
# compile-cache dedup: one program for a homogeneous cluster
# ---------------------------------------------------------------------------

# the host bookkeeping the engines share (ISSUE 52): each name is ONE
# function, ClusterEngine's, inherited by both front ends
ONE_BODY = (
    "begin_step", "begin_burst", "finish", "drain", "step", "step_burst",
    "prewarm", "reserved_appends", "_tiers", "_step_bufs", "_burst_bufs",
    "_scan_slots", "_effective_mask", "_replay_committed", "_maybe_rebase",
    "_apply_rebase", "_rebase_stalled_step", "_ingest_audit",
    "_record_flight", "_stamp_appends", "_span_recorder", "_observe_spans",
    "_program", "_dispatch", "_apply_window")
# where the engines truly differ: hooks that BOTH front ends define
HOOKS = ("_norm_timeouts", "_link_models", "_span_rep", "_count_appends",
         "_observe")


@pytest.mark.parametrize("name", ONE_BODY)
def test_the_engines_share_one_body(name):
    """A perf PR that forks a method in one engine fails here, not on
    the ledger two PRs later."""
    from rdma_paxos_tpu.runtime.sim import ClusterEngine
    fn = getattr(ClusterEngine, name)
    assert getattr(SimCluster, name) is fn, name
    assert getattr(ShardedCluster, name) is fn, name


def test_the_front_ends_define_the_hooks_and_nothing_tells_them_apart():
    import inspect

    from rdma_paxos_tpu.runtime.sim import ClusterEngine
    for cls in (SimCluster, ShardedCluster):
        assert cls.__mro__[1] is ClusterEngine
        for name in HOOKS + ("_PROGRAMS", "__init__"):
            assert name in vars(cls), (cls.__name__, name)
    assert not hasattr(SimCluster(CFG, 3), "G")
    body = inspect.getsource(ClusterEngine)
    for probe in ("isinstance(self", "hasattr(self", "SimCluster)",
                  "ShardedCluster)", "self.G"):
        assert probe not in body, probe


def test_the_stamp_loop_does_nothing_but_stamp():
    """The one per-OPERATION loop of the post-readback rules calls
    ``stamp_append``, positionally, and nothing else: PR 52's first
    build worked a cell's labels out once an entry there, and the
    chip's host read 0.56 us an entry more (PERF.md sec. 6)."""
    import ast
    import inspect
    import textwrap

    from rdma_paxos_tpu.runtime.sim import ClusterEngine
    tree = ast.parse(textwrap.dedent(
        inspect.getsource(ClusterEngine._stamp_appends)))
    loops = [n for n in ast.walk(tree) if isinstance(n, ast.For)]
    assert len(loops) == 1
    calls = [n for stmt in loops[0].body for n in ast.walk(stmt)
             if isinstance(n, ast.Call)]
    assert [ast.unparse(c.func) for c in calls] == ["spans.stamp_append"]
    assert not calls[0].keywords


def test_single_compile_for_homogeneous_g4():
    """G groups sharing one LogConfig share ONE compiled step: the
    whole G=4 workload — elections in every group plus committed
    traffic — runs through exactly one program, and the shared cache
    gains exactly one group-step entry."""
    cfg = LogConfig(n_slots=64, slot_bytes=64, window_slots=16,
                    batch_slots=8)
    before = set(STEP_CACHE)
    sc = ShardedCluster(cfg, 3, 4, stable_fast_path=False)
    sc.place_leaders()
    for g in range(4):
        for i in range(6):
            sc.submit(g, sc.leader(g), b"v%d" % i)
    for _ in range(3):
        sc.step()
    assert all(sc.last["commit"][g].max() >= 6 for g in range(4))
    assert len(sc.programs_used) == 1, sc.programs_used
    added = set(STEP_CACHE) - before
    group_steps = [k for k in added if "group" in k]
    assert len(group_steps) == 1, group_steps
    # a second homogeneous cluster — even a DIFFERENT group count —
    # adds no cache entries: the group-step callable is batch-size-
    # polymorphic, so the cache cannot proliferate per G
    now = set(STEP_CACHE)
    sc2 = ShardedCluster(cfg, 3, 8, stable_fast_path=False)
    sc2.place_leaders()
    sc2.step()
    assert set(STEP_CACHE) == now


def test_prewarm_tiers_shared_across_groups_and_clusters():
    cfg = LogConfig(n_slots=64, slot_bytes=64, window_slots=16,
                    batch_slots=8)
    sc = ShardedCluster(cfg, 3, 2)
    sc.prewarm(tiers=(2,))
    warmed = set(STEP_CACHE)
    # same-shape cluster: everything already compiled
    sc2 = ShardedCluster(cfg, 3, 2)
    sc2.prewarm(tiers=(2,))
    assert set(STEP_CACHE) == warmed
    # different group count: SAME cache entries (shared tiers)
    sc3 = ShardedCluster(cfg, 3, 4)
    sc3.prewarm(tiers=(2,))
    assert set(STEP_CACHE) == warmed


def test_step_burst_commits_backlog_in_one_dispatch():
    sc = ShardedCluster(CFG, 3, 2)
    sc.place_leaders()
    for g in range(2):
        for i in range(40):                 # > 2 batches per group
            sc.submit(g, sc.leader(g), b"b%d-%d" % (g, i))
    d0 = sc.dispatches
    res = sc.step_burst()
    assert sc.dispatches == d0 + 1          # K fused steps, ONE dispatch
    for g in range(2):
        assert res["commit"][g].max() >= 40
        got = [p for (_t, _c, _r, p) in sc.replayed[g][0]]
        assert got == [b"b%d-%d" % (g, i) for i in range(40)]


# ---------------------------------------------------------------------------
# chaos smoke: single-group leader crash is contained
# ---------------------------------------------------------------------------

def test_fault_isolation_one_group_leader_crash():
    """Shard nemesis (chaos-subsystem primitives, I1–I5 per group):
    crash group 0's leader mid-run — the other three groups' commit
    frontiers must keep STRICTLY advancing through the outage, and the
    victim group must recover under a new leader."""
    v = ShardNemesisRunner(n_replicas=3, n_groups=4, seed=0,
                           steps=40, crash_step=15).run()
    assert v["ok"], v
    assert not v["invariant_violations"]
    f = v["frontiers"]
    for g in range(1, 4):
        assert f["at_heal"][g] > f["at_crash"][g], (g, f)
    assert v["target_recovered"]
    assert v["new_leader"] != v["crashed_leader"]
    # determinism: same seed, same verdict (chaos contract)
    v2 = ShardNemesisRunner(n_replicas=3, n_groups=4, seed=0,
                            steps=40, crash_step=15).run()
    assert v2 == v


def test_partition_is_per_group():
    sc = ShardedCluster(CFG, 3, 2)
    sc.place_leaders()
    sc.partition(0, [[0], [1, 2]])
    assert not sc.peer_mask[0].all()
    assert sc.peer_mask[1].all()            # group 1 untouched
    sc.heal(0)
    assert sc.peer_mask.all()


# ---------------------------------------------------------------------------
# sharded KVS: routing, per-group sessions, failover dedup
# ---------------------------------------------------------------------------

def test_sharded_kvs_routes_and_reads():
    sc = ShardedCluster(CFG, 3, 4)
    sc.place_leaders()
    kv = ShardedKVS(sc, cap=256)
    data = {b"city%d" % i: b"v%d" % i for i in range(24)}
    for k, v in data.items():
        kv.put(k, v)
    for _ in range(3):
        sc.step()
    groups_hit = set()
    for k, v in data.items():
        assert kv.get(k, linearizable=True) == v
        groups_hit.add(kv.group_of(k))
    assert len(groups_hit) > 1              # keys actually spread
    kv.remove(next(iter(data)))
    sc.step()
    sc.step()
    assert kv.get(next(iter(data))) is None


def test_sharded_session_per_group_seqnos_and_dedup():
    sc = ShardedCluster(CFG, 3, 4)
    sc.place_leaders()
    kv = ShardedKVS(sc, cap=256)
    sess = kv.session(7)
    placed = {}
    for i in range(12):
        k = b"s%d" % i
        g, rid = sess.put(k, b"val%d" % i)
        placed.setdefault(g, []).append(rid)
    # per-group dedup sequence numbers: each group's stream is 1..n
    for g, rids in placed.items():
        assert rids == list(range(1, len(rids) + 1)), (g, rids)
    for _ in range(3):
        sc.step()
    # a network-duplicated retransmit applies exactly once
    k0 = b"s0"
    g0 = kv.group_of(k0)
    sess.retransmit_put(k0, b"val0", req_id=placed[g0][0]
                        if placed[g0] else 1)
    sc.step()
    sc.step()
    lead = sc.leader_hint(g0)
    kv.groups[g0]._fold(lead)
    assert kv.groups[g0].deduped[lead] >= 1
    assert kv.get(k0, linearizable=True) == b"val0"


def test_direct_puts_share_the_session_conn_namespace():
    """A direct stamped ShardedKVS.put and a ShardedSession with the
    same external client id hit the SAME per-group dedup stream — a
    direct put can never alias a DIFFERENT session's high-water mark
    (the two submission paths use one conn_for mapping)."""
    sc = ShardedCluster(CFG, 3, 4)
    sc.place_leaders()
    kv = ShardedKVS(sc, cap=256)
    sess = kv.session(2)
    k = b"alias-probe"
    g = kv.group_of(k)
    assert kv.conn_for(2, g) == sess.conn_for(g)
    # client 5's raw external id can no longer collide with client 2's
    # namespaced conn in any group (injective mapping both paths)
    assert kv.conn_for(5, g) != sess.conn_for(g) or 5 * 4 + g == 2 * 4 + g
    _, rid = sess.put(k, b"v1")
    for _ in range(3):
        sc.step()
    # a direct put as the SAME client with the same req_id is deduped
    kv.put(k, b"v1", client_id=2, req_id=rid)
    sc.step()
    sc.step()
    lead = sc.leader_hint(g)
    kv.groups[g]._fold(lead)
    assert kv.groups[g].deduped[lead] >= 1
    assert kv.get(k, linearizable=True) == b"v1"
    # unstamped puts stay dedup-exempt (conn 0 is preserved)
    assert kv.conn_for(0, g) == 0


def test_sharded_session_failover_in_one_group_only():
    sc = ShardedCluster(CFG, 3, 4)
    sc.place_leaders()
    kv = ShardedKVS(sc, cap=256)
    sess = kv.session(3)
    # seed every group with one committed write
    seeds = {}
    for i in range(40):
        k = b"f%d" % i
        g = kv.group_of(k)
        if g not in seeds:
            seeds[g] = k
            sess.put(k, b"seed")
        if len(seeds) == 4:
            break
    for _ in range(3):
        sc.step()
    # crash group g0's leader; an in-flight put must survive via
    # retransmit to the new leader, deduped exactly-once
    g0 = kv.group_of(b"hotkey")
    old = sc.leader(g0)
    _, rid = sess.put(b"hotkey", b"v1")
    others = [r for r in range(3) if r != old]
    sc.partition(g0, [[old], others])
    sc.step(timeouts={g0: [others[0]]})
    sc.step()
    assert sc.leader_hint(g0) == others[0]
    sess.retransmit_put(b"hotkey", b"v1", rid)
    for _ in range(3):
        sc.step()
    assert kv.get(b"hotkey", linearizable=True) == b"v1"
    # every OTHER group kept its leader and its data
    for g, k in seeds.items():
        if g == g0:
            continue
        assert sc.last["role"][g].tolist().count(int(Role.LEADER)) == 1
        assert kv.get(k, linearizable=True) == b"seed"


# ---------------------------------------------------------------------------
# observability: per-group metrics, span keys, health router
# ---------------------------------------------------------------------------

def test_per_group_metric_series():
    sc = ShardedCluster(CFG, 3, 2)
    sc.obs = Observability()
    sc.place_leaders()
    for g in range(2):
        sc.submit(g, sc.leader(g), b"m")
    sc.step()
    sc.step()
    snap = sc.obs.metrics.snapshot()
    for g in range(2):
        assert f"shard_commit{{group={g}}}" in snap["gauges"]
        assert f"shard_term{{group={g}}}" in snap["gauges"]
        assert f"shard_leader{{group={g}}}" in snap["gauges"]
        assert (snap["counters"]
                [f"shard_committed_entries_total{{group={g}}}"] >= 1)


def test_span_correlation_keyed_by_group_term_index():
    sc = ShardedCluster(CFG, 3, 2)
    obs = Observability()
    obs.spans.set_sample_every(1)
    sc.obs = obs
    sc.place_leaders()
    kv = ShardedKVS(sc, cap=256)
    sess = kv.session(1)
    # one write per group (find a key for each)
    done = set()
    i = 0
    while len(done) < 2:
        k = b"sp%d" % i
        g = kv.group_of(k)
        if g not in done:
            sess.put(k, b"x")
            done.add(g)
        i += 1
    for _ in range(3):
        sc.step()
    dump = obs.spans.dump()
    stamped = [s for s in dump["spans"] if s.get("term") is not None]
    assert stamped, dump
    # every stamped span carries its group, and the (group, term,
    # index) key resolves while same (term, index) in the OTHER group
    # does not collide
    groups_seen = {s["group"] for s in stamped}
    assert groups_seen <= {0, 1} and groups_seen
    for s in stamped:
        key = obs.spans.key_for(s["term"], s["index"], group=s["group"])
        other = obs.spans.key_for(s["term"], s["index"],
                                  group=1 - s["group"])
        if s["status"] == "open":
            assert key == (s["conn"], s["req"])
            assert other != key
        # ALL of a span's replica ids live in ONE namespace (g*R + r):
        # the session's submit origin must match the append leader's
        # namespaced id, and every event replica must belong to the
        # span's group's track range
        assert s["origin"] == s["leader"]
        assert s["origin"] // sc.R == s["group"]
        for phase, rep, _ts in s["events"]:
            if rep >= 0:
                assert rep // sc.R == s["group"], (phase, rep, s)


def test_health_document_serializes_router():
    sc = ShardedCluster(CFG, 3, 2)
    sc.place_leaders()
    doc = sc.health()
    assert doc["n_groups"] == 2
    assert len(doc["groups"]) == 2
    for g, snap in enumerate(doc["groups"]):
        assert snap["group"] == g
        assert snap["leader"] == sc.leader_hint(g)
        assert len(snap["commit"]) == 3
        assert "anchor" in snap and "ts_monotonic" in snap
    # the routing table rides the health doc and reconstructs exactly
    r2 = KeyRouter.from_dict(doc["router"])
    for i in range(50):
        assert r2.group_of(b"h%d" % i) == sc.router.group_of(b"h%d" % i)
    # the whole document is JSON-serializable (operator contract)
    json.dumps(doc)
