"""Causal command tracing (rdma_paxos_tpu.obs.spans): span lifecycle,
cross-replica correlation, step-phase attribution, Perfetto export —
unit level plus the driver/sim/chaos integration contracts:

* a sampled command's span walks submit/enqueue → append ``(term,
  index)`` → quorum → per-replica commit → per-replica apply → ack,
  and retires bounded;
* orphaned spans on leader failover are closed with a ``failover``
  status, never leaked;
* the Chrome trace-event export validates against the trace-event
  schema and matches a golden file byte-for-byte on a scripted clock;
* every obs dump (trace ring, health snapshot, span dump) carries the
  SAME process ``(monotonic, wall)`` anchor pair;
* instrumentation is host-side only: no ``obs`` call site is reachable
  from the jitted modules, and compiled-step cache keys are unchanged
  with tracing at 100% and fencing on;
* chaos reproducer artifacts embed the span dump;
* ``benchmarks/reporting.emit`` produces the standardized BENCH line +
  registry snapshot.
"""

import collections
import json
import os
import threading
import time

import pytest

from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.obs import Observability, clock as obs_clock
from rdma_paxos_tpu.obs import spans as spans_mod
from rdma_paxos_tpu.obs.health import make_snapshot
from rdma_paxos_tpu.obs.metrics import MetricsRegistry
from rdma_paxos_tpu.obs.spans import (
    SpanRecorder, StepPhaseProfiler, breakdown, format_breakdown,
    to_chrome_trace)
from rdma_paxos_tpu.obs.trace import TraceRing
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from rdma_paxos_tpu.runtime.sharded_driver import ShardedClusterDriver
from rdma_paxos_tpu.runtime.sim import SimCluster

CFG = LogConfig(n_slots=64, slot_bytes=32, window_slots=16, batch_slots=8)
TO = TimeoutConfig(elec_timeout_low=1e9, elec_timeout_high=2e9)  # manual

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "spans_chrome_trace.json")


def _scripted_clock(step_s: float = 0.001, start: float = 0.0):
    """Deterministic monotonic clock: start+0.001, start+0.002, ..."""
    t = [start]

    def clock():
        t[0] += step_s
        return round(t[0], 6)
    return clock


def _scripted_recorder():
    """A recorder driven through one full span + one failover span on
    the scripted clock — the golden-file scenario."""
    rec = SpanRecorder(sample_every=1, clock=_scripted_clock())
    rec.begin(7, 1, 0)                        # enqueue on replica 0
    rec.stamp_append(7, 1, term=3, index=5, leader=0, replicas=(0, 1))
    rec.commit_advance(0, 6)                  # leader commit -> quorum
    rec.apply_advance(0, 6)
    rec.commit_advance(1, 6)
    rec.apply_advance(1, 6)
    rec.ack_release(0, 1)
    rec.begin(7, 2, 0)                        # orphaned at failover
    rec.fail_open(0)
    return rec


# ---------------------------------------------------------------------------
# span recorder lifecycle
# ---------------------------------------------------------------------------

def test_span_lifecycle_full_chain():
    rec = _scripted_recorder()
    c = rec.counts()
    assert c["open"] == 0 and c["done"] == 2     # both retired, bounded
    assert c["sampled"] == {"done": 1, "failover": 1}
    dump = rec.dump()
    done = [s for s in dump["spans"] if s["status"] == "done"][0]
    assert (done["term"], done["index"], done["leader"]) == (3, 5, 0)
    phases = [p for p, _, _ in done["events"]]
    assert phases == ["enqueue", "append", "commit", "quorum",
                      "apply", "commit", "apply", "ack"]
    # commit/apply marks landed on BOTH correlated replicas
    assert sorted(r for p, r, _ in done["events"] if p == "commit") \
        == [0, 1]
    # timestamps are monotone in event order
    ts = [t for _, _, t in done["events"]]
    assert ts == sorted(ts)


def test_failover_spans_closed_never_leaked():
    rec = SpanRecorder(sample_every=1)
    for i in range(5):
        rec.begin(9, i + 1, 2)
    rec.stamp_append(9, 1, term=1, index=0, leader=2, replicas=(2,))
    assert rec.open_count == 5
    assert rec.fail_open(2) == 5
    assert rec.open_count == 0                  # never leaked
    statuses = {s["status"] for s in rec.dump()["spans"]}
    assert statuses == {"failover"}
    # the (term, index) correlation entry is cleaned up too
    assert rec.key_for(1, 0) is None


def test_sampling_rate_limit_and_capacity():
    rec = SpanRecorder(sample_every=4, capacity=3)
    sampled = sum(rec.begin(1, i + 1, 0) for i in range(16))
    # one in four hits the sampler; the 4th sampled hits capacity
    assert sampled == 3
    assert rec.open_count == 3 and rec.dropped == 1
    off = SpanRecorder(sample_every=0)
    assert off.begin(1, 1, 0) is False and not off.enabled
    assert off.open_count == 0


def test_acked_spans_with_dead_replica_do_not_wedge_recorder():
    """A permanently-stopped replica's frontier never advances, so
    acked spans keep pending commit/apply marks: at capacity the
    oldest such span is evicted (the client has its ack; the missing
    marks are the evidence) instead of refusing every future sample."""
    rec = SpanRecorder(sample_every=1, capacity=4)
    for i in range(10):
        req = i + 1
        rec.begin(8, req, 0)
        # replica 1 is dead: only replica 0's frontier ever advances
        rec.stamp_append(8, req, term=1, index=i, leader=0,
                         replicas=(0, 1))
        rec.commit_advance(0, i + 1)
        rec.apply_advance(0, i + 1)
        rec.ack_release(0, req)
    c = rec.counts()
    # tracing never stopped: no sample was refused (the overflow was
    # evicted into the bounded done ring, whose oldest entries age
    # out), the open set stayed bounded, and sampling is still live
    assert c["dropped"] == 0
    assert c["open"] <= 4 and c["done"] == 4
    assert rec.begin(8, 99, 0) is True        # still sampling


def test_retransmit_reuses_span_and_first_append_wins():
    rec = SpanRecorder(sample_every=1)
    rec.begin(5, 1, 0)
    rec.begin(5, 1, 1)                           # retransmit elsewhere
    assert rec.open_count == 1
    rec.stamp_append(5, 1, term=2, index=9, leader=0, replicas=(0,))
    rec.stamp_append(5, 1, term=3, index=12, leader=1, replicas=(1,))
    sp = rec.dump()["spans"][0]
    assert (sp["term"], sp["index"]) == (2, 9)   # first commit wins
    assert sp["retransmits"] == 2
    assert [p for p, _, _ in sp["events"]].count("retransmit") == 2


def test_recorder_thread_safety_smoke():
    rec = SpanRecorder(sample_every=1, capacity=10000)

    def work(base):
        for i in range(300):
            rec.begin(base, i + 1, 0)
            rec.stamp_append(base, i + 1, 1, base * 1000 + i, 0,
                             replicas=(0,))
        rec.ack_release(0, 300)
    ts = [threading.Thread(target=work, args=(b,)) for b in (1, 2, 3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    c = rec.counts()
    assert c["open"] + c["done"] + c["dropped"] >= 900


# ---------------------------------------------------------------------------
# satellite: unified clocks — one (monotonic, wall) anchor pair on
# every dump (trace, health, spans)
# ---------------------------------------------------------------------------

def test_all_dumps_share_one_clock_anchor():
    a = obs_clock.anchor()
    assert set(a) == {"monotonic", "wall"}
    assert obs_clock.anchor() == a               # stable per process
    ring = TraceRing(capacity=4)
    ring.record("tick")
    assert json.loads(ring.dump_json())["anchor"] == a
    snap = make_snapshot(replica=0)
    assert snap["anchor"] == a and "ts_monotonic" in snap and "ts" in snap
    rec = SpanRecorder(sample_every=1)
    assert rec.dump()["anchor"] == a
    obs = Observability()
    assert obs.snapshot()["anchor"] == a
    # projection: monotonic ts maps onto the wall timebase exactly
    assert obs_clock.to_wall(a["monotonic"], a) == pytest.approx(
        a["wall"])


# ---------------------------------------------------------------------------
# Perfetto export: schema validation + golden file
# ---------------------------------------------------------------------------

def _validate_chrome_trace(doc):
    """The Chrome trace-event schema subset Perfetto requires: a
    traceEvents list whose entries carry name/ph/pid/tid, a numeric
    ts (except metadata), 'X' events a numeric dur, instants a scope."""
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert "name" in ev["args"]
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")
    json.dumps(doc)                              # serializable as-is


def test_chrome_trace_golden_file():
    rec = _scripted_recorder()
    dump = rec.dump(anchor={"monotonic": 0.0, "wall": 100.0})
    doc = to_chrome_trace(dump)
    _validate_chrome_trace(doc)
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert doc == golden, (
        "Perfetto export drifted from the golden file — if the change "
        "is intentional, regenerate tests/golden/spans_chrome_trace"
        ".json (see test module docstring)")


def test_chrome_trace_merges_multi_replica_dumps_on_anchor():
    """Two 'processes' with different anchors: the merged timeline
    aligns their events on the shared wall timebase."""
    r0 = SpanRecorder(sample_every=1, clock=_scripted_clock())
    r0.begin(3, 1, 0)
    r0.stamp_append(3, 1, term=1, index=0, leader=0, replicas=(0,))
    r0.commit_advance(0, 1)
    r0.apply_advance(0, 1)
    r0.ack_release(0, 1)
    # host 1's monotonic clock reads 1000s ahead of host 0's, but its
    # anchor says so — the merge must cancel the offset exactly
    r1 = SpanRecorder(sample_every=1,
                      clock=_scripted_clock(start=1000.0))
    r1.begin(3, 1, 1)                 # same (conn, req) seen on host 1
    r1.stamp_append(3, 1, term=1, index=0, leader=0, replicas=(1,))
    r1.commit_advance(1, 1)
    r1.apply_advance(1, 1)
    d0 = r0.dump(anchor={"monotonic": 0.0, "wall": 50.0})
    d1 = r1.dump(anchor={"monotonic": 1000.0, "wall": 50.0})
    doc = to_chrome_trace([d0, d1])
    _validate_chrome_trace(doc)
    pids = {e["pid"] for e in doc["traceEvents"] if e["ph"] == "i"}
    assert pids == {0, 1}             # one track per replica
    # anchor alignment: host 1's marks land near host 0's on the
    # merged timeline (µs apart), not 1000 s away
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] == "i"]
    assert max(ts) - min(ts) < 1e6
    # correlation: both replicas' marks carry the same (term, index)
    args = [e["args"] for e in doc["traceEvents"] if e["ph"] == "i"]
    assert {(a["term"], a["index"]) for a in args} == {(1, 0)}


def test_breakdown_report():
    rec = _scripted_recorder()
    bd = breakdown(rec.dump())
    assert bd["spans"] == {"done": 1, "failover": 1}
    assert set(bd["segments"]) == {"enqueue->append", "append->quorum",
                                   "quorum->apply", "apply->ack"}
    for st in bd["segments"].values():
        assert st["n"] == 1 and st["p50_us"] >= 0
    text = format_breakdown(bd)
    assert "enqueue->append" in text and "p99_us" in text


def test_cli_merge_and_report(tmp_path, capsys):
    rec = _scripted_recorder()
    f1 = tmp_path / "spans0.json"
    f1.write_text(json.dumps(rec.dump(
        anchor={"monotonic": 0.0, "wall": 10.0})))
    f2 = tmp_path / "spans1.json"
    f2.write_text(json.dumps(rec.dump(
        anchor={"monotonic": 5.0, "wall": 10.0})))
    out = tmp_path / "trace.json"
    assert spans_mod.main(["merge", str(f1), str(f2),
                           "-o", str(out)]) == 0
    doc = json.load(open(out))
    _validate_chrome_trace(doc)
    assert doc["otherData"]["dumps"] == 2
    assert spans_mod.main(["report", str(f1)]) == 0
    cap = capsys.readouterr().out
    assert "append->quorum" in cap and "perfetto" in cap


# ---------------------------------------------------------------------------
# step-phase profiler
# ---------------------------------------------------------------------------

def test_phase_profiler_feeds_registry_and_fence_is_separate():
    reg = MetricsRegistry()
    prof = StepPhaseProfiler(metrics=reg, fence=False)
    c = SimCluster(CFG, 3)
    c.profiler = prof
    c.run_until_elected(0)
    c.submit(0, b"x")
    c.step()
    for phase in ("host_encode", "device_dispatch", "quorum_wait",
                  "apply"):
        h = reg.get("step_phase_us", phase=phase, replica=-1)
        assert h["count"] >= 1, phase
    # fencing OFF by default: no device_sync series exists
    assert reg.get("step_phase_us", phase="device_sync",
                   replica=-1) == 0
    assert "device_dispatch" in prof.report()

    # fence on: device-sync time lands in its OWN series
    reg2 = MetricsRegistry()
    c.profiler = StepPhaseProfiler(metrics=reg2, fence=True)
    c.submit(0, b"y")
    c.step()
    assert reg2.get("step_phase_us", phase="device_sync",
                    replica=-1)["count"] >= 1
    assert reg2.get("step_phase_us", phase="device_dispatch",
                    replica=-1)["count"] >= 1


# ---------------------------------------------------------------------------
# driver integration: end-to-end spans through the poll loop
# ---------------------------------------------------------------------------

def _step_until(d, pred, n=200):
    for _ in range(n):
        d.step()
        if pred():
            return True
    return False


def test_driver_end_to_end_spans_and_failover():
    d = ClusterDriver(CFG, 3, timeout_cfg=TO)
    try:
        d.obs.spans.set_sample_every(1)
        d.runtimes[0].timer._deadline = 0.0
        d.step()
        assert d.leader() == 0
        handler = d._make_handler(0)
        conn = (0 << 24) | 1
        ev1 = handler(int(EntryType.CONNECT), conn, b"")
        ev2 = handler(int(EntryType.SEND), conn, b"SET k v\n")
        assert _step_until(d, lambda: ev2.done.is_set())
        assert ev1.status == 0 and ev2.status == 0
        for _ in range(5):
            d.step()                  # follower frontiers catch up
        c = d.obs.spans.counts()
        assert c["done"] == 2 and c["open"] == 0
        dump = d.obs.spans.dump()
        for sp in dump["spans"]:
            assert sp["status"] == "done"
            assert sp["term"] is not None and sp["index"] is not None
            # correlated (term, index) marks across ALL three replicas
            for phase in ("commit", "apply"):
                reps = {r for p, r, _ in sp["events"] if p == phase}
                assert reps == {0, 1, 2}, (phase, sp)
            # the ack fired (followers' marks may trail it in order)
            assert "ack" in [p for p, _, _ in sp["events"]]
        # (term, index) pairs are unique -> cross-replica join key
        tis = [(sp["term"], sp["index"]) for sp in dump["spans"]]
        assert len(set(tis)) == len(tis)
        doc = to_chrome_trace(dump)
        _validate_chrome_trace(doc)
        cp = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["pid"] == spans_mod.CP_PID]
        assert cp                     # critical-path track exists

        # failover: a span left inflight is closed, not leaked
        ev3 = handler(int(EntryType.SEND), conn, b"SET k2 v\n")
        assert ev3 is not None
        with d._lock:
            d._fail_inflight_locked(d.runtimes[0], "test-failover")
        c = d.obs.spans.counts()
        assert c["open"] == 0
        assert c["sampled"].get("failover") == 1
    finally:
        d.stop()


def test_kvs_session_spans_via_sim():
    from rdma_paxos_tpu.models.replicated_kvs import ReplicatedKVS
    # KVS commands are CMD_W*4 bytes — same geometry as
    # tests/test_replicated_kvs.py so compiled steps are shared
    kv_cfg = LogConfig(n_slots=128, slot_bytes=128, window_slots=32,
                       batch_slots=16)
    c = SimCluster(kv_cfg, 3)
    c.obs = Observability()
    c.obs.spans.set_sample_every(1)
    c.run_until_elected(0)
    kv = ReplicatedKVS(c, cap=64)
    sess = kv.session(1)
    rid = sess.put(0, b"k", b"v1")
    for _ in range(4):
        c.step()
    kv._fold(0)
    assert kv.last_req[0].get(1, 0) >= rid
    c.obs.spans.ack_key(1, rid)
    sp = [s for s in c.obs.spans.dump()["spans"]
          if s["req"] == rid and s["conn"] == 1][0]
    phases = [p for p, _, _ in sp["events"]]
    assert phases[0] == "submit" and "append" in phases
    assert sp["status"] == "done"
    assert {r for p, r, _ in sp["events"] if p == "commit"} == {0, 1, 2}


# ---------------------------------------------------------------------------
# satellite: static jit-safety guard — no obs call site reachable from
# the jitted modules, and cache keys unchanged at 100% tracing
# ---------------------------------------------------------------------------

def test_no_obs_reachable_from_jitted_modules():
    """consensus/step.py and ops/* run inside jit/shard_map: no
    metrics/trace/spans call site may exist there — statically, by
    transitive import provenance AND source scan. Enforced by the
    graftlint ``jit-purity`` pass (the deduped ``SCAN_PATTERNS``
    union carries this test's former inline list)."""
    from rdma_paxos_tpu.analysis import assert_jit_purity
    assert_jit_purity()


def test_cache_keys_unchanged_with_full_tracing_and_fence():
    """Compiled-step cache keys are bit-identical with spans at 100%
    sampling AND the profiler fencing enabled — instrumentation stays
    host-side (the fence only blocks on already-compiled outputs)."""
    cfg = LogConfig(n_slots=64, slot_bytes=32, window_slots=16,
                    batch_slots=8)
    bare = SimCluster(cfg, 3)
    bare.run_until_elected(0)
    bare.submit(0, b"x")
    bare.step()
    keys_before = set(SimCluster._STEP_CACHE)

    traced = SimCluster(cfg, 3)
    traced.obs = Observability()
    traced.obs.spans.set_sample_every(1)
    traced.profiler = StepPhaseProfiler(metrics=traced.obs.metrics,
                                        fence=True)
    traced.run_until_elected(0)
    traced.obs.spans.begin(1, 1, 0)     # span birth (the driver's job)
    traced.submit(0, b"y", conn=1, req_id=1)
    traced.step()
    traced.step()
    assert traced.obs.spans.counts()["open"] \
        + traced.obs.spans.counts()["done"] >= 1
    d = ClusterDriver(cfg, 3, timeout_cfg=TO, fence=True)
    d.obs.spans.set_sample_every(1)
    d.cluster.run_until_elected(0)
    d.step()
    d.stop()
    assert set(SimCluster._STEP_CACHE) == keys_before, (
        "causal tracing / fencing changed the compiled-step cache "
        "keys — instrumentation leaked into jitted code")


# ---------------------------------------------------------------------------
# satellite: chaos artifacts carry the span dump
# ---------------------------------------------------------------------------

def test_reproducer_artifact_embeds_span_dump(tmp_path):
    from rdma_paxos_tpu.chaos.artifact import (
        load_reproducer, write_reproducer)
    obs = Observability()
    obs.spans.set_sample_every(1)
    obs.spans.begin(4, 1, 0)
    obs.spans.stamp_append(4, 1, term=1, index=0, leader=0,
                           replicas=(0,))
    path = write_reproducer(str(tmp_path / "repro.json"), seed=3,
                            schedule=[], reason="test", obs=obs)
    doc = load_reproducer(path)
    assert doc["spans"]["spans"], "artifact lost the span dump"
    assert doc["spans"]["anchor"] == obs_clock.anchor()
    sp = doc["spans"]["spans"][0]
    assert (sp["term"], sp["index"]) == (1, 0)


@pytest.mark.chaos
def test_nemesis_runner_records_spans():
    """The nemesis runner traces every command (sample_every=1), so a
    violation artifact would ship the full causal timeline; the
    healthy run here just proves spans flow end to end under chaos."""
    from rdma_paxos_tpu.chaos.runner import NemesisRunner
    runner = NemesisRunner(n_replicas=3, seed=11, steps=30,
                           settle_steps=15, fault_kinds=("drop",))
    verdict = runner.run()
    assert verdict["ok"] is True
    dump = runner.obs.spans.dump()
    assert dump["spans"], "no spans recorded under the nemesis"
    stamped = [s for s in dump["spans"] if s["term"] is not None]
    assert stamped, "no span gained a (term, index) correlation"
    assert any(s["status"] == "done" for s in dump["spans"])


# ---------------------------------------------------------------------------
# satellite: shared bench reporting emitter
# ---------------------------------------------------------------------------

def test_reporting_emit_line_and_snapshot(tmp_path, capsys):
    from benchmarks.reporting import emit
    reg = MetricsRegistry()
    reg.inc("ops_total", 5, replica=0)
    path = str(tmp_path / "bench.jsonl")
    row = emit("test_metric", 42.5, "ops/s",
               detail=dict(replicas=3), registry=reg, json_path=path)
    out = capsys.readouterr().out
    line = next(l for l in out.splitlines() if l.startswith("BENCH:"))
    doc = json.loads(line[len("BENCH:"):])
    assert doc["metric"] == "test_metric" and doc["value"] == 42.5
    assert doc["unit"] == "ops/s" and doc["detail"] == {"replicas": 3}
    assert "metrics" not in doc            # stdout line stays lean
    filed = json.loads(open(path).read().splitlines()[0])
    assert filed["metrics"]["counters"]["ops_total{replica=0}"] == 5
    assert set(filed["anchor"]) == {"monotonic", "wall"}
    assert row["metrics"] == filed["metrics"]


# ---------------------------------------------------------------------------
# the closed account of the dispatch cycle (nested phases, the residual,
# per-operation sums, stalls, device scopes)
# ---------------------------------------------------------------------------

ACCT_CFG = LogConfig(n_slots=128, slot_bytes=64, window_slots=32,
                     batch_slots=8)
# of a cycle on the serial loop; the rest nest in one of these
CYCLE_CHILDREN = ("profiler", "dispatch_gate", "idle_wait",
                  "pipeline_wait", "observe", "admin_pump",
                  "host_encode", "device_dispatch",
                  "device_sync", "quorum_wait", "post_readback", "apply",
                  "finish_tail", "post_step_rules", "apply_replay_ack")
NEW_PHASES = ("cycle", "unattributed", "profiler", "admin_pump",
              "dispatch_gate", "idle_wait", "pipeline_wait",
              "input_transfer", "readback_rest", "post_readback", "replay_fetch",
              "replay_decode", "finish_tail", "store_append",
              "replay_send", "replay_drain", "post_step_rules", "observe",
              "intake_to_ack", "intake_queue_wait")


def _sink_port(answers=False):
    """A TCP server that reads and drops: the 'app' the followers'
    ReplayEngine replays into. With ``answers`` it says ``+OK`` to
    whatever it reads, as an app would."""
    import socket
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(16)

    def drain(c):
        try:
            while c.recv(65536):
                if answers:
                    c.sendall(b"+OK\n")
        except OSError:
            pass

    def accept():
        while True:
            try:
                c, _ = srv.accept()
            except OSError:
                return
            threading.Thread(target=drain, args=(c,), daemon=True).start()
    threading.Thread(target=accept, daemon=True).start()
    return srv


def _closed_loop_sets(d, n_clients, n_sets):
    """``n_clients`` threads, one SET outstanding each, through the
    shim's handler (the sharded driver: client ``c`` through replica
    ``c % R``'s, and its CONNECT is held, not an event); -> events
    acknowledged."""
    sharded = isinstance(d, ShardedClusterDriver)
    done = []

    def client(c):
        r = c % d.R if sharded else 0
        handler = d._make_handler(r)
        conn = (r << 24) | (100 + c)
        evs = [handler(int(EntryType.CONNECT), conn, b"")]
        if sharded:
            assert evs.pop() == 0
        else:
            assert evs[0].done.wait(30)
        # the sharded driver routes by key prefix: client c keeps to a
        # prefix of group c % G, so every group is served
        stem = b"k" if not sharded else next(
            k for k in (b"s%d" % j for j in range(1000))
            if d.router.group_of(k) == c % d.G) + b"-"
        for i in range(n_sets):
            ev = handler(int(EntryType.SEND), conn,
                         b"SET %s%d v\n" % (stem, i))
            assert ev.done.wait(30), (c, i)
            evs.append(ev)
        done.append(len(evs))
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    return sum(done)


def _account_driver(tmp_path, pipeline, bench_wrapper=False):
    """A led ClusterDriver with stores and replay sinks, its fetches
    counted; with ``bench_wrapper`` the benchmark's own span is
    installed over ``cluster._fetch_all`` as a traced run installs it."""
    srv = _sink_port()
    d = ClusterDriver(ACCT_CFG, 3, workdir=str(tmp_path),
                      app_ports=[srv.getsockname()[1]] * 3,
                      timeout_cfg=TO, pipeline=pipeline)
    d.cluster.run_until_elected(0)
    d.step()
    assert d.leader() == 0
    fetches = []
    jitted = d.cluster._fetch_all

    def counted(log, starts):
        fetches.append(1)
        return jitted(log, starts)
    d.cluster._fetch_all = counted
    # reads of the membership view off the device state, counted
    d.member_reads = []
    current = d._mm.current

    def counted_current(r=0):
        d.member_reads.append(r)
        return current(r)
    d._mm.current = counted_current
    dep = None
    if bench_wrapper:
        from perfbench.deployments._driver_common import (
            DriverDeployment, SpanAcc)
        dep = DriverDeployment.__new__(DriverDeployment)
        dep.driver = d
        dep.bench_spans = {"replay_fetch": SpanAcc()}
        dep.enable_tracing()
    else:
        d._phase_prof.enable_events()
    return d, srv, fetches, dep


def _sharded_account_driver(tmp_path, pipeline):
    """``_account_driver``'s sharded twin: three groups on three
    replicas, group g led by replica g, stores and replay 'apps' that
    answer; the same four things are handed back."""
    srv = _sink_port(answers=True)
    d = ShardedClusterDriver(ACCT_CFG, 3, 3, workdir=str(tmp_path),
                             app_ports=[srv.getsockname()[1]] * 3,
                             pipeline=pipeline)
    assert d.cluster.place_leaders("round_robin") == [0, 1, 2]
    d.step()
    assert d.leaders() == [0, 1, 2]
    fetches = []
    jitted = d.cluster._fetch_all

    def counted(log, starts):
        fetches.append(1)
        return jitted(log, starts)
    d.cluster._fetch_all = counted
    d.member_reads = []         # the sharded loop has no membership view
    d._phase_prof.enable_events()
    return d, srv, fetches, None


def _delta(d, base):
    return {p: (a[0] - base[p][0], a[1] - base[p][1])
            for p, a in d._phase_prof.acc.items()}


@pytest.fixture(scope="module", params=["single_group", "sharded"])
def serial_account(request, tmp_path_factory):
    sharded = request.param == "sharded"
    d, srv, fetches, _ = (_sharded_account_driver if sharded
                          else _account_driver)(
        tmp_path_factory.mktemp("acct"), pipeline=0)
    try:
        # compile what the traffic runs BEFORE the loop starts, so that
        # the account below starts and ends on cycle boundaries
        if sharded:
            warm = []
            for r in range(d.R):
                handler = d._make_handler(r)
                for c in (1, 2, 3, 4):
                    conn = (r << 24) | c
                    assert handler(int(EntryType.CONNECT), conn, b"") == 0
                    warm.append(handler(int(EntryType.SEND), conn,
                                        b"SET w%d%d v\n" % (r, c)))
        else:
            handler = d._make_handler(0)
            warm = [handler(int(EntryType.CONNECT), (0 << 24) | c, b"")
                    for c in (1, 2)]
        assert _step_until(d, lambda: all(e.done.is_set() for e in warm))
        base = dict(d._phase_prof.acc)
        n_fetch0 = len(fetches)
        counters0 = d.obs.metrics.snapshot()["counters"]
        arrays0 = counters0["readback_arrays_total"]
        del d.member_reads[:]
        d.run()
        acked = _closed_loop_sets(d, n_clients=3 if sharded else 2,
                                  n_sets=150)
        time.sleep(0.2)             # the loop parks: idle_wait
        d.stop()
        assert d.loop_error is None
        counters = d.obs.metrics.snapshot()["counters"]
        return dict(acc=_delta(d, base), acked=acked, sharded=sharded,
                    fetches=len(fetches) - n_fetch0,
                    events=list(d._phase_prof.events),
                    counters=counters, counters0=counters0,
                    readback_arrays=(counters["readback_arrays_total"]
                                     - arrays0),
                    member_reads=list(d.member_reads),
                    replacement=dict(
                        auto_evict=d.auto_evict,
                        config_phase=d._config_phase,
                        rebuilders=len(d._rebuilders), lost=len(d._lost)))
    finally:
        d.stop()
        srv.close()


def test_cycle_account_closes_on_the_serial_loop(serial_account):
    acc = serial_account["acc"]
    n_cycles, cycle_us = acc["cycle"]
    assert n_cycles >= 150
    for phase in NEW_PHASES:
        assert acc[phase][0] > 0, f"{phase} never recorded"
    # direct children + the residual ARE the cycle (float adds apart)
    parts = sum(acc[p][1] for p in CYCLE_CHILDREN) + acc["unattributed"][1]
    assert abs(parts - cycle_us) < 1e-6 * cycle_us + 1.0
    # the program names all but a sliver of its working time
    working = cycle_us - acc["idle_wait"][1]
    assert acc["unattributed"][1] < 0.05 * working, (
        acc["unattributed"], working)
    # totals are inclusive: what nests never exceeds what contains it
    # (input_transfer: host_encode on the step path, device_dispatch on
    # the burst path)
    assert acc["input_transfer"][1] <= (acc["host_encode"][1]
                                        + acc["device_dispatch"][1])
    assert acc["readback_rest"][1] <= acc["quorum_wait"][1]
    assert (acc["replay_fetch"][1] + acc["replay_decode"][1]
            <= acc["apply"][1])
    assert (acc["store_append"][1] + acc["replay_send"][1]
            + acc["replay_drain"][1] + acc["ack_release"][1]
            <= acc["apply_replay_ack"][1])
    # a container over everything would name every device-idle gap
    names = {e[0] for e in serial_account["events"]}
    assert names and not names & {"cycle", "pipeline_wait",
                                  "unattributed", "profiler",
                                  "intake_to_ack", "intake_queue_wait"}
    assert serial_account["counters"]["replay_applies_total"] > 0


# what ISSUE 38 asked of the sharded loop, beyond NEW_PHASES
SHARDED_COUNTERS = ("replay_applies_total", "replay_followers_total",
                    "replay_reply_bytes_total", "intake_fragments_total",
                    "intake_payload_bytes_total", "readback_arrays_total",
                    "group_appends_total")


def test_sharded_dispatch_records_every_phase_and_counter(serial_account):
    """Every phase and counter the benchmark's per-layer metrics read
    is recorded by the sharded loop too, by dispatches that append in
    every group and replay to every replica's app."""
    if not serial_account["sharded"]:
        pytest.skip("the sharded driver's account")
    acc = serial_account["acc"]
    counters = {k: v - serial_account["counters0"].get(k, 0)
                for k, v in serial_account["counters"].items()}
    for phase in NEW_PHASES + ("ack_release", "apply", "host_encode",
                               "device_dispatch", "quorum_wait"):
        assert acc[phase][0] > 0, f"{phase} never recorded"
    for name in SHARDED_COUNTERS:
        assert counters[name] > 0, name
    assert "cfg_rescans_total" in counters
    n = acc["device_dispatch"][0]
    # at most G groups append in a dispatch, and every group was served
    assert n <= counters["group_appends_total"] <= 3 * n
    acks = [counters["group_acks_total{group=%d}" % g] for g in range(3)]
    assert all(acks) and sum(acks) >= serial_account["acked"]
    # a replica follows two groups: ONE list of its operations a dispatch
    assert counters["replay_followers_total"] <= 3 * n


def test_one_readback_array_a_dispatch(serial_account):
    """The default programs hand the host ONE array a dispatch: the
    counter the benchmark's ``readback_arrays_per_dispatch`` reads rises
    by exactly 1 a dispatch, and ``readback_rest`` (the reads after the
    first: none) is still recorded every dispatch, at next to nothing,
    so ``readback_rest_us`` reads about 0 and not ``null``."""
    acc = serial_account["acc"]
    n = acc["device_dispatch"][0]
    assert n >= 150 and acc["quorum_wait"][0] == n
    assert serial_account["readback_arrays"] == n
    assert acc["readback_rest"][0] == n
    assert acc["readback_rest"][1] < 0.02 * acc["quorum_wait"][1]
    assert acc["readback_rest"][1] < 50.0 * n        # us


def test_steady_serving_reads_no_membership_state(serial_account):
    """Over 150 loaded dispatches the failure detector took the
    membership view from each step's ``res``: not one
    ``MembershipManager.current`` (four index programs and four
    blocking reads of the device state) on a serving cycle."""
    assert serial_account["acc"]["post_step_rules"][0] >= 150
    assert serial_account["member_reads"] == []


def test_intake_sums_are_one_sample_an_operation(serial_account):
    acc = serial_account["acc"]
    assert acc["intake_to_ack"][0] == serial_account["acked"]
    assert acc["intake_queue_wait"][0] == serial_account["acked"]
    assert 0 < acc["intake_queue_wait"][1] <= acc["intake_to_ack"][1]


@pytest.mark.parametrize("bench_wrapper", [False, True],
                         ids=["bare", "benchmark_span_installed"])
def test_replay_fetch_phase_counts_fetch_dispatches(tmp_path,
                                                    bench_wrapper):
    d, srv, fetches, dep = _account_driver(tmp_path, pipeline=0,
                                           bench_wrapper=bench_wrapper)
    try:
        base = dict(d._phase_prof.acc)
        n0 = len(fetches)
        d.run()
        _closed_loop_sets(d, n_clients=1, n_sets=40)
        d.stop()
        assert d.loop_error is None
        acc = _delta(d, base)
        assert acc["replay_fetch"][0] == len(fetches) - n0 > 0
        if dep is not None:
            # the benchmark's span brackets the same fetches from
            # outside (it ends inside the last array's conversion)
            span = dep.bench_spans["replay_fetch"]
            assert span.count == len(fetches) - n0
            assert span.total_us <= acc["replay_fetch"][1] * 1.05 + 50
    finally:
        d.stop()
        srv.close()


def test_benchmark_span_installed_still_serves_every_fetch_width(tmp_path):
    """With the benchmark's ``(log, starts)`` wrapper over
    ``cluster._fetch_all`` (``enable_tracing``) the fetch still runs at
    the smallest width that holds the need: the width rides on the
    engine's ``ReplayFetch``, which the wrapper does not see, and
    ``fetch_rows_total`` counts it once a fetch, ``replay_fetch`` and
    the benchmark's span once each."""
    d, srv, fetches, dep = _account_driver(tmp_path, pipeline=0,
                                           bench_wrapper=True)
    try:
        rf = d.cluster._replay_fetch
        assert rf.widths == (4, 16, 64)
        assert d.cluster._fetch_all is not rf       # the wrapper is on
        handler = d._make_handler(0)
        conn = (0 << 24) | 9
        ev = handler(int(EntryType.CONNECT), conn, b"")
        assert _step_until(d, ev.done.is_set)
        span = dep.bench_spans["replay_fetch"]
        sent = 0
        # follower 2's app stands still for `need` SETs, then applies
        for need, widths in ((1, [4]), (4, [4]), (5, [16]), (16, [16]),
                             (17, [64]), (64, [64]), (70, [64, 16])):
            d.cluster.wedge_apply(2)
            evs = [handler(int(EntryType.SEND), conn,
                           b"SET k%d v\n" % (sent + i))
                   for i in range(need)]
            sent += need
            assert _step_until(d, lambda: all(e.done.is_set()
                                              for e in evs))
            d.step()
            d.step()
            last = d.cluster.last
            assert int(last["commit"][2]) - d.cluster.applied[2] == need
            rows0 = d.obs.metrics.get("fetch_rows_total")
            n0, span0 = len(fetches), span.count
            acc0 = d._phase_prof.acc["replay_fetch"][0]
            d.cluster.unwedge_apply(2)
            d.step()
            assert d.cluster.applied[2] == int(d.cluster.last["commit"][2])
            n = len(fetches) - n0
            assert n == len(widths), (need, n)
            assert span.count - span0 == n
            assert d._phase_prof.acc["replay_fetch"][0] - acc0 == n
            assert (d.obs.metrics.get("fetch_rows_total") - rows0
                    == sum(widths)), need
        sets = [p for (_, _, _, p) in d.cluster.replayed[2]
                if p.startswith(b"SET")]
        assert sets == [b"SET k%d v\n" % i for i in range(sent)]
    finally:
        d.stop()
        srv.close()


# a membership change and a replica's recovery (ISSUE 43): recorded
# only when they run
REPLACEMENT_PHASES = ("config_change", "checkpoint", "recover",
                      "app_rebuild")
REPLACEMENT_COUNTERS = ("recover_bytes_total", "recover_entries_total",
                        "replay_reconnects_total", "evictions_total",
                        "config_changes_total", "checkpoints_total")


def test_a_serving_driver_records_nothing_of_a_replacement(serial_account):
    """A driver built WITHOUT ``auto_evict`` and handed no membership
    change, over 150 loaded dispatches: not one sample of the four
    phases, not one count, not one read of the membership view, no
    rebuild worker: the cells that never ask for a replacement run
    nothing new (the phases and counters are there, at 0, so that a
    reader of deltas finds them)."""
    acc = serial_account["acc"]
    for phase in REPLACEMENT_PHASES:
        assert acc[phase] == (0, 0.0), phase
    before, after = serial_account["counters0"], serial_account["counters"]
    for name in REPLACEMENT_COUNTERS:
        assert after[name] == before[name] == 0, name
    assert serial_account["member_reads"] == []
    assert serial_account["replacement"] == dict(
        auto_evict=False, config_phase=None, rebuilders=0, lost=0)


@pytest.mark.parametrize("pipeline", [0, 2], ids=["serial", "pipelined"])
def test_replacement_phases_nest_in_the_cycle_account(tmp_path, pipeline):
    """A checkpoint, a lost follower's eviction, its recovery with a
    fresh app and its way back in, on a running loop under load:
    ``checkpoint`` and ``recover`` nest in ``admin_pump`` (a drained
    serial iteration), ``config_change`` and ``app_rebuild`` span many
    cycles and are credited whole, one sample each; the cycle's
    account closes as it did."""
    srv = _sink_port(answers=True)
    d = ClusterDriver(ACCT_CFG, 3, workdir=str(tmp_path),
                      app_ports=[srv.getsockname()[1]] * 3,
                      timeout_cfg=TO, pipeline=pipeline, auto_evict=True,
                      fail_threshold=5,
                      app_snapshot=(lambda s: b"", lambda s, blob: None,
                                    lambda s: None))
    try:
        d.cluster.prewarm()
        d.prewarm_recovery()
        d.cluster.run_until_elected(0)
        d.step()
        base = dict(d._phase_prof.acc)
        d.run()
        stop = threading.Event()
        acked = []

        def client():
            handler = d._make_handler(0)
            conn = (0 << 24) | 77
            assert handler(int(EntryType.CONNECT), conn, b"").done.wait(30)
            while not stop.is_set():
                ev = handler(int(EntryType.SEND), conn,
                             b"SET k%d v\n" % len(acked))
                assert ev.done.wait(30) and ev.status == 0
                acked.append(ev)
        t = threading.Thread(target=client, daemon=True)
        t.start()

        def until(cond, what):
            deadline = time.monotonic() + 60
            while not cond():
                assert time.monotonic() < deadline and t.is_alive(), what
                time.sleep(0.005)

        def stands_on(mask):
            m = d.membership()
            return (m is not None and m["mask"] == mask and m["stable"]
                    and not m["changing"])
        until(lambda: len(acked) > 20, "no load")
        d.checkpoint_app(1)
        d.fail_replica(2)
        until(lambda: stands_on(0b011), "never evicted")
        n = len(acked)
        until(lambda: len(acked) > n + 20, "two members do not serve")
        d.recover_replica(2)            # waits for the app's rebuild
        d.request_membership(0b111)
        until(lambda: stands_on(0b111), "never back in")
        n = len(acked)
        until(lambda: len(acked) > n + 20, "three members do not serve")
        stop.set()
        t.join(30)
        assert not t.is_alive()
        d.stop()
        assert d.loop_error is None
        acc = _delta(d, base)
        assert [acc[p][0] for p in REPLACEMENT_PHASES] == [2, 1, 1, 1]
        assert all(acc[p][1] > 0 for p in REPLACEMENT_PHASES)
        parts = (sum(acc[p][1] for p in CYCLE_CHILDREN)
                 + acc["unattributed"][1])
        assert abs(parts - acc["cycle"][1]) < 1e-6 * acc["cycle"][1] + 1.0
        # both ran inside a serial iteration's admin_pump
        assert (acc["checkpoint"][1] + acc["recover"][1]
                <= acc["admin_pump"][1])
        counters = d.obs.metrics.snapshot()["counters"]
        assert counters["evictions_total"] == 1
        assert counters["config_changes_total"] == 2
        assert counters["checkpoints_total{replica=1}"] == 1
        assert counters["replay_reconnects_total"] == 1
        assert counters["recover_entries_total"] > 20
        assert not d.runtimes[2].app_dirty
    finally:
        d.stop()
        srv.close()


def test_phase_stall_leaves_one_trace_event_and_one_count():
    reg, ring = MetricsRegistry(), TraceRing()
    prof = StepPhaseProfiler(metrics=reg, trace=ring,
                             step_index=lambda: 42)
    low = TimeoutConfig().elec_timeout_low
    assert prof.STALL_US == low * 1e6
    prof.start("cycle")
    prof.start("apply")
    prof.start("replay_fetch")
    time.sleep(low + 0.02)
    prof.stop("replay_fetch")
    prof.stop("apply")                  # as long, but its child's stall
    prof.start("idle_wait")
    time.sleep(low + 0.02)              # waiting for work is no stall
    prof.stop("idle_wait")
    prof.start("observe")
    prof.stop("observe")                # short
    prof.stop("cycle")
    prof.start("cycle")                 # long only by its waiting
    prof.start("pipeline_wait")
    time.sleep(low * 0.7)
    prof.stop("pipeline_wait")
    prof.start("observe")
    time.sleep(low * 0.7)
    prof.stop("observe")
    prof.stop("cycle")
    evs = ring.events(kind="phase_stall")
    assert len(evs) == 1
    assert evs[0].fields["phase"] == "replay_fetch"
    assert evs[0].fields["step"] == 42
    assert evs[0].fields["us"] >= low * 1e6
    counters = reg.snapshot()["counters"]
    assert counters["phase_stalls_total"] == 1
    assert counters["phase_stall_us_total{phase=replay_fetch}"] >= low * 1e6
    assert sum(v for k, v in counters.items()
               if k.startswith("phase_stall_us_total")) == \
        counters["phase_stall_us_total{phase=replay_fetch}"]


def test_profiler_survives_an_abandoned_phase_and_reads_zero_rows():
    prof = StepPhaseProfiler()
    # every key exists before any phase ran: a reader's dict(acc) never
    # sees the dict grow, and sums() still hides the dead rows
    assert set(NEW_PHASES) <= set(prof.acc) and prof.sums() == {}
    prof.start("cycle")
    prof.start("apply")
    prof.start("replay_fetch")          # raised before its stop
    prof.stop("apply")                  # pops the abandoned frame too
    prof.start("apply")
    prof.stop("apply")
    prof.stop("cycle")
    assert prof.acc["apply"][0] == 2 and prof.acc["replay_fetch"][0] == 0
    assert prof._stack() == []
    prof.stop("never_started")          # as before: ignored
    prof.credit("intake_to_ack", 300.0, 3)
    assert prof.sums()["intake_to_ack"] == dict(n=3, total_us=300.0,
                                                max_us=100.0)


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["single_group", "sharded"])
def test_pipelined_loop_loses_no_phase_with_two_threads(tmp_path, sharded):
    """tests/test_pipeline.py's set-up: a record longer than one burst
    queued BEFORE the loop starts, so dispatch and readback overlap and
    both threads are in the profiler at once."""
    d, srv, _fetches, _ = (_sharded_account_driver if sharded
                           else _account_driver)(tmp_path, pipeline=2)
    try:
        handler = d._make_handler(0)
        conns = [(0 << 24) | 11, (0 << 24) | 12]
        evs = [handler(int(EntryType.CONNECT), c, b"") for c in conns]
        if sharded:             # held with the connection's first SEND
            assert evs == [0, 0]
            evs = []
        # (the sharded driver pins each connection to its first key's
        # group: as many again, so that ONE group's queue is as long)
        evs += [handler(int(EntryType.SEND), conns[i % 2], b"w%03d" % i)
                for i in range(400 if sharded else 200)]
        base = dict(d._phase_prof.acc)
        arrays0 = d.obs.metrics.snapshot()["counters"][
            "readback_arrays_total"]
        del d.member_reads[:]
        d.run(period=0.001)
        for ev in evs:
            assert ev.done.wait(30)
        time.sleep(0.1)
        d.stop()
        assert d.loop_error is None
        assert d.cluster.max_inflight_dispatches >= 2
        acc = _delta(d, base)
        # one array a dispatch and no membership read, with dispatches
        # in flight too (where the cached view used to stand in)
        assert d.obs.metrics.snapshot()["counters"][
            "readback_arrays_total"] - arrays0 == acc["quorum_wait"][0]
        assert d.member_reads == []
        # every dispatch was encoded, read back, applied and tailed:
        # no start or stop went missing between the two threads
        n = acc["device_dispatch"][0]
        assert n > 0
        for phase in ("host_encode", "quorum_wait", "post_readback",
                      "apply", "finish_tail"):
            assert acc[phase][0] == n, (phase, acc[phase], n)
        assert acc["observe"][0] >= n       # idle parks observe too
        assert acc["intake_to_ack"][0] == len(evs)
        # a cycle an iteration of the dispatch loop AND a cycle a
        # ticket on the readback thread: both threads' accounts close
        assert acc["cycle"][0] > n
        parts = (sum(acc[p][1] for p in CYCLE_CHILDREN)
                 + acc["unattributed"][1])
        assert abs(parts - acc["cycle"][1]) < 1e-6 * acc["cycle"][1] + 1.0
        assert acc["pipeline_wait"][1] > 0
    finally:
        d.stop()
        srv.close()


STEP_SCOPES = ("control_gather", "election", "append", "fanout", "absorb",
               "cfg_rescan", "ack_quorum", "commit_scan", "apply_prune",
               "audit_digest", "telemetry")


@pytest.fixture(scope="module")
def lowered_texts():
    import jax
    import jax.numpy as jnp

    from rdma_paxos_tpu.consensus.step import arg_layout
    c = SimCluster(ACCT_CFG, 3, audit=True, telemetry=True)
    inp = jnp.zeros(arg_layout(ACCT_CFG, 3).shape((3,)), jnp.int32)
    step = c._program("step", elections=True)[0].lower(c.state, inp)
    fetches = {W: fn.lower(c.state.log, jnp.zeros((3,), jnp.int32))
               for W, fn in c._fetch_all.programs.items()}
    assert len(fetches) == 3
    return dict(step=[step.as_text(debug_info=True)],
                fetch=[f.as_text(debug_info=True)
                       for f in fetches.values()])


@pytest.mark.parametrize("scope", STEP_SCOPES + ("replay_fetch",))
def test_lowered_program_carries_each_scope(lowered_texts, scope):
    import re
    # the replay fetch is one program a static width: each carries it
    for text in lowered_texts["fetch" if scope == "replay_fetch"
                              else "step"]:
        # the path a device trace shows:
        # jit(replica_step)/vmap(append)/...
        assert re.search(r"[/(]%s[/)]" % scope, text), scope


# ---------------------------------------------------------------------------
# ISSUE 45: where the two loop threads wait for each other. The host
# lock's contended takes, the program call and the replay fetch's parts
# are phases; a follower app's answer time is a credited sum
# ---------------------------------------------------------------------------

LOCK_WAITS = ("dispatch_lock_wait", "fetch_lock_wait")
ISSUE45_PHASES = LOCK_WAITS + ("program_call", "fetch_enqueue",
                               "fetch_read", "replay_answer_wait")


def _hold(lock, seconds):
    """Another thread takes ``lock`` and keeps it ``seconds``; returns
    once it holds it, with the thread."""
    got = threading.Event()

    def holder():
        with lock:
            got.set()
            time.sleep(seconds)
    t = threading.Thread(target=holder, daemon=True)
    t.start()
    assert got.wait(5)
    return t


@pytest.mark.parametrize("phase", ISSUE45_PHASES)
def test_issue45_phase_is_in_acc_from_the_start(phase):
    prof = StepPhaseProfiler()
    assert prof.acc[phase] == (0, 0.0, 0.0)
    assert phase in StepPhaseProfiler.DETAIL
    # a wait by design is no stall; each of the others is a ring entry
    assert (phase in StepPhaseProfiler.WAITS) == (phase in LOCK_WAITS)
    assert phase not in StepPhaseProfiler.NOT_IN_RING


def test_cycle_account_closes_with_the_new_phases_nested_two_deep():
    prof = StepPhaseProfiler()
    prof.enable_events()
    lock = threading.RLock()
    prof.start("cycle")
    prof.start("device_dispatch")
    prof.start("input_transfer")
    prof.stop("input_transfer")
    holder = _hold(lock, 0.01)
    with spans_mod.held(prof, lock, "dispatch_lock_wait"):
        prof.start("program_call")
        time.sleep(0.002)
        prof.stop("program_call")
    prof.stop("device_dispatch")
    holder.join(5)
    holder = _hold(lock, 0.01)
    prof.start("apply")
    prof.start("replay_fetch")
    with spans_mod.held(prof, lock, "fetch_lock_wait"):
        prof.start("fetch_enqueue")
        prof.stop("fetch_enqueue")
    prof.start("fetch_read")
    time.sleep(0.002)
    prof.stop("fetch_read")
    prof.stop("replay_fetch")
    prof.stop("apply")
    prof.stop("cycle")
    holder.join(5)
    acc = prof.acc
    for phase in ISSUE45_PHASES[:-1]:
        assert acc[phase][0] == 1, phase
    # direct children + profiler + unattributed = cycle, exactly
    parts = sum(acc[p][1] for p in ("device_dispatch", "apply",
                                    "profiler", "unattributed"))
    assert abs(parts - acc["cycle"][1]) < 1e-3
    # inclusive totals: each part inside what holds it
    assert (acc["input_transfer"][1] + acc["dispatch_lock_wait"][1]
            + acc["program_call"][1] <= acc["device_dispatch"][1])
    assert acc["dispatch_lock_wait"][1] >= 5_000
    fetch_parts = sum(acc[p][1] for p in ("fetch_lock_wait",
                                          "fetch_enqueue", "fetch_read"))
    assert fetch_parts <= acc["replay_fetch"][1] <= acc["apply"][1]
    assert acc["replay_fetch"][1] - fetch_parts < 200   # bookkeeping
    assert set(ISSUE45_PHASES[:-1]) <= {e[0] for e in prof.events}


def test_a_long_contended_take_is_a_wait_and_no_stall():
    reg, ring = MetricsRegistry(), TraceRing()
    prof = StepPhaseProfiler(metrics=reg, trace=ring)
    low = TimeoutConfig().elec_timeout_low
    lock = threading.RLock()
    prof.start("cycle")
    prof.start("device_dispatch")
    holder = _hold(lock, low + 0.05)            # 150 ms
    with spans_mod.held(prof, lock, "dispatch_lock_wait"):
        pass
    prof.stop("device_dispatch")
    prof.stop("cycle")
    holder.join(5)
    n, total, _mx = prof.acc["dispatch_lock_wait"]
    assert n == 1 and total >= low * 1e6
    assert prof.acc["device_dispatch"][1] >= low * 1e6
    assert ring.events(kind="phase_stall") == []
    assert reg.snapshot()["counters"]["phase_stalls_total"] == 0


def test_acquire_of_a_free_lock_reads_no_clock(monkeypatch):
    prof = StepPhaseProfiler()
    prof.enable_events()
    reads = []
    real = spans_mod._now_ns

    def counted():
        reads.append(1)
        return real()
    monkeypatch.setattr(spans_mod, "_now_ns", counted)
    lock = threading.RLock()
    prof.acquire(lock, "fetch_lock_wait")
    assert lock._is_owned()
    lock.release()
    with spans_mod.held(prof, lock, "dispatch_lock_wait"):
        assert lock._is_owned()
    with spans_mod.held(None, lock, "dispatch_lock_wait"):   # no profiler
        assert lock._is_owned()
    assert not lock._is_owned()
    assert reads == [] and not prof.events
    assert all(prof.acc[p] == (0, 0.0, 0.0) for p in LOCK_WAITS)
    # and a contended one reads it: a start and a stop
    holder = _hold(lock, 0.01)
    prof.acquire(lock, "fetch_lock_wait")
    lock.release()
    holder.join(5)
    assert len(reads) >= 2 and prof.acc["fetch_lock_wait"][0] == 1


@pytest.mark.parametrize("engine", ["sim", "sharded"])
def test_contended_host_lock_takes_are_named_in_both_engines(engine):
    """Another thread holds ``_host_lock`` 30 ms while ``begin_burst``,
    then ``_replay_committed``, run: each side's wait is one sample of
    its own phase, and the fetch's three parts are the fetch."""
    from rdma_paxos_tpu.shard.cluster import ShardedCluster
    if engine == "sim":
        c = SimCluster(ACCT_CFG, 3)
        c.run_until_elected(0)

        def submit(i):
            c.submit(0, b"SET k%d v" % i)
        wedge = [(r,) for r in range(3)]
    else:
        c = ShardedCluster(ACCT_CFG, 3, 2)
        assert c.place_leaders("round_robin") == [0, 1]

        def submit(i):
            for g in range(2):
                c.submit(g, g, b"SET k%d v" % i)
        wedge = [(g, r) for g in range(2) for r in range(3)]
    for i in range(3):                  # compile the burst and the fetch
        submit(i)
        c.step_burst()
    prof = c.profiler = StepPhaseProfiler()
    submit(10)
    holder = _hold(c._host_lock, 0.03)
    ticket = c.begin_burst()
    holder.join(5)
    n, total, _mx = prof.acc["dispatch_lock_wait"]
    assert n == 1 and total >= 25_000           # its first take met it
    assert prof.acc["program_call"][0] == 1
    assert (prof.acc["program_call"][1]
            <= prof.acc["device_dispatch"][1])
    # the entry commits with every apply wedged, so that the fetch is
    # still to run when the lock is held again
    for w in wedge:
        c.wedge_apply(*w)
    c.finish(ticket)
    for _ in range(3):
        res = c.step_burst()
    assert prof.acc["replay_fetch"][0] == 0
    for w in wedge:
        c.unwedge_apply(*w)
    holder = _hold(c._host_lock, 0.03)
    c._replay_committed(res)
    holder.join(5)
    acc = prof.acc
    assert acc["fetch_lock_wait"][0] == 1
    assert acc["fetch_lock_wait"][1] >= 25_000
    assert acc["fetch_enqueue"][0] == acc["fetch_read"][0] \
        == acc["replay_fetch"][0] >= 1
    parts = sum(acc[p][1] for p in ("fetch_lock_wait", "fetch_enqueue",
                                    "fetch_read"))
    assert parts <= acc["replay_fetch"][1] <= parts / 0.97
    assert acc["dispatch_lock_wait"][0] == 1    # no other take waited
    applied = c.applied
    assert int(applied.min()) == int(res["commit"].min()) > 0
