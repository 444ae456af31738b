"""Five replicas, ``native/toyssdb``, four-entry requests, a ring that
turns over and over: ``apus_ssdb_r5`` at toy size.

Five ``toyssdb`` processes under the interposition shim and a
``ClusterDriver``; a small closed loop of 16-pair ``multi_set``s (1,834
bytes, four 512-byte log entries) to the leader's app, on a ring of 256
slots, so that the ring fills, is pruned and is recycled more than three
times inside the test. Then every app is held to the plain reference
(``perfbench/reference/multiset_dict.py``) and the counters this
deployment added are held to the engine's own state. After that, in
order: a follower loses the third entry of two requests (the check has
to see it); two followers' rows are cut off (requests still commit on
three of five); a third is cut (nothing is acknowledged).
"""

import os
import socket
import subprocess
import threading
import time

import pytest

from perfbench.generators.line_multiset import (
    prefix_stream, request_line, request_pairs)
from perfbench.reference.multiset_dict import MultisetDict
from rdma_paxos_tpu.config import LogConfig, TimeoutConfig
from rdma_paxos_tpu.consensus.log import EntryType
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.runtime.driver import ClusterDriver
from tests.test_replace_follower import (     # build_native: autouse here too
    NATIVE, build_native, free_ports, wait_listening, wait_until)  # noqa: F401

CFG = LogConfig(n_slots=256, slot_bytes=512, window_slots=32,
                batch_slots=16)
# wide: no election is meant
TO = TimeoutConfig(elec_timeout_low=4.0, elec_timeout_high=8.0)
R, CLIENTS, REQUESTS, SEED = 5, 4, 60, 47
MIX = dict(pairs=16, key_format="k%010d", value_format="v%0100d")
CASES = {"serial_psum": dict(pipeline=0, fanout="psum"),
         "serial_gather": dict(pipeline=0, fanout="gather"),
         "pipelined_psum": dict(pipeline=2, fanout="psum"),
         "pipelined_gather": dict(pipeline=2, fanout="gather")}


class Client:
    """One connection, one ``multi_set`` outstanding; what it sent and
    what was acknowledged, as ``[(key, value)]`` a request."""

    def __init__(self, port, cid):
        self.cid, self.j = cid, 0
        self.prefixes = prefix_stream(SEED, cid)
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.file = self.sock.makefile("rb")
        self.acked = []

    def request(self, timeout=60.0):
        """-> the reply (``None``: none within ``timeout``)."""
        pairs = request_pairs(MIX, self.cid, self.j, next(self.prefixes))
        self.j += 1
        line = request_line(pairs)
        assert len(line) == 1834
        self.sock.settimeout(timeout)
        try:
            self.sock.sendall(line)
            reply = self.file.readline().strip()
        except OSError:
            return None
        if reply == b"+OK 16":
            self.acked.append(pairs)
        return reply or None


def run_clients(clients, n):
    failed = []

    def loop(c):
        for _ in range(n):
            reply = c.request()
            if reply != b"+OK 16":
                failed.append((c.cid, reply))
                return
    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not failed, failed


def ask(port, lines):
    """Every line's answer, the lines written a few hundred at a time."""
    out = []
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        f = s.makefile("rb")
        for i in range(0, len(lines), 256):
            part = lines[i:i + 256]
            s.sendall(b"".join(ln + b"\n" for ln in part))
            out += [f.readline().strip() for _ in part]
    return out


def app_view(port, keys):
    return dict(count=int(ask(port, [b"COUNT"])[0]),
                answers=ask(port, [b"GET " + k for k in keys]))


def counters_of(d):
    out = {}
    for key, v in d.obs.metrics.snapshot()["counters"].items():
        name = key.split("{", 1)[0]
        out[name] = out.get(name, 0) + v
    return out


def settled(d):
    """-> (counters, the leader's row of the last readback, the offsets
    rebased away), of one moment: taken again while a heartbeat's
    dispatch finishes in between."""
    while True:
        before = counters_of(d)
        with d.cluster._host_lock:
            last = {k: d.cluster.last[k].copy()
                    for k in ("head", "end", "role")}
            rebased = int(d.cluster.rebased_total)
        if counters_of(d) == before:
            return before, last, rebased


def lose_third_entry(engine):
    """From now on this follower's app gets every ``multi_set`` without
    the bytes of its third log entry; -> the way back."""
    apply, held, sb = engine.apply, {}, CFG.slot_bytes

    def faulty(etype, conn, payload):
        if etype != int(EntryType.SEND):
            return apply(etype, conn, payload)
        lines = (held.pop(conn, b"") + payload).split(b"\n")
        if lines[-1]:
            held[conn] = lines[-1]
        whole = b"".join(ln[:2 * sb] + (ln + b"\n")[3 * sb:]
                         for ln in lines[:-1])
        return apply(etype, conn, whole) if whole else None
    engine.apply = faulty

    def restore():
        del engine.apply
    return restore


@pytest.fixture(scope="module", params=sorted(CASES))
def served(request, tmp_path_factory):
    """The whole drill once a case; -> what the tests look at."""
    workdir = str(tmp_path_factory.mktemp("ssdb"))
    ports = free_ports(R)
    d = ClusterDriver(CFG, R, workdir=workdir, app_ports=ports,
                      timeout_cfg=TO, **CASES[request.param])
    apps = []
    for r, port in enumerate(ports):
        env = dict(os.environ,
                   LD_PRELOAD=os.path.join(NATIVE, "interpose.so"),
                   RP_PROXY_SOCK=os.path.join(workdir, f"proxy{r}.sock"))
        apps.append(subprocess.Popen(
            [os.path.join(NATIVE, "toyssdb"), str(port)], env=env,
            stderr=subprocess.DEVNULL))
    clients = []
    try:
        for p in ports:
            wait_listening(p)
        d.cluster.prewarm()
        d.run(period=0.002)
        wait_until(lambda: d.leader() >= 0, "no leader")
        # whoever leads (every timer ran out under the compile): the
        # clients' replica; a follower to break, two to lose, one more
        lead = d.leader()
        victim, third, *first_two = [r for r in range(R) if r != lead]
        followers = [ports[r] for r in range(R) if r != lead]

        # what the leader was offered and took, dispatch by dispatch,
        # tallied beside the engine's own counters
        tally = dict(offered=0, accepted=0)
        finish = d.cluster.finish

        def tallying(ticket):
            res = finish(ticket)
            if res["role"][lead] == int(Role.LEADER):
                tally["offered"] += len(ticket.taken[lead])
                tally["accepted"] += (int(res["accepted"][lead])
                                      if ticket.taken[lead] else 0)
            return res
        d.cluster.finish = tallying

        clients = [Client(ports[lead], c) for c in range(CLIENTS)]
        run_clients(clients, 1)         # the connections' own entries
        wait_until(lambda: all(int(ask(p, [b"COUNT"])[0]) >= 16 * CLIENTS
                               for p in followers), "an app stays behind")
        c0 = settled(d)[0]
        run_clients(clients, REQUESTS - 1)
        ref = MultisetDict(MIX["pairs"])
        for c in clients:
            ref.feed(c.acked)
        keys = sorted(ref.held)
        # the followers' apply frontier trails the acks (the leader's
        # app is asked nothing yet: its answers are replicated requests)
        wait_until(lambda: all(int(ask(p, [b"COUNT"])[0]) >= len(ref)
                               for p in followers), "an app stays behind")
        c1, last, rebased = settled(d)
        waited_in_vain = sum(rt.replay.order_timeouts for rt in d.runtimes
                             if rt.replay is not None)
        out = dict(case=request.param, lead=lead, ref=ref, keys=keys, c0=c0,
                   c1=c1, held=dict(ref.held), waited_in_vain=waited_in_vain,
                   last=last, rebased=rebased, tally=dict(tally),
                   views=[app_view(p, keys) for p in ports])

        # a follower loses the third entry of two requests
        restore = lose_third_entry(d.runtimes[victim].replay)
        n_before = sum(len(c.acked) for c in clients)
        run_clients(clients[:2], 1)
        lost = [c.acked[-1] for c in clients[:2]]
        for pairs in lost:
            ref.multi_set(pairs)
        wait_until(lambda: all(int(ask(ports[r], [b"COUNT"])[0])
                               >= len(ref) for r in range(R)
                               if r != victim), "an app stays behind")
        time.sleep(0.3)                 # the victim's turn, were it sound
        restore()
        lost_keys = [k for pairs in lost for k, _ in pairs]
        out["lost"] = dict(
            victim=victim, acked_more=sum(len(c.acked) for c in clients)
            - n_before, views=[app_view(p, lost_keys) for p in ports],
            keys=lost_keys, want=len(ref))

        # two followers' machines lost: three of five still commit
        for r in first_two:
            d.fail_replica(r)
        run_clients(clients, 2)
        more = [pairs for c in clients for pairs in c.acked[-2:]]
        for pairs in more:
            ref.multi_set(pairs)
        more_keys = [k for pairs in more for k, _ in pairs]
        wait_until(lambda: all(int(ask(ports[r], [b"GET " + more_keys[-1]])
                                   [0] != b"-") for r in (lead, third)),
                   "a live app stays behind")
        out["degraded"] = dict(
            keys=more_keys, live=(lead, third), cut=first_two,
            views=[app_view(p, more_keys) for p in ports])

        # a third: no majority, nothing is acknowledged
        d.fail_replica(third)
        out["no_quorum_replies"] = [c.request(timeout=1.5)
                                    for c in clients[:2]]
        d.stop()
        assert d.loop_error is None
        return out
    finally:
        d.stop()
        for c in clients:
            c.sock.close()
        for a in apps:
            a.kill()
            a.wait()


def test_every_app_holds_what_the_reference_holds(served):
    ref = MultisetDict(MIX["pairs"])
    ref.held = served["held"]           # as the main load left it
    assert len(served["keys"]) == CLIENTS * REQUESTS * 16
    for r, view in enumerate(served["views"]):
        least, most = ref.count_bounds(0)
        assert least <= view["count"] <= most, r
        assert ref.part_held(view["count"]) == 0, r
        assert view["count"] == len(served["keys"]), r
        assert ref.wrong_values(served["keys"], view["answers"]) == 0, r


def test_a_request_is_four_entries_exactly(served):
    c0, c1 = served["c0"], served["c1"]
    ops = CLIENTS * (REQUESTS - 1)
    assert (c1["intake_fragments_total"]
            - c0["intake_fragments_total"]) == 4 * ops
    assert (c1["intake_payload_bytes_total"]
            - c0["intake_payload_bytes_total"]) == 1834 * ops


def test_the_ring_turned_three_times_and_was_pruned(served):
    c1, last = served["c1"], served["last"]
    end = int(last["end"][served["lead"]]) + served["rebased"]
    head = int(last["head"][served["lead"]]) + served["rebased"]
    assert c1["ring_wraps_total"] >= 3
    assert c1["ring_wraps_total"] == end // CFG.n_slots
    assert end >= 4 * CLIENTS * REQUESTS
    # what the pruner gave back is the leader's head advance, and the
    # ring never held more than it has
    assert c1["pruned_slots_total"] == head > 0
    assert end - head < CFG.n_slots
    assert c1["cfg_rescans_total"] == 0


def test_offered_is_accepted_plus_clamped(served):
    c1, tally = served["c1"], served["tally"]
    assert (c1["append_clamped_total"] + tally["accepted"]
            == tally["offered"])
    # every entry admitted at intake was appended once and only once
    assert tally["accepted"] == c1["intake_fragments_total"]


def test_a_whole_request_is_one_apply_a_follower(served):
    c0, c1 = served["c0"], served["c1"]
    requests = c1["replay_requests_total"] - c0["replay_requests_total"]
    applies = c1["replay_applies_total"] - c0["replay_applies_total"]
    assert requests == (R - 1) * CLIENTS * (REQUESTS - 1)
    # its four entries are neighbours in the log and are joined into
    # one write, unless a commit frontier falls between them
    assert requests <= applies <= 2 * requests
    # an answer a loaded host's app took over 50 ms for is counted by
    # its engine and credited to the registry, none lost
    assert c1["replay_order_timeouts_total"] == served["waited_in_vain"]


def test_a_lost_third_entry_is_seen_by_the_check(served):
    lost, ref = served["lost"], served["ref"]
    assert lost["acked_more"] == 2
    for r, view in enumerate(lost["views"]):
        faults = MultisetDict(16)
        faults.held = {k: ref.held[k] for k in lost["keys"]}
        if r == lost["victim"]:
            # the app refused what was left of each request: whole or
            # not at all, and the count shows it
            assert view["count"] == lost["want"] - 32
            assert view["answers"] == [b"-"] * 32
            assert faults.wrong_values(lost["keys"], view["answers"]) == 32
        else:
            assert view["count"] == lost["want"], r
            assert faults.wrong_values(lost["keys"], view["answers"]) == 0


def test_three_of_five_commit_and_two_of_five_do_not(served):
    deg, ref = served["degraded"], served["ref"]
    assert len(deg["keys"]) == CLIENTS * 2 * 16
    want = [ref.held[k] for k in deg["keys"]]
    for r in deg["live"]:
        assert deg["views"][r]["answers"] == want, r
    for r in deg["cut"]:                # cut off before these were sent
        assert deg["views"][r]["answers"] == [b"-"] * len(want), r
    assert all(reply != b"+OK 16" for reply in served["no_quorum_replies"])


# ---- the newline rule of ReplayEngine, as it stands --------------------
#
# ``ReplayEngine.apply`` takes a write that ends in a newline for the end
# of a request (``self._whole``). This mix never puts a newline at an
# entry's end but the request's last; a block-framed protocol with
# varying widths would. What happens then is written down here (and in
# PERF.md section 7): the log's metadata row has no "more follows" mark.

class BlockApp:
    """Answers ``ok`` after every ``width`` bytes of a connection (or
    never: ``width`` 0), whatever newlines they hold."""

    def __init__(self, width):
        self.width, self.got = width, b""
        self.listener = socket.socket()
        self.listener.bind(("127.0.0.1", 0))
        self.listener.listen(1)
        self.port = self.listener.getsockname()[1]
        self.thread = threading.Thread(target=self.serve, daemon=True)
        self.thread.start()

    def serve(self):
        conn, _ = self.listener.accept()
        answered = 0
        while True:
            data = conn.recv(4096)
            if not data:
                return
            self.got += data
            while self.width and len(self.got) >= (answered + 1) * self.width:
                conn.sendall(b"ok\n")
                answered += 1


def test_a_newline_inside_a_request_costs_a_wait_and_keeps_the_order():
    from rdma_paxos_tpu.proxy.proxy import ReplayEngine
    first, rest = b"0123456789\n", b"abcdefgh\n"    # one request, 20 bytes
    app = BlockApp(len(first) + len(rest))
    engine = ReplayEngine("127.0.0.1", app.port)
    send, n = int(EntryType.SEND), 4
    engine.apply(int(EntryType.CONNECT), 7, b"")
    t0 = time.monotonic()
    for _ in range(n):
        engine.apply(send, 7, first)    # ends in a newline: "whole"
        engine.apply(send, 7, rest)     # waits for an answer that cannot come
    took = time.monotonic() - t0
    engine.apply(int(EntryType.CLOSE), 7, b"")
    app.thread.join(10)
    # every request waited ORDER_WAIT_S once (the answer to the one
    # before arrives in between, so the engine never gives up waiting)
    assert engine.order_timeouts == n
    assert took >= n * engine.ORDER_WAIT_S
    assert engine.take_replayed() == (2 * n, n)
    assert app.got == (first + rest) * n            # order kept


def test_an_app_that_never_answers_is_waited_for_three_times():
    from rdma_paxos_tpu.proxy.proxy import ReplayEngine
    app = BlockApp(0)
    engine = ReplayEngine("127.0.0.1", app.port)
    engine.apply(int(EntryType.CONNECT), 7, b"")
    for i in range(8):
        engine.apply(int(EntryType.SEND), 7, b"line %d\n" % i)
    assert engine.order_timeouts == engine.GIVE_UP_AFTER == 3
    engine.apply(int(EntryType.CLOSE), 7, b"")
    app.thread.join(10)
    assert app.got == b"".join(b"line %d\n" % i for i in range(8))
