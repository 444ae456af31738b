"""A dispatch hands the device ONE argument (PR 51).

``consensus/step.py`` ``arg_layout`` is the one source of offsets: the
engines' staging buffers are views of the packed array by it, and the
programs take the array apart by it in the trace. Here: the layout's
own arithmetic; and the round trip, what ``begin_step`` /
``begin_burst`` pack and put, unpacked in a traced function, against
the six (seven, nine with ``txn``) arrays a dispatch handed over
before, built here by the documented rule and by nothing of the
layout's. The programs are stubbed (the argument is what is under
test), so every tier and width runs without a compile."""

import jax
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import (
    M_CONN, M_LEN, M_REQID, M_TYPE, META_W, EntryType)
from rdma_paxos_tpu.consensus.step import (
    ARG_LANES, ARG_TILE_ROWS, StepInput, arg_layout, arg_layout_of)
from rdma_paxos_tpu.parallel.mesh import axes_spec
from rdma_paxos_tpu.runtime.sim import SimCluster
from rdma_paxos_tpu.shard.cluster import ShardedCluster

CFG = LogConfig(n_slots=1024, slot_bytes=64, window_slots=16, batch_slots=8)
B, SW = CFG.batch_slots, CFG.slot_words
WIDE = LogConfig(n_slots=1024, slot_bytes=512, window_slots=64,
                 batch_slots=64)
SEND = int(EntryType.SEND)
MESH = (1, 3)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 3, reason="a replica mesh needs 3 devices")


# ---------------------------------------------------------------------------
# the layout's own arithmetic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("txn", [False, True])
@pytest.mark.parametrize("R", [3, 5, 7])
@pytest.mark.parametrize("K", [1, 2, 4, 8, 16])
def test_fields_tile_the_row_and_the_width_gives_k_back(K, R, txn):
    # a step of WIDE is wider than a tile, as the cells' is: its pad
    # stays under one; CFG's toy step is not (a tile a step anyway)
    lay = arg_layout(WIDE, R, K, txn)
    pad = lay.rows * ARG_LANES - sum(
        int(np.prod(shape)) for _n, _o, shape in lay.fields)
    assert 0 <= pad < ARG_TILE_ROWS * ARG_LANES
    lay = arg_layout(CFG, R, K, txn)
    names = [n for n, _o, _s in lay.fields]
    assert names == (["data", "meta", "count", "peer_mask", "applied",
                      "qdepth", "timeout"]
                     + ["txn_watch", "txn_term"] * txn)
    at = 0
    for _name, off, shape in lay.fields:    # back to back, no overlap
        assert off == at
        at += int(np.prod(shape))
    assert at == K * B * (SW + META_W) + K + R + 3 + 2 * txn
    assert lay.rows * ARG_LANES >= at and lay.rows % ARG_TILE_ROWS == 0
    assert lay.K == K and lay.shape((2, R)) == (2, R, lay.rows, ARG_LANES)
    if not txn:
        assert arg_layout_of(CFG, R, lay.rows) == lay


def test_a_step_narrower_than_a_tile_still_adds_one():
    """Where a step's words could hide in the pad (a toy geometry: a
    step under a tile's 1,024 words), a step adds a tile all the same:
    the rows still name one K."""
    tiny = LogConfig(n_slots=64, slot_bytes=16, window_slots=4,
                     batch_slots=2)
    rows = [arg_layout(tiny, 3, K).rows for K in range(1, 17)]
    assert rows == sorted(set(rows))
    for K, n in enumerate(rows, 1):
        lay = arg_layout_of(tiny, 3, n)
        assert lay.K == K
        _name, off, shape = lay.fields[-1]
        assert off + 1 <= n * ARG_LANES
    with pytest.raises(AssertionError):    # no K has that many rows
        arg_layout_of(WIDE, 3, arg_layout(WIDE, 3, 2).rows + 1)


@pytest.mark.parametrize("lead", [(3,), (3, 3)], ids=["R", "G_R"])
def test_views_are_views_and_a_window_is_one_block(lead):
    """Every field is a VIEW of the packed array (a write lands in it),
    K leads the batch fields, and a window's rows are one contiguous
    block: ``pack_window`` scatters through ``du8.reshape(-1)``, which
    must not be a copy."""
    lay = arg_layout(CFG, 3, 4)
    packed = np.zeros(lay.shape(lead), np.int32)
    v = lay.views(packed)
    assert v["data"].shape == (4,) + lead + (B, SW)
    assert v["meta"].shape == (4,) + lead + (B, META_W)
    assert v["count"].shape == (4,) + lead
    assert v["peer_mask"].shape == lead + (3,)
    assert v["applied"].shape == v["qdepth"].shape == lead
    for i, (name, arr) in enumerate(v.items(), 1):
        assert np.shares_memory(arr, packed), name
        arr[...] = i
    got = lay.split(packed)
    assert {n: int(a.max()) for n, a in got.items()} == {
        n: i for i, n in enumerate(v, 1)}
    idx = (2,) + tuple(x - 1 for x in lead)
    block = v["data"].view(np.uint8)[idx]
    assert block.flags.c_contiguous
    assert np.shares_memory(block.reshape(-1), packed)


# ---------------------------------------------------------------------------
# the round trip: the engines' packed argument against the arrays of old
# ---------------------------------------------------------------------------

def stubbed(c, sharded):
    """``c`` with every program replaced by a stub that keeps the
    packed argument it was handed; ``last`` as after an idle step."""
    got = []

    def program(state, packed):
        got.append(packed)
        return state, None
    c._program = lambda kind, K=None, elections=None: (program, ("stub",))
    zeros = np.zeros((c.G, c.R) if sharded else (c.R,), np.int64)
    with c._host_lock:
        c.last = dict(end=zeros, head=zeros)
    return got


def legacy_batch(takes, K, sw=SW):
    """``data [K, B, sw]``, ``meta [K, B, META_W]``, ``count [K]`` of
    one replica's take, by the rule the six arrays were packed by:
    entry i is row ``i % B`` of step ``i // B``, its payload the row's
    first bytes, its type, connection, request and length in the
    metadata row."""
    data = np.zeros((K, B, sw), np.int32)
    meta = np.zeros((K, B, META_W), np.int32)
    count = np.zeros((K,), np.int32)
    for i, (t, conn, req, payload) in enumerate(takes):
        k, row = divmod(i, B)
        data[k, row].view(np.uint8)[:len(payload)] = np.frombuffer(
            payload, np.uint8)
        meta[k, row, [M_TYPE, M_CONN, M_REQID, M_LEN]] = (
            t, conn, req, len(payload))
        count[k] += 1
    return data, meta, count


def entries(rng, n, tag):
    return [(SEND, 1 + int(rng.integers(9)), i,
             b"%s-%d-" % (tag, i) + bytes(rng.integers(
                 0, 256, int(rng.integers(0, 40)), np.uint8)))
            for i in range(n)]


def unpacked(lay, packed):
    """The ``StepInput`` of every step, out of the packed argument, in
    a traced function (as the programs take it apart)."""
    @jax.jit
    def steps(p):
        parts = lay.split(p)
        return [lay.step_input(parts, k) for k in range(lay.K)]
    return [jax.tree.map(np.asarray, s) for s in steps(packed)]


def check_sharding(c, packed):
    if c.mesh is None:
        assert isinstance(packed.sharding,
                          jax.sharding.SingleDeviceSharding)
        return
    want = jax.sharding.NamedSharding(c.mesh, axes_spec(c.mesh))
    assert packed.sharding.is_equivalent_to(want, packed.ndim)
    # replica r's words go to chip r whole (of every group)
    shape = packed.shape[:-3] + (1,) + packed.shape[-2:]
    assert [s.data.shape for s in packed.addressable_shards] == [shape] * 3


@pytest.mark.parametrize("R", [3, 5, 7])
@pytest.mark.parametrize("K", SimCluster.K_TIERS)
def test_burst_round_trip(K, R):
    rng = np.random.default_rng(100 * K + R)
    c = SimCluster(CFG, R, fanout="gather")
    got = stubbed(c, sharded=False)
    # even replicas queue K windows' worth, give or take (some of it
    # past the burst's reach: that is its qdepth), odd ones nothing
    queued = [entries(rng, int(rng.integers((K - 1) * B + 1, K * B + 6)),
                      b"r%d" % r) if r % 2 == 0 else []
              for r in range(R)]
    for r in range(R):
        c.submit_many(r, queued[r])
    with c._host_lock:
        c.applied = rng.integers(0, 1000, R).astype(np.int64)
    c.peer_mask = rng.integers(0, 2, (R, R)).astype(np.int32)
    t = c.begin_burst(max_k=K)
    assert t.K == K and len(got) == 1
    lay = arg_layout(CFG, R, K)
    assert got[0].shape == lay.shape((R,))
    check_sharding(c, got[0])
    steps = unpacked(lay, got[0])
    for r in range(R):
        assert t.taken[r] == queued[r][:K * B]
        data, meta, count = legacy_batch(t.taken[r], K)
        for k, s in enumerate(steps):
            assert isinstance(s, StepInput)
            assert np.array_equal(s.batch_data[r], data[k]), (r, k)
            assert np.array_equal(s.batch_meta[r], meta[k]), (r, k)
            assert s.batch_count[r] == count[k]
            assert np.array_equal(s.peer_mask[r], c.peer_mask[r])
            assert s.apply_done[r] == c.applied[r]
            assert s.queue_depth[r] == max(0, len(queued[r]) - K * B)
            assert s.timeout_fired[r] == 0
            assert s.txn_watch is None and s.txn_term is None


@pytest.mark.parametrize("txn", [False, True])
@pytest.mark.parametrize("R", [3, 5, 7])
def test_step_round_trip(R, txn):
    rng = np.random.default_rng(R + 10 * txn)
    cfg = LogConfig(n_slots=1024, slot_bytes=128, window_slots=16,
                    batch_slots=8) if txn else CFG
    c = SimCluster(cfg, R, fanout="gather", txn=txn)
    got = stubbed(c, sharded=False)
    for r in range(R):
        c.submit_many(r, entries(rng, int(rng.integers(0, 2 * B)),
                                 b"s%d" % r))
    with c._host_lock:
        c.applied = rng.integers(0, 1000, R).astype(np.int64)
    c.peer_mask = rng.integers(0, 2, (R, R)).astype(np.int32)
    if txn:
        c.rebased_total = 64
        c.set_txn_watch(64 + 17, 5)
    fired = [1, R - 1]
    t = c.begin_step(fired)
    lay = arg_layout(cfg, R, 1, txn)
    assert got[0].shape == lay.shape((R,))
    (s,) = unpacked(lay, got[0])
    for r in range(R):
        take = t.taken[r]
        assert len(take) <= B
        data, meta, count = legacy_batch(take, 1, cfg.slot_words)
        assert np.array_equal(s.batch_data[r], data[0])
        assert np.array_equal(s.batch_meta[r], meta[0])
        assert s.batch_count[r] == count[0] == len(take)
        assert s.timeout_fired[r] == (r in fired)
        assert np.array_equal(s.peer_mask[r], c.peer_mask[r])
        assert s.apply_done[r] == c.applied[r]
        assert s.queue_depth[r] == len(c.pending[r])
        if txn:
            assert s.txn_watch[r] == 17 and s.txn_term[r] == 5
        else:
            assert s.txn_watch is None and s.txn_term is None


@needs_mesh
@pytest.mark.parametrize("K", [2, 4])
def test_spmd_burst_round_trip(K):
    """``mode="spmd"``: the one array is put with the programs' own
    sharding, a replica's row to its chip."""
    rng = np.random.default_rng(K)
    c = SimCluster(CFG, 3, mode="spmd", fanout="psum")
    got = stubbed(c, sharded=False)
    n = (K - 1) * B + 3
    c.submit_many(0, entries(rng, n, b"m"))
    t = c.begin_burst(max_k=K)
    assert t.K == K
    check_sharding(c, got[0])
    lay = arg_layout(CFG, 3, K)
    data, meta, count = legacy_batch(t.taken[0], K)
    for k, s in enumerate(unpacked(lay, got[0])):
        assert np.array_equal(s.batch_data[0], data[k])
        assert np.array_equal(s.batch_meta[0], meta[k])
        assert list(s.batch_count) == [count[k], 0, 0]
        assert not s.batch_data[1:].any() and not s.batch_meta[1:].any()
        assert s.peer_mask.all()


@pytest.mark.parametrize("mesh", [None, pytest.param(MESH, marks=needs_mesh)],
                         ids=["one_chip", "mesh"])
@pytest.mark.parametrize("K, txn", [(1, False), (1, True), (2, False),
                                    (4, False)])
def test_sharded_engine_round_trip(K, txn, mesh):
    """G = 3 groups of three: ``[G, R, width]``, the group axis before
    the replica axis as the mesh's axes go; K = 1 is ``begin_step``
    (with ``txn`` its two watch words, a group's in each of its
    rows)."""
    G = R = 3
    rng = np.random.default_rng(7 * K + (mesh is not None) + 2 * txn)
    cfg = LogConfig(n_slots=1024, slot_bytes=128, window_slots=16,
                    batch_slots=8) if txn else CFG
    c = ShardedCluster(cfg, R, G, mesh=mesh, fanout="psum", txn=txn)
    got = stubbed(c, sharded=True)
    for g in range(G):
        c.submit_many(g, g, entries(
            rng, int(rng.integers(max(K - 1, 0) * B + 1, K * B + 1)),
            b"g%d" % g))
    with c._host_lock:
        c.applied = rng.integers(0, 1000, (G, R)).astype(np.int64)
    fired = {1: [0, 2]}
    if txn:
        c.rebased_total[:] = (0, 32, 64)
        c.set_txn_watch(1, 32 + 9, 4)
    t = c.begin_step(fired) if K == 1 else c.begin_burst(max_k=K)
    assert t.K == K
    lay = arg_layout(cfg, R, K, txn)
    assert got[0].shape == lay.shape((G, R))
    check_sharding(c, got[0])
    for k, s in enumerate(unpacked(lay, got[0])):
        for g in range(G):
            for r in range(R):
                data, meta, count = legacy_batch(t.taken[g][r], K,
                                                 cfg.slot_words)
                assert np.array_equal(s.batch_data[g, r], data[k])
                assert np.array_equal(s.batch_meta[g, r], meta[k])
                assert s.batch_count[g, r] == count[k]
                assert s.timeout_fired[g, r] == (
                    K == 1 and r in fired.get(g, ()))
        assert np.array_equal(s.peer_mask, c.peer_mask)
        assert np.array_equal(s.apply_done, c.applied)
        assert np.array_equal(
            s.queue_depth, [[len(q) for q in grp] for grp in c.pending])
        if txn:
            assert s.txn_watch.tolist() == [[-1] * R, [9] * R, [-1] * R]
            assert s.txn_term.tolist() == [[0] * R, [4] * R, [0] * R]
        else:
            assert s.txn_watch is None
    assert sum(len(x) for grp in t.taken for x in grp) > 0


def test_a_released_set_comes_back_clean_and_whole():
    """The pool's contract over the packed set: the rows a ticket wrote
    are zeroed at release, the small words are written anew by the
    next dispatch, and the set that comes back is the same buffer."""
    c = SimCluster(CFG, 3, fanout="gather")
    got = stubbed(c, sharded=False)
    rng = np.random.default_rng(3)
    c.submit_many(0, entries(rng, B + 2, b"a"))
    t = c.begin_burst(max_k=2)
    first = t.bufs["packed"]
    assert first.any()
    c._staging.release(t.bufs, [((k, 0), min(B, B + 2 - k * B))
                                for k in range(2)])
    with c._host_lock:
        c._tickets.clear()
    t2 = c.begin_burst(max_k=2)          # nothing queued: an idle burst
    assert t2.bufs["packed"] is first
    lay = arg_layout(CFG, 3, 2)
    for s in unpacked(lay, got[1]):
        assert not s.batch_data.any() and not s.batch_meta.any()
        assert not s.batch_count.any() and s.peer_mask.all()
