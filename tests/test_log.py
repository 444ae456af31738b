"""Unit tests for the slot-ring log — the wrap/fit edge cases the reference
log (``dare_log.h:466-558``) handles with byte-level splitting rules, here
exercised on the slot-based TPU design (SURVEY.md §7 step 1)."""

import jax.numpy as jnp
import numpy as np
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import (
    EntryType, M_LEN, M_TERM, M_TYPE, META_W,
    Log, absorb_window, append_batch, extract_window, last_term, make_log,
    row_words, window_rows,
)

CFG = LogConfig(n_slots=16, slot_bytes=16, window_slots=8, batch_slots=4)


def mk_batch(vals, typ=EntryType.SEND):
    B = CFG.batch_slots
    data = np.zeros((B, CFG.slot_words), np.int32)
    meta = np.zeros((B, META_W), np.int32)
    for i, v in enumerate(vals):
        data[i, 0] = v
        meta[i, M_TYPE] = int(typ)
        meta[i, M_LEN] = 4
    return jnp.asarray(data), jnp.asarray(meta), jnp.asarray(
        len(vals), jnp.int32)


def i32(v):
    return jnp.asarray(v, jnp.int32)


def test_append_and_extract():
    log = make_log(CFG)
    data, meta, cnt = mk_batch([10, 11, 12])
    log, end = append_batch(log, i32(0), i32(0), data, meta, cnt, i32(5))
    assert int(end) == 3
    wd, wm = extract_window(log, i32(0), 8)
    assert wd[0, 0] == 10 and wd[2, 0] == 12
    assert wm[0, M_TERM] == 5
    assert int(last_term(log, end)) == 5


def test_append_clamps_to_capacity():
    """Appends never overtake head (free-space check of log_append_entry);
    capacity is n_slots-1 — one slot stays free so the prev-term check
    never reads a recycled slot."""
    log = make_log(CFG)
    end, head = i32(0), i32(0)
    for k in range(5):  # try to push 20 entries into a 16-slot ring
        data, meta, cnt = mk_batch([k * 4, k * 4 + 1, k * 4 + 2, k * 4 + 3])
        log, end = append_batch(log, end, head, data, meta, cnt, i32(1))
    assert int(end) == 15  # clamped at n_slots-1 with head=0
    # prune head -> space opens up
    data, meta, cnt = mk_batch([99])
    log, end = append_batch(log, end, i32(4), data, meta, cnt, i32(1))
    assert int(end) == 16
    wd, _ = extract_window(log, i32(15), 1)
    assert wd[0, 0] == 99


def test_wraparound_extract():
    """The ring wrap that costs the reference two RDMA sends
    (dare_ibv_rc.c:1539-1545) is a plain modular gather here."""
    log = make_log(CFG)
    end, head = i32(0), i32(0)
    for k in range(7):
        data, meta, cnt = mk_batch([4 * k, 4 * k + 1, 4 * k + 2, 4 * k + 3])
        head = i32(max(0, int(end) - 4))
        log, end = append_batch(log, end, head, data, meta, cnt, i32(1))
    assert int(end) == 28
    wd, _ = extract_window(log, i32(24), 4)  # crosses slot 15 -> 0
    np.testing.assert_array_equal(np.asarray(wd[:4, 0]), [24, 25, 26, 27])


@pytest.mark.parametrize("W", [1, 4, 8, 16])
def test_window_rows_are_the_gathered_window(W):
    """The replay fetch's slices (``window_rows``) give the rows of the
    modular gather (``extract_window``), fused, from every start: the
    ring's first turn, each slot of the wrap, later turns, the top of
    the i32 range; alone and under ``vmap`` with a start a ring row."""
    import jax
    rng = np.random.default_rng(W)
    n, cols = CFG.n_slots, CFG.slot_words + META_W
    # the pad columns hold noise here: none of it may come back
    logs = Log(buf=jnp.asarray(rng.integers(
        0, 1 << 30, (3, n, row_words(CFG.slot_words)), dtype=np.int32)),
        slot_words=CFG.slot_words)
    sliced = jax.jit(jax.vmap(lambda lg, s: window_rows(lg, s, W)))
    gathered = jax.jit(jax.vmap(lambda lg, s: extract_window(lg, s, W)))
    starts = list(range(3 * n)) + [2 ** 31 - 2 * n + k for k in range(n)]
    for s0 in starts:
        at = i32([s0, (s0 + 5) % (3 * n), (s0 * 7 + 3) % (3 * n)])
        rows = np.asarray(sliced(logs, at))
        wd, wm = gathered(logs, at)
        assert rows.shape == (3, W, cols)
        np.testing.assert_array_equal(rows[..., :CFG.slot_words], wd)
        np.testing.assert_array_equal(rows[..., CFG.slot_words:], wm)


def test_absorb_extends():
    leader, follower = make_log(CFG), make_log(CFG)
    data, meta, cnt = mk_batch([1, 2, 3])
    leader, lend = append_batch(leader, i32(0), i32(0), data, meta, cnt,
                                i32(2))
    wd, wm = extract_window(leader, i32(0), 8)
    follower, fend = absorb_window(follower, i32(0), wd, wm, i32(0), i32(3))
    assert int(fend) == 3
    fd, fm = extract_window(follower, i32(0), 8)
    np.testing.assert_array_equal(np.asarray(fd[:3, 0]), [1, 2, 3])
    assert fm[0, M_TERM] == 2


def test_absorb_gap_rejected():
    follower = make_log(CFG)
    wd = jnp.zeros((8, CFG.slot_words), jnp.int32)
    wm = jnp.zeros((8, META_W), jnp.int32)
    follower, fend = absorb_window(follower, i32(0), wd, wm, i32(5), i32(3))
    assert int(fend) == 0  # wstart(5) > my_end(0): ignored


def test_absorb_truncates_divergent_suffix():
    """Raft log-matching: a stale uncommitted suffix (deposed leader's
    entries) is discarded at the first term mismatch — the analog of
    log_adjustment rewinding via NC determinants (dare_ibv_rc.c:1292)."""
    a, b = make_log(CFG), make_log(CFG)
    d, m, c = mk_batch([1, 2])
    a, aend = append_batch(a, i32(0), i32(0), d, m, c, i32(1))
    b, bend = append_batch(b, i32(0), i32(0), d, m, c, i32(1))
    # b (deposed leader) appends garbage in term 2
    d2, m2, c2 = mk_batch([97, 98, 99])
    b, bend = append_batch(b, bend, i32(0), d2, m2, c2, i32(2))
    assert int(bend) == 5
    # a (new leader, term 3) appends one entry and sends window from 0
    d3, m3, c3 = mk_batch([42])
    a, aend = append_batch(a, aend, i32(0), d3, m3, c3, i32(3))
    wd, wm = extract_window(a, i32(0), 8)
    b, bend = absorb_window(b, bend, wd, wm, i32(0), aend)
    assert int(bend) == 3  # truncated from 5 to leader's end
    bd, bm = extract_window(b, i32(0), 8)
    np.testing.assert_array_equal(np.asarray(bd[:3, 0]), [1, 2, 42])
    np.testing.assert_array_equal(np.asarray(bm[:3, M_TERM]), [1, 1, 3])


def test_absorb_shorter_window_never_truncates():
    a = make_log(CFG)
    d, m, c = mk_batch([1, 2, 3, 4])
    a, aend = append_batch(a, i32(0), i32(0), d, m, c, i32(1))
    wd, wm = extract_window(a, i32(0), 8)
    # absorb only first 2 entries (same term): end must stay 4
    a, aend2 = absorb_window(a, aend, wd, wm, i32(0), i32(2))
    assert int(aend2) == 4
