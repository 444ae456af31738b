"""Deterministic in-process multi-replica cluster — the test/bench harness.

The reference validates only end-to-end on a real IB cluster (SURVEY.md §4);
this harness runs the full protocol (election, replication, commit, pruning,
reconfig, partitions) deterministically on one host: N replicas are either N
rows of a ``vmap``-simulated axis (``mode="sim"``, any single device) or one
per device of a real mesh (``mode="spmd"``, shard_map).

Partitions/crashes are expressed through per-replica ``peer_mask`` rows —
the analog of ``reconf_bench.sh`` killing processes, but reproducible.

The host bookkeeping is written once, in ``ClusterEngine``, over a grid
of cells; ``SimCluster`` (one group, cells ``(r,)``) and
``shard.cluster.ShardedCluster`` (G groups, cells ``(g, r)``) are its two
front ends. The module-level rules above the class are what the body is
made of and what ``runtime/host.py`` and the drivers share with it.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rdma_paxos_tpu.config import LogConfig, REBASE_STALL_STEPS
from rdma_paxos_tpu.consensus.log import EntryType, M_GIDX
from rdma_paxos_tpu.consensus.state import Role
from rdma_paxos_tpu.consensus.step import (
    SCAN_KEYS, arg_layout, fetch_rows, unpack_scalars)
from rdma_paxos_tpu.obs.spans import held
from rdma_paxos_tpu.parallel.mesh import (
    axes_spec, build_sim_burst, build_sim_scan, build_sim_step,
    build_spmd_burst, build_spmd_scan, build_spmd_step, make_replica_mesh,
    stack_states)
from rdma_paxos_tpu.runtime import hostpath
from rdma_paxos_tpu.runtime.hostpath import LazyReplayStream


# Compiled steps are shared across ALL cluster engines (same static
# config ⇒ same XLA program); without this every cluster re-traces the
# protocol. Module-level so the sharded multi-group engine
# (rdma_paxos_tpu.shard.cluster.ShardedCluster) and SimCluster share
# ONE cache — a G-group cluster and a single-group cluster built from
# the same LogConfig never compile the same program twice, and tests
# can assert cache-key sets across both engines.
STEP_CACHE: Dict[tuple, object] = {}


# ---------------------------------------------------------------------------
# Host-bookkeeping rules as module functions: what ``ClusterEngine``'s
# methods call once a cell (or once an entry: these stay free of the
# grid), and what is shared with callers outside the engines.
# ---------------------------------------------------------------------------

def redigest_fn(cfg: LogConfig, window_slots: int):
    """Fetch (or compile once into the shared cache) the jitted range
    re-digest pass (``consensus/step.py:build_redigest``). The cache
    key carries a distinct ``"redigest"`` marker, so repair-off
    clusters' key sets (and programs) are untouched — the same
    discipline as the ``audit=``/``telemetry=`` variants."""
    key = (cfg, "redigest", int(window_slots))
    fn = STEP_CACHE.get(key)
    if fn is None:
        from rdma_paxos_tpu.consensus.step import build_redigest
        fn = build_redigest(cfg, window_slots=window_slots)
        STEP_CACHE[key] = fn
    return fn


def run_redigest(cluster, buf_row, lo: int, hi: int, *, group: int,
                 rebased_total: int, replica: int) -> int:
    """Shared range re-digest rule for BOTH engines: digest the
    committed entries ``[lo, hi)`` (raw offsets) of one replica's log
    row through the jitted pass and feed them to the ledger as
    BACKFILL windows (absolute indices, ``backfill=True`` — the
    ledger's frontier self-check is not consulted for out-of-order
    history re-reports). The stamped gidx column must equal the
    expected index for every digested entry — a recycled slot means
    the range is no longer physically present and backfilling it would
    fabricate coverage. Returns the number of indices recorded.

    Caller contract: dispatches drained (``require_drained`` — the
    pass reads device log state an in-flight donated dispatch would
    race) and ``lo >= head`` of that replica."""
    require_drained(cluster._tickets, "redigest")
    if cluster.auditor is None:
        raise RuntimeError("redigest requires an audit=True cluster")
    lo, hi = int(lo), int(hi)
    if hi <= lo:
        return 0
    W = cluster._replay_W
    fn = redigest_fn(cluster.cfg, W)
    done = 0
    start = lo
    while start < hi:
        with cluster._host_lock:
            d_fut, t_fut, g_fut = fn(buf_row, jnp.int32(start))
        dig = np.asarray(d_fut)
        trm = np.asarray(t_fut)
        gix = np.asarray(g_fut)
        n = min(hi - start, W)
        expect = np.arange(start, start + n, dtype=gix.dtype)
        if not np.array_equal(gix[:n], expect):
            bad = int(np.argmax(gix[:n] != expect))
            raise RuntimeError(
                "redigest integrity: slot of index %d holds gidx %d "
                "(recycled past the range) — cannot backfill" %
                (start + bad, int(gix[bad])))
        cluster.auditor.record_window(
            replica, start + rebased_total, dig[:n], trm[:n],
            start + n + rebased_total, group=group, backfill=True,
            step=cluster.step_index)
        done += n
        start += n
    return done


def cap_tiers(k_tiers: Sequence[int],
              max_k: Optional[int]) -> Tuple[int, ...]:
    """The governed tier-cap rule, shared by BOTH engines: the fused
    tiers bounded at ``max_k`` — always a non-empty subset of the
    engine's prewarmed ladder, so a capped dispatch can never hit an
    uncompiled program. ``max_k <= 1`` is the SERIAL step, not a
    burst tier: refuse loudly rather than silently dispatching the
    smallest burst (the SLO-shed contract promises serial)."""
    if max_k is None:
        return tuple(k_tiers)
    if int(max_k) < 2:
        raise ValueError(
            "max_k <= 1 is the serial step tier — dispatch step(), "
            "not a capped burst")
    return tuple(k for k in k_tiers if k <= int(max_k)) \
        or tuple(k_tiers[:1])


def cap_scan_tiers(cluster, K: int) -> None:
    """Validate and cap an engine's fused-dispatch tier set at ``K``
    (the benches' ``--scan K`` contract, held in ONE place next to
    ``K_TIERS``): K must be >= 2 — the smallest fused tier — and the
    burst/scan sizing then picks the smallest capped tier covering
    the backlog as usual."""
    K = int(K)
    if K < 2:
        raise ValueError(
            "scan K must be >= 2 (the smallest fused tier)")
    cluster.K_TIERS = (tuple(t for t in cluster.K_TIERS if t <= K)
                       or cluster.K_TIERS[:1])


def require_drained(tickets, site: str) -> None:
    """Serial-path rule: a fused ``step()``/``step_burst()`` while
    dispatches are in flight would finish out of FIFO order AND mutate
    the pending queues before the violation surfaced — refuse up
    front, before any batch take."""
    if tickets:
        raise RuntimeError(
            "%s() with %d in-flight dispatch(es): finish the "
            "pipeline first" % (site, len(tickets)))


def requeue_shortfall(pending: List, take: List, acc: int) -> None:
    """Step/requeue rule: appends stop entirely the step the replica
    is not leader and the device capacity clamp drops suffixes only,
    so the appended set is always a PREFIX of ``take`` — requeue the
    remainder at the FRONT of ``pending``, in order (in place)."""
    if acc < len(take):
        pending[:0] = take[acc:]


def clamp_burst_take(pending_len: int, end: int, head: int,
                     n_slots: int, max_take: int,
                     reserved: int = 0) -> int:
    """Burst capacity rule: never enqueue more than the ring can take
    without drops (mid-burst drops would reorder a connection's
    fragments against later steps). ``reserved`` subtracts appends
    already dispatched but not yet reflected in ``end`` (in-flight
    pipelined tickets)."""
    avail = (n_slots - 1) - (end - head) - reserved
    return min(pending_len, max(avail, 0), max_take)


def count_ring(prof, last, res, taken, n_slots: int, g=()) -> None:
    """What one dispatch did to the leader's ring, off the packed row
    against the last dispatch's (``last`` is kept in the same rebase
    frame as the state, and the rollover's delta is a multiple of
    ``n_slots``): slots the pruner gave back (``head``'s advance),
    entries offered that the capacity clamp did not take (they are
    queued again: the same ``accepted`` the requeue rule reads), and
    turns of the ring ``end`` completed. Of one group (its scope
    ``g``, ``taken`` its replicas' takes); no leader, or two claims,
    counts nothing."""
    if last is None:
        return
    lead = np.flatnonzero(res["role"][g] == int(Role.LEADER))
    if lead.size != 1:
        return
    r = int(lead[0])
    head, end = res["head"][g], res["end"][g]
    for counter, n in (
            ("pruned_slots_total", int(head[r]) - int(last["head"][g][r])),
            ("ring_wraps_total", int(end[r]) // n_slots
             - int(last["end"][g][r]) // n_slots),
            ("append_clamped_total",
             len(taken[r]) - int(res["accepted"][g][r]))):
        if n > 0:
            prof.count(counter, n)


def rebase_delta_of(heads: Sequence[int], n_slots: int) -> int:
    """Rebase frontier rule: the coordinated i32-rollover delta is the
    minimum head rounded DOWN to a multiple of ``n_slots`` (the slot
    of global index g is g % n_slots and entries do not move, so the
    subtraction must preserve the mapping). <= 0 means 'cannot fire'
    (a lagging head pins the rollover — the stall-surfacing path)."""
    if not heads:
        return 0
    return min(heads) & ~(n_slots - 1)


def decode_window(wm: np.ndarray, wd: np.ndarray, n: int,
                  replayed: List, frames: Optional[List],
                  collect_frames: bool, rebase: int = 0) -> None:
    """Replay frontier rule: batched decode of ``n`` fetched entries
    (``hostpath.decode_batch`` — one compacted payload blob + cumsum
    offset table per window, zero per-entry bytes objects), appended
    as ONE columnar batch to the lazy ``replayed`` stream and, when a
    consumer opted in, as the store-ready framed blob to ``frames``.
    The single decode implementation for both engines AND both fetch
    paths (the standalone replay fetch and the scan tier's in-dispatch
    replay rows)."""
    batch = hostpath.decode_batch(wm, wd, n, rebase)
    if batch is None:
        return
    hostpath.extend_stream(replayed, batch)
    if collect_frames:
        frames.append(batch.frames())


def make_put(cluster):
    """The engine's ONE host-to-device put: ``put(array)`` takes ONE
    host array whose leading axes are the mesh's (``[R, ...]``, or the
    sharded engine's ``[G, R, ...]``) and returns it on the device.
    Bound once, to ``cluster.mesh`` as it stands; both engines'
    dispatches, prewarm, replay fetch and rebase go through it.

    A dispatch hands it its ONE packed argument (``consensus/step.py``
    ``arg_layout``: the burst's batches and small words, a row a
    replica), where it handed over six arrays until PR 51: a put costs
    by the ARRAY, not by the byte (about 0.28 ms each on one chip, 84
    us a device buffer on a mesh, 0.03 ms a MB: PERF.md, PRs 46, 51).

    Without a mesh it is ``jnp.asarray`` (every row a vmap row on the
    default device). With one, ONE ``jax.device_put`` with the sharding
    the ``build_spmd_*`` programs' ``in_specs`` name (``parallel/
    mesh.py`` ``axes_spec``: ``P("replica")`` or ``P("group",
    "replica")``), replica r's rows straight to chip r: an argument
    put on one chip is split over the mesh INSIDE the call, on the
    dispatch thread and under the host lock (0.7-1.0 ms an array on
    four chips: PERF.md, PR 46). The slices are views of the caller's
    buffer and live by its rule (a ticket keeps its staging buffer
    until ``finish``).

    Each call counts, where the engine has a profiler, the transfer it
    started (``input_put_calls_total``), the device buffers it made
    (``input_put_buffers_total``: one without a mesh, one a chip of the
    mesh with) and the bytes it handed over
    (``input_put_bytes_total``)."""
    mesh = cluster.mesh
    buffers = 1 if mesh is None else mesh.size
    rows = (None if mesh is None
            else jax.sharding.NamedSharding(mesh, axes_spec(mesh)))

    def put(array):
        prof = cluster.profiler
        if prof is not None:
            prof.count("input_put_calls_total", 1)
            prof.count("input_put_buffers_total", buffers)
            prof.count("input_put_bytes_total", array.nbytes)
        if rows is None:
            return jnp.asarray(array)
        return jax.device_put(array, rows)
    return put


class FetchedColumns:
    """A column range of fetched rows that are still on the device:
    converts like an array (``np.asarray``). The rows come to the host
    ONCE, at the first conversion of either range (a ``jax.Array``
    keeps its host copy), and a range is a view of that copy."""

    __slots__ = ("rows", "cols")

    def __init__(self, rows, cols: slice):
        self.rows, self.cols = rows, cols

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.rows)[..., self.cols]


class ReplayFetch:
    """The standalone replay fetch of BOTH engines: ``fetch_rows`` over
    every replica's ring row (``batch_axes`` vmaps: ``[R]``, or the
    sharded engine's ``[G, R]``), compiled at a few static widths of
    the engine's replay window and called at the smallest that holds
    ``need``, the rows the furthest-behind replica lacks. The engine
    sets ``need`` under its host lock and calls the fetch as
    ``cluster._fetch_all(log, starts)``, looked up at call time: a
    wrapper put over that attribute (the benchmark's span) sees two
    arguments and two results that convert with ``np.asarray``, payload
    words and metadata, as it always did. The metadata's ``shape[-2]``
    says how wide the fetch was.

    A c50 dispatch commits 50 entries and the widest window is 4,096
    rows a replica: every row was copied to the host, in two arrays,
    after a ring-sized layout copy, 3.5-7.4 ms a dispatch (PERF.md
    section 6, PR 48). A need above the widest is swept in gulps of
    the widest by the caller's loop, as before."""

    # the replay window over these: 256, 1,024 and 4,096 rows a replica
    # at the cells' geometry (a c1 dispatch commits 1 entry, c50 50, s5
    # 200, and twice that after a hiccup of the loop)
    DIVISORS = (16, 4, 1)

    def __init__(self, widest: int, batch_axes: int):
        self.widths = tuple(sorted({max(widest // d, 1)
                                    for d in self.DIVISORS}))
        self.programs = {}
        for W in self.widths:
            def fn(log, start, W=W):
                return fetch_rows(log, start, window_slots=W)
            for _ in range(batch_axes):
                fn = jax.vmap(fn)
            self.programs[W] = jax.jit(fn)
        # set by the engine inside its host lock, read by the call
        # it makes next, inside the same take
        self.need = widest

    def width_for(self, need: int) -> int:
        return next((W for W in self.widths if W >= need),
                    self.widths[-1])

    def __call__(self, log, starts):
        rows = self.programs[self.width_for(self.need)](log, starts)
        words = log.slot_words
        return (FetchedColumns(rows, slice(0, words)),
                FetchedColumns(rows, slice(words, None)))

    def warm(self, log, starts) -> None:
        """Every width once (results dropped), so that no served fetch
        compiles or loads a program."""
        for fn in self.programs.values():
            fn(log, starts)


class StepTicket:
    """One dispatched-but-not-finished protocol step/burst.

    ``begin_step``/``begin_burst`` encode + dispatch and return one of
    these immediately (the device program runs asynchronously);
    ``finish`` blocks on the outputs and runs every post-step host
    rule. Serial ``step()``/``step_burst()`` are exactly
    ``finish(begin_*())`` — the pipelined driver simply keeps more
    than one ticket in flight."""

    __slots__ = ("kind", "out", "taken", "timeouts", "K", "bufs",
                 "applied0")

    def __init__(self, kind: str, out, taken, timeouts, K: int, bufs,
                 applied0=None):
        self.kind = kind          # "step" | "burst" | "scan"
        self.out = out            # device output pytree (futures)
        self.taken = taken        # per-replica (or [g][r]) popped rows
        self.timeouts = timeouts
        self.K = K
        self.bufs = bufs          # staging buffer set (pool-owned)
        # scan tier: the host apply cursors the dispatch staged its
        # replay window at (the readback rows start here)
        self.applied0 = applied0


def read_scalars(ticket: StepTicket) -> Dict[str, np.ndarray]:
    """The ONE device-to-host read of a dispatch, which blocks on the
    program: its packed rows (``[..., R, NS + R]``, with a leading K
    from a fused dispatch, whose result is the final step's rows with
    ``accepted`` cumulative in-program), unpacked into the ``res``
    dict — every scalar the host rules consume, the config view and
    ``peer_acked`` included. ``cfg_rescanned`` alone is summed over a
    fused dispatch's steps here: in how many of them the full-ring
    config rescan ran. Shared by both engines."""
    out = ticket.out
    if ticket.kind == "step":
        return unpack_scalars(np.asarray(out.scal))
    rows = np.asarray(out["scal"] if ticket.kind == "scan" else out.scal)
    res = unpack_scalars(rows[-1])
    res["cfg_rescanned"] = rows[
        ..., SCAN_KEYS.index("cfg_rescanned")].sum(axis=0)
    return res


class StagingPool:
    """Persistent, reusable host staging buffers for window encode.

    A set is ONE contiguous i32 buffer, the dispatch's packed argument
    (``packed``: ``[*lead, rows, 128]``, ``consensus/step.py``
    ``arg_layout``), and VIEWS of it by field: ``data`` / ``meta``
    (and ``data_u8``) where ``pack_rows`` writes the batch, the small
    words (``count``, ``peer_mask``, ``applied``, ``qdepth``, ...)
    where ``begin_*`` writes them, so that what was packed is what is
    put, and no byte is copied twice.

    Allocating + zeroing the [R, B, slot_words] batch arrays every
    step was a measurable share of ``host_encode``; the pool hands out
    preallocated sets and zeroes ONLY the rows the previous user
    actually wrote (recorded at release; the small words are written
    anew every dispatch). A set stays checked out for
    the lifetime of its ticket, so a pipelined driver can never
    overwrite a buffer an in-flight dispatch is still reading —
    double-buffering falls out of the pool discipline (depth D keeps
    at most D+1 sets alive)."""

    def __init__(self):
        self._pools: Dict[tuple, List[dict]] = {}
        self._lock = threading.Lock()

    def acquire(self, lay, lead: tuple, fused: bool = True) -> dict:
        """A set for ``lay`` under the leading axes ``lead``; a single
        step's (``fused=False``: K = 1) has the batch fields' K axis
        dropped."""
        key = (lay, lead, fused)
        with self._lock:
            pool = self._pools.setdefault(key, [])
            if pool:
                return pool.pop()
        packed = np.zeros(lay.shape(lead), np.int32)
        bufs = lay.views(packed)
        if not fused:
            for name in ("data", "meta", "count"):
                bufs[name] = bufs[name][0]
        # u8 view of the payload words: zero-copy packing target (one
        # bytes->row copy per entry instead of pad+frombuffer+copy)
        bufs["data_u8"] = bufs["data"].view(np.uint8)
        bufs["packed"] = packed
        bufs["key"] = key
        return bufs

    def release(self, bufs: dict, dirty_rows) -> None:
        """Return a set; ``dirty_rows`` yields (index-tuple, n) pairs —
        the rows written since acquire — which are zeroed here so the
        next acquire starts clean without a full-buffer memset."""
        data, meta = bufs["data"], bufs["meta"]
        for idx, n in dirty_rows:
            if n > 0:
                data[idx][:n] = 0
                meta[idx][:n] = 0
        with self._lock:
            self._pools[bufs["key"]].append(bufs)


def pack_rows(bufs: dict, idx: tuple, take: Sequence[Tuple],
              slot_bytes: int) -> None:
    """Zero-copy entry packing: write (etype, conn, req, payload) rows
    straight into the staging buffers at ``idx`` (e.g. ``(r,)`` or
    ``(k, g, r)``) — the single packing rule for both engines, now one
    ``hostpath.pack_window`` batch pass per window (one payload join +
    one scatter + four column writes instead of a per-entry loop)."""
    hostpath.pack_window(bufs["data_u8"][idx], bufs["meta"][idx],
                         take, slot_bytes)


def assemble_frames(types, conns, lens, raw, idxs) -> bytes:
    """Store-ready framed blob for the client entries at ``idxs`` of a
    decoded window: ``([u32 len][u8 etype][u32 conn][payload])*``,
    built by ``hostpath.frames_from_cols`` — headers and payload
    scattered over a precomputed offset table into ONE output
    allocation (byte-golden against the previous two-pass masked
    gather; pinned by tests/test_hostpath.py). ONE implementation
    shared by SimCluster and ShardedCluster so the byte format can
    never drift between the engines (the G=1 parity contract)."""
    row = raw.shape[1]
    cl = np.minimum(lens[idxs].astype(np.int64), row)
    keep = np.arange(row, dtype=np.int64) < cl[:, None]
    blob = raw[idxs][keep].tobytes()
    offs = np.zeros(idxs.size + 1, np.int64)
    np.cumsum(cl, out=offs[1:])
    return hostpath.frames_from_cols(types[idxs], conns[idxs], cl,
                                     blob, offs)


def cell_of(nest, idx: tuple):
    """The entry of a per-cell nest of lists (``pending``, ``replayed``,
    ``frames``, a ticket's ``taken``) at ``idx``: ``nest[r]`` in a
    single group, ``nest[g][r]`` in the sharded engine. The group
    prefix ``idx[:-1]`` gives that group's list of replicas (the whole
    nest in a single group)."""
    for i in idx:
        nest = nest[i]
    return nest


def set_cell(nest, idx: tuple, value) -> None:
    """Rebind the entry of a per-cell nest at ``idx``."""
    cell_of(nest, idx[:-1])[idx[-1]] = value


def nest_cells(flat: List, lead: tuple) -> List:
    """A list with one entry a cell, in cell order, as the nest the
    callers index: itself under ``(R,)``, ``[g][r]`` under ``(G, R)``."""
    for n in reversed(lead[1:]):
        flat = [flat[i:i + n] for i in range(0, len(flat), n)]
    return flat


class ScopeCounter:
    """A host counter kept one a rebase scope (a consensus group): an
    int64 array of the scope shape, under ``_<name>`` on the instance,
    which the shared body indexes by a cell's group prefix. From
    outside it is that array (``rebased_total[g]``) where the engine
    has groups and a plain int where it is ONE group (scope shape
    ``()``): the drivers put it into health documents as it comes."""

    def __set_name__(self, owner, name):
        self.slot = "_" + name

    def __get__(self, obj, owner=None):
        if obj is None:
            return self
        arr = getattr(obj, self.slot)
        return arr if arr.ndim else int(arr)

    def __set__(self, obj, value):
        getattr(obj, self.slot)[...] = value


class ClusterEngine:
    """The host bookkeeping of BOTH engines, written once over the grid
    of cells: the dispatch cycle (``begin_step`` / ``begin_burst`` /
    ``finish``), requeue, replay, audit, rebase, spans and the choice
    of the compiled program.

    A cell is one replica's row of one consensus group and is named by
    an index tuple: ``(r,)`` in a single group (``SimCluster``, lead
    shape ``(R,)``), ``(g, r)`` in the sharded engine
    (``ShardedCluster``, lead shape ``(G, R)``). ``res[k][idx]``,
    ``self.applied[idx]`` and ``pack_rows(bufs, (k,) + idx, ...)`` mean
    the same thing under both; the lists a caller indexes
    (``pending[r]`` / ``pending[g][r]``) are reached with ``cell_of``.
    A cell's group prefix ``idx[:-1]`` is its SCOPE: what rolls over
    together, has one leader, one txn watch and one link model (``()``
    is the single group: one scope over all replicas).

    The two classes below are front ends: a constructor, the table of
    ``parallel/mesh.py`` builders with the engine's part of the
    ``STEP_CACHE`` key, a few hooks where the engines truly differ
    (``_norm_timeouts``, ``_link_models``, ``_span_rep``,
    ``_count_appends``, ``_observe``), and the public addressing (the
    sharded methods take a ``group`` first). What is seen from outside
    keeps its shape: ``res[k]`` is ``[R]`` or ``[G, R]``, and
    ``need_recovery`` / ``_wedged`` / ``read_blocked`` hold ``r`` or
    ``(g, r)``."""

    # legacy alias (tests and callers key off the class attribute);
    # the SAME dict object as the module-level shared cache
    _STEP_CACHE: Dict[tuple, object] = STEP_CACHE

    # burst size tiers: the smallest tier >= the steps needed is compiled
    # (bounded recompiles) and padded with zero-count steps
    K_TIERS = (2, 4, 8, 16)

    # consecutive post-threshold zero-delta steps before the stall is
    # declared — shared with NodeDaemon (config.REBASE_STALL_STEPS)
    REBASE_STALL_STEPS = REBASE_STALL_STEPS

    # coordinated i32-offset rollovers performed (see _maybe_rebase)
    rebases = ScopeCounter()
    rebased_total = ScopeCounter()
    # rebase-stall surfacing (ADVICE.md #3): a heard-but-lagging
    # row's low head pins the agreed delta at 0, so end marches
    # toward the i32 ceiling with no rollover possible. Consecutive
    # post-threshold steps with delta 0 are counted; past
    # REBASE_STALL_STEPS each further step increments
    # ``rebase_stalled`` (and the attached registry's counter), and
    # the transition emits one ``rebase_stalled`` trace event
    # (re-armed by the next successful rollover).
    rebase_stall_steps = ScopeCounter()
    rebase_stalled = ScopeCounter()

    # ---------------- what a front end supplies ----------------

    # kind ("step" | "burst" | "scan") -> (the kind's mark in a
    # STEP_CACHE key, its builder without a mesh, its builder over one)
    _PROGRAMS: Dict[str, tuple] = {}

    def _norm_timeouts(self, timeouts) -> tuple:
        """``(kept, cells)``: the fired election timers as the ticket
        and the flight record keep them, and as cell indices."""
        raise NotImplementedError

    def _link_models(self) -> dict:
        """scope -> attached chaos link model (an int names a group)."""
        raise NotImplementedError

    def _span_rep(self, *idx) -> int:
        """A cell's replica id in the span recorder's heaps."""
        raise NotImplementedError

    def _count_appends(self, prof, appended: int) -> None:
        """``appended`` scopes' leaders appended in this dispatch."""
        raise NotImplementedError

    def _observe(self, res) -> None:
        """Per-scope metric series at the tail of ``finish``."""
        raise NotImplementedError

    # ---------------- construction ----------------

    def __init__(self, cfg: LogConfig, lead: tuple, state, *, mode: str,
                 mesh, key_mesh: tuple, state_sharding,
                 group_size: Optional[int], use_pallas: Optional[bool],
                 interpret: bool, fanout: str, stable_fast_path: bool,
                 audit: bool, flight_capacity: int, telemetry: bool,
                 scan: bool, txn: bool):
        self.cfg = cfg
        # device-resident K-window scan tier (hostpath PR): with
        # scan=True, begin_burst dispatches the fused-scan program —
        # same protocol computation as the burst (and the same packed
        # scalar rows), but the committed rows are extracted INSIDE
        # the dispatch instead of by a separate fetch dispatch, and no
        # per-field stacks are returned. Mutable at runtime (A/B benches
        # flip it); scan-off clusters never build a scan program, so
        # their STEP_CACHE keys are untouched (tests pin it).
        self.scan = bool(scan)
        self.scan_dispatches = 0
        self._lead = lead = tuple(int(n) for n in lead)
        self.R = lead[-1]
        self.group_size = group_size or self.R
        # the grid: every cell's index, beside the key it has in
        # need_recovery / _wedged (r, or (g, r)); and every scope's
        self._cells = list(np.ndindex(*lead))
        self._keyed = [(idx, idx if len(idx) > 1 else idx[0])
                       for idx in self._cells]
        self._scopes = list(np.ndindex(*lead[:-1]))
        self._mode = mode
        # the mesh's part of a STEP_CACHE key (nothing where the mode
        # says it all; the static device layout otherwise)
        self._key_mesh = key_mesh
        # correctness observability (obs/audit.py): audit=True compiles
        # the digest-chain step variants (distinct cache keys — the
        # default programs are untouched), feeds every step's digest
        # windows to a cluster AuditLedger keyed (group, term, index),
        # and records a bounded flight ring of step inputs/outputs for
        # post-mortem dumps
        self._audit = audit
        if audit:
            from rdma_paxos_tpu.obs.audit import (
                AuditLedger, FlightRecorder)
            self.auditor = AuditLedger(self.R, *lead[:-1])
            self.flight = FlightRecorder(flight_capacity)
        else:
            self.auditor = None
            self.flight = None
        # device telemetry (obs/device.py): telemetry=True compiles the
        # counter-vector step variants (distinct cache keys — default
        # programs untouched, exactly the audit= discipline), reduces
        # each dispatch's vectors host-side at finish() (the readback
        # thread under the pipelined driver), accumulates them into
        # ``device_counters`` [*lead, T_N], and exports device_*
        # registry series when an obs facade is attached. On a mesh the
        # out_specs gather brings every chip's vector back into the
        # global array, so per-shard counters survive the shard_map
        # (tests pin mesh ≡ vmap telemetry parity).
        self._telemetry = telemetry
        if telemetry:
            from rdma_paxos_tpu.obs import device as _device
            self.device_counters = _device.zeros(*lead)
        else:
            self.device_counters = None
        # cross-group transaction lane (txn/lane.py): txn=True compiles
        # the prepare-vote SERIAL step variants (distinct cache keys —
        # default programs untouched, exactly the audit=/telemetry=
        # discipline; tests/test_txn.py pins txn=False bit-identity;
        # burst/scan programs never carry the lane). The armed watch,
        # one a scope, is host state in the ABSOLUTE index domain;
        # begin_step converts to the log-offset domain the device
        # compares in, and the votes come back as the ``[*lead]``
        # matrix from the SAME dispatch that replicated the prepares.
        self._txn = txn
        self._txn_watch = np.full(lead[:-1], -1, np.int64)  # -1 = clear
        self._txn_wterm = np.zeros(lead[:-1], np.int64)
        # production default: the Pallas quorum kernel on TPU (same code
        # path as the benches), jnp reference scan elsewhere
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self._use_pallas = use_pallas
        self._interpret = interpret
        self._fanout = fanout
        # dispatch the elections-free STABLE step on iterations where no
        # election timer fired (the latency hot path — Phase B statically
        # removed, one fewer collective); compiled lazily on first use
        self._stable_fast_path = stable_fast_path
        self.mesh = mesh
        # placed across the mesh up front, so the donated step never
        # pays a layout change mid-serving
        self._state_sharding = state_sharding
        # the donated device-state handle: REBINDING it races the next
        # dispatch  # guarded-by: _host_lock [writes]
        self.state = (state if state_sharding is None
                      else jax.device_put(state, state_sharding))
        self._program("step", elections=True)
        # compile-count accounting: every shared-cache key this cluster
        # dispatches through (the single-compile guard's witness)
        self.programs_used: set = set()
        # device dispatch counters: protocol steps (the one-dispatch-
        # per-step claim shard_bench proves) and replay fetch sweeps
        self.dispatches = 0
        self.fetch_dispatches = 0
        # every argument of a dispatch, of prewarm, of the replay fetch
        # and of a rebase goes to the device through this (shardings
        # built here, once)
        self._put = make_put(self)
        # all cells' windows in ONE dispatch (the per-replica loop of
        # fetch+slice dispatches dominated the host replay path). The
        # REPLAY window is wider than the protocol window: a K-step
        # burst commits up to K*batch_slots entries at once, and each
        # fetch dispatch costs host time — sweep in gulps of at most
        # this, in the narrowest of ReplayFetch's widths that holds
        # what was committed.
        self._replay_W = min(cfg.n_slots // 2,
                             max(4 * cfg.window_slots, 256))
        # _fetch_all is what the fetch is CALLED through (a traced
        # benchmark run wraps it)
        self._replay_fetch = ReplayFetch(self._replay_W, len(lead))
        self._fetch_all = self._replay_fetch
        # host bookkeeping
        # host apply cursor — single-writer: advanced in-place by the
        # finishing (readback) thread only; whole-array WRITES rebind
        # under the lock  # guarded-by: _host_lock [writes]
        self.applied = np.zeros(lead, np.int64)
        # every scope its own hear-matrix: its own fault domain
        self.peer_mask = np.ones(lead + (self.R,), np.int32)
        # a split that partition() found sound under the psum fan-out
        self._psum_split = False
        # guarded-by: _host_lock
        self.pending = nest_cells([[] for _ in self._cells], lead)
        # pipelined dispatch (begin_*/finish): FIFO of in-flight
        # tickets, the staging-buffer pool, and the dispatch
        # concurrency counters (max_inflight_dispatches is the
        # acceptance witness that the pipeline really overlapped).
        # _host_lock guards the host queues (pending/applied/last)
        # against the dispatch-thread/readback-thread split — serial
        # callers pay one uncontended acquire.
        # guarded-by: _host_lock
        self._tickets: collections.deque = collections.deque()
        self._staging = StagingPool()
        self._host_lock = threading.RLock()
        self.inflight_dispatches = 0         # guarded-by: _host_lock
        self.max_inflight_dispatches = 0     # guarded-by: _host_lock
        # published by pointer swap under the lock; lock-free READS see
        # a complete (stale at worst) result dict by design
        # guarded-by: _host_lock [writes]
        self.last: Optional[Dict[str, np.ndarray]] = None
        # (type, conn_id, req_id, payload) per cell, in apply order
        # — columnar LazyReplayStream batches on the hot path, legacy
        # tuple view on demand (tests/models/recovery)
        self.replayed = nest_cells(
            [LazyReplayStream() for _ in self._cells], lead)
        # store-ready framed blobs (([u32 len][etype][conn][payload])*)
        # built VECTORIZED during the window decode — the driver hands
        # them to StableStore.append_framed untouched. Only produced
        # when a consumer opts in (collect_frames), so pure-sim tests
        # don't accumulate them.
        self.collect_frames = False
        self.frames = nest_cells([[] for _ in self._cells], lead)
        # cells whose log was force-pruned past their apply cursor
        # (force_log_pruning left them behind): replay stops — recycled
        # slots must never reach the app — until snapshot recovery
        self.need_recovery: set = set()
        self._wedged: set = set()     # test hook: frozen apply (wedged app)
        self._rebases = np.zeros(lead[:-1], np.int64)
        self._rebased_total = np.zeros(lead[:-1], np.int64)
        self._rebase_stall_steps = np.zeros(lead[:-1], np.int64)
        self._rebase_stalled = np.zeros(lead[:-1], np.int64)
        # host-side observability facade (rdma_paxos_tpu.obs); attached
        # by ClusterDriver (or tests). NEVER read inside jitted code —
        # instrumentation must not change compiled-step cache keys.
        self.obs = None
        # optional obs.spans.StepPhaseProfiler: attributes step wall
        # time to phases (host encode / device dispatch / optional
        # fenced device sync / quorum-wait readback / apply). Host-side
        # only; with fence off it never blocks and never imports jax.
        self.profiler = None
        # read-path subsystem (runtime/reads.py, attached via
        # reads.attach): step-domain leader leases observed — and the
        # queued read hub drained — at the tail of every finish(),
        # which under the pipelined driver is the readback thread.
        # Pure host bookkeeping: never enters jitted code, adds no
        # STEP_CACHE keys (tests/test_reads.py pins it).
        self.leases = None
        self.reads = None
        # log-as-product streams hub (streams/__init__.py, attached
        # via streams.attach): observed at the finish() tail AFTER the
        # read drain (watch cursors follow the same committed frontier
        # reads serve from) and BEFORE the governor (a deep watch
        # backlog is demand the governor must see). Pure host-side
        # consumer: never enters jitted code, adds no STEP_CACHE keys
        # (tests/test_streams.py pins it).
        self.streams = None
        # adaptive dispatch governor (runtime/governor.py, attached
        # via governor.attach_governor): observed at the tail of every
        # finish() — the readback thread under the pipelined driver —
        # exactly like leases/reads. Pure host bookkeeping: the tier
        # it picks is always one of the prewarmed K_TIERS programs,
        # so it adds no STEP_CACHE keys (tests/test_governor.py pins
        # the ladder-only contract). ONE program spans all scopes, so
        # the dispatch uses the max over the per-group rungs.
        self.governor = None
        # cross-group 2PC coordinator (txn/coordinator.py, attached via
        # txn.attach_coordinator): observed at the very tail of every
        # finish() — after the governor, so admission demand it creates
        # is next-step demand. Pure host bookkeeping; the device lane
        # it reads rides the txn= step variant's cache keys only.
        self.txn = None
        # elastic topology controller (topology/transition.py,
        # attached via topology.attach_topology to an engine with
        # groups): fed record placements from the stamp loop (same
        # outside-the-host-lock contract as txn) and observed at the
        # finish() tail, after txn. Host bookkeeping only.
        self.topology = None
        # cells barred from SERVING reads by the repair pipeline
        # (digest quarantine AND the storm policy, whose holds leave
        # replay running and so never enter need_recovery) — consulted
        # by the KVS serving gate and the read hub; keys match
        # need_recovery's shape
        self.read_blocked: set = set()
        self.step_index = 0
        # dispatch-side logical clock: advances at begin_* (step_index
        # advances at finish) so an in-flight pipeline never feeds the
        # link model the same per-step randomness twice; serial callers
        # see the two clocks equal at every dispatch.
        self._dispatch_clock = 0
        # runtime lock sanitizer (analysis/runtime_guard.py): under
        # RP_SANITIZE=1 the guarded-by declarations above become
        # per-access lock-ownership assertions — a latent unlocked
        # mutation fails the test at the exact access. No-op otherwise.
        from rdma_paxos_tpu.analysis import runtime_guard
        runtime_guard.maybe_guard(self, "_host_lock", __file__)

    # ---------------- addressing shared by the front ends ----------------

    @staticmethod
    def _labels(scope: tuple) -> dict:
        """The labels of a scope's metric series, trace events, ledger
        windows and span keys: its group, none for the single group."""
        return {"group": scope[0]} if scope else {}

    def _submit(self, idx: tuple, entries) -> None:
        """Locked: a concurrent ``begin_*`` batch take swaps the pending
        list object, and an unlocked append to the old object would be
        silently lost."""
        with self._host_lock:
            cell_of(self.pending, idx).extend(entries)

    def _arm_txn_watch(self, scope: tuple, index: int, term: int) -> None:
        if not self._txn:
            raise RuntimeError("set_txn_watch requires txn=True")
        self._txn_watch[scope] = int(index)
        self._txn_wterm[scope] = int(term)

    def _disarm_txn_watch(self, scope: tuple) -> None:
        self._txn_watch[scope] = -1
        self._txn_wterm[scope] = 0

    def _partition(self, scope: tuple, groups) -> None:
        """Split one scope's replicas: they hear only same-group peers.
        One rebind of the matrix: a dispatch on another thread reads
        the old one or the new one, never a half-written one."""
        mask = np.zeros((self.R, self.R), np.int32)
        for g in groups:
            for i in g:
                for j in g:
                    mask[i, j] = 1
        np.fill_diagonal(mask, 1)
        full = self.peer_mask.copy()
        full[scope] = mask
        self.peer_mask = full

    def _heal(self, scope: tuple) -> None:
        full = self.peer_mask.copy()
        full[scope] = 1
        self.peer_mask = full
        self._psum_split = False

    def _leader(self, scope: tuple) -> int:
        """The scope's leader iff exactly one replica claims it."""
        assert self.last is not None
        ids = [r for r in range(self.R)
               if self.last["role"][scope + (r,)] == int(Role.LEADER)]
        return ids[0] if len(ids) == 1 else -1

    def _elect(self, idx: tuple, timeouts, max_steps: int) -> int:
        for _ in range(max_steps):
            res = self.step(timeouts=timeouts)
            if res["role"][idx] == int(Role.LEADER):
                return idx[-1]
        raise AssertionError(
            "election did not converge"
            + (" in group %d" % idx[0] if len(idx) > 1 else ""))

    # ---------------- the compiled programs ----------------

    def _scan_slots(self, K: int) -> int:
        """The scan tier's staged replay width: a K-step scan advances
        commit by at most ``K * batch_slots``, so a small-K dispatch
        never pays the full replay window's extract/transfer (the
        fallback fetch covers a host that fell further behind)."""
        return min(self._replay_W,
                   max(K * self.cfg.batch_slots,
                       self.cfg.window_slots))

    def _program(self, kind: str, K: Optional[int] = None,
                 elections: Optional[bool] = None) -> tuple:
        """``(program, key)``: fetch (or compile once into the SHARED
        runtime cache) the protocol step (``kind="step"``, with or
        without ``elections``), the K-step burst or the K-window scan
        of this engine's static config — the single source of every
        variant, so they can never drift apart in build flags, and the
        ONE place a ``STEP_CACHE`` key is formed. The key carries
        everything static that shapes the program — the engine mode
        and (``_key_mesh``) the static device layout — and deliberately
        NOT a group count: the jitted callable is batch-size-
        polymorphic, so clusters of ANY group count share one entry
        per variant. The "audit" / "telemetry" / "txn" marks are
        appended ONLY when asked for, and "scan" keys exist only on
        scan=True clusters: default clusters' keys (and programs) are
        bit-identical to the ones from before each option
        (tests/test_audit.py and its siblings guard exactly this)."""
        mark, build, build_mesh = self._PROGRAMS[kind]
        if self.mesh is not None:
            build = build_mesh
        step = kind == "step"
        slots = self._scan_slots(K) if kind == "scan" else None
        key = ((self.cfg, self.R, self._mode) + self._key_mesh
               + (self._use_pallas, self._interpret, self._fanout) + mark
               + ((elections,) if step else (K,) if slots is None
                  else (K, slots))
               + (("audit",) if self._audit else ())
               + (("telemetry",) if self._telemetry else ())
               + (("txn",) if self._txn and step else ()))
        fn = STEP_CACHE.get(key)
        if fn is None:
            kw = dict(use_pallas=self._use_pallas,
                      interpret=self._interpret, fanout=self._fanout,
                      audit=self._audit, telemetry=self._telemetry)
            if step:
                kw.update(elections=elections, txn=self._txn)
            if slots is not None:
                kw.update(replay_slots=slots)
            over = () if self.mesh is None else (self.mesh,)
            fn = build(self.cfg, self.R, *over, **kw)
            STEP_CACHE[key] = fn
        return fn, key

    def prewarm(self, tiers: Optional[Sequence[int]] = None) -> None:
        """Compile every step variant and burst tier up front (on copies
        of the live state — donation would otherwise consume it). A
        first-use JIT pause of seconds mid-serving stalls the whole
        commit pipeline; paying it before traffic starts keeps the
        serving path pause-free. One compile covers ALL groups, and all
        clusters through the shared runtime cache."""
        cfg, R, lead = self.cfg, self.R, self._lead
        # through the dispatches' own put, at the dispatches' own
        # shapes: an argument placed otherwise (a committed, sharded
        # argument and an uncommitted one-chip argument) is another
        # executable of the same ``jax.jit``, and the first served
        # dispatch would compile it inside the loop
        def idle(lay):
            return self._put(lay.idle(lead, self.peer_mask))

        def run(fn, packed):
            fn(jax.tree.map(lambda x: x.copy(), self.state), packed)
        packed = idle(arg_layout(cfg, R, 1, self._txn))
        for elections in (True, False):
            run(self._program("step", elections=elections)[0], packed)
        for K in (tiers if tiers is not None else self.K_TIERS):
            packed = idle(arg_layout(cfg, R, K))
            for kind in ("burst", "scan") if self.scan else ("burst",):
                run(self._program(kind, K)[0], packed)
        # and the replay fetch at every width, so that no served use
        # compiles anything
        self._replay_fetch.warm(self.state.log,
                                self._put(np.zeros(lead, np.int32)))

    # ---------------- stepping ----------------

    def _effective_mask(self) -> np.ndarray:
        """The step's hear-matrix: the base peer_mask, each scope's
        refined by its attached link model (host-side data only; psum
        fan-out still requires the EFFECTIVE mask to be full)."""
        models = self._link_models()
        if not models:
            return self.peer_mask
        mask = self.peer_mask.copy()
        for scope, lm in models.items():
            mask[scope] = lm.effective_mask(mask[scope],
                                            self._dispatch_clock)
        return mask

    def _dispatch_mask(self) -> np.ndarray:
        mask = self._effective_mask()
        if (self._fanout == "psum" and not self._psum_split
                and not mask.all()):
            raise ValueError(
                "psum fan-out requires full connectivity; use "
                "fanout='gather' to model partitions")
        return mask

    def _step_bufs(self) -> dict:
        return self._staging.acquire(
            arg_layout(self.cfg, self.R, 1, self._txn), self._lead,
            fused=False)

    def _burst_bufs(self, K: int) -> dict:
        return self._staging.acquire(
            arg_layout(self.cfg, self.R, K), self._lead)

    # holds-lock: _host_lock
    def reserved_appends(self) -> np.ndarray:
        """Per-cell appends dispatched but not yet finished — the
        pipelined capacity reservation (``end`` has not caught up).
        Callers hold ``_host_lock`` (begin_burst's capacity sizing and
        the chaos runner's drained-serial room check)."""
        out = np.zeros(self._lead, np.int64)
        for t in self._tickets:
            for idx in self._cells:
                out[idx] += len(cell_of(t.taken, idx))
        return out

    def _dispatch(self, prof, fn, key, packed, kind: str, taken: List,
                  timeouts, K: int, bufs: dict,
                  applied0=None) -> StepTicket:
        """Call the program on the packed argument and queue its
        ticket (``taken`` as the nest callers index), under the host
        lock: the state handle is rebound."""
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            if prof is not None:
                prof.start("program_call")
            self.state, out = fn(self.state, packed)
            if prof is not None:
                prof.stop("program_call")
            ticket = StepTicket(kind, out, taken, timeouts, K, bufs,
                                applied0=applied0)
            if kind == "scan":
                self.scan_dispatches += 1
            self._tickets.append(ticket)
            self.inflight_dispatches += 1
            self.max_inflight_dispatches = max(
                self.max_inflight_dispatches, self.inflight_dispatches)
        if prof is not None:
            prof.stop("device_dispatch")
        self.dispatches += 1
        self.programs_used.add(key)
        self._dispatch_clock += K
        return ticket

    def begin_step(self, timeouts=(),
                   take_batch: bool = True) -> StepTicket:
        """Encode + DISPATCH one protocol step for every cell in one
        device dispatch; returns immediately with the in-flight ticket
        (pass to :meth:`finish`, FIFO). ``timeouts`` fires election
        timers, in the front end's addressing. With
        ``take_batch=False`` no client entries are packed (heartbeat /
        election dispatches of the pipelined driver, which routes all
        appends through capacity-clamped bursts so a shortfall requeue
        can never reorder against in-flight dispatches)."""
        cfg, B = self.cfg, self.cfg.batch_slots
        prof = self.profiler
        if prof is not None:
            prof.start("host_encode")
        timeouts, fired = self._norm_timeouts(timeouts)
        mask = self._dispatch_mask()
        bufs = self._step_bufs()
        count, qdepth = bufs["count"], bufs["qdepth"]
        count[:] = 0
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            taken = []
            for idx in self._cells:
                queue = cell_of(self.pending, idx)
                take = queue[:B] if take_batch else []
                if take:
                    set_cell(self.pending, idx, queue[B:])
                taken.append(take)
                qdepth[idx] = len(queue) - len(take)
            bufs["applied"][:] = self.applied
        for idx, take in zip(self._cells, taken):
            if take:
                pack_rows(bufs, idx, take, cfg.slot_bytes)
                count[idx] = len(take)
        tmo = bufs["timeout"]
        tmo[:] = 0
        for idx in fired:
            tmo[idx] = 1
        bufs["peer_mask"][:] = mask
        if self._txn:
            # device watches compare log offsets: shift each armed
            # ABSOLUTE index by its scope's i32 rollovers so far, then
            # broadcast across the replica axis
            bufs["txn_watch"][:] = np.where(
                self._txn_watch >= 0,
                self._txn_watch - self._rebased_total, -1)[..., None]
            bufs["txn_term"][:] = self._txn_wterm[..., None]
        if prof is not None:
            prof.start("input_transfer")
        packed = self._put(bufs["packed"])
        if prof is not None:
            prof.stop("input_transfer")
        # no timer fired in ANY scope ⟹ Phase B is provably a no-op:
        # dispatch the stable step (bit-identical outputs, one fewer
        # collective)
        fn, key = self._program(
            "step", elections=bool(fired) or not self._stable_fast_path)
        if prof is not None:
            prof.stop("host_encode")
            prof.start("device_dispatch")
        return self._dispatch(prof, fn, key, packed, "step",
                              nest_cells(taken, self._lead), timeouts, 1,
                              bufs)

    def _tiers(self, max_k: Optional[int]) -> Tuple[int, ...]:
        """Fused tiers bounded at ``max_k`` (the shared ``cap_tiers``
        rule — a subset of ``K_TIERS``, never a new compile)."""
        return cap_tiers(self.K_TIERS, max_k)

    def begin_burst(self, max_k: Optional[int] = None) -> StepTicket:
        """Encode + DISPATCH up to ``max(K_TIERS)`` fused protocol
        steps for every cell; returns immediately with the in-flight
        ticket. Capacity sizing subtracts appends reserved by OTHER
        in-flight tickets, so pipelined bursts can never overrun the
        ring (a mid-burst drop would reorder a connection's
        fragments). ``max_k`` caps the tier choice (and the take) at a
        lower rung of the same ladder — the governor's dial."""
        cfg, B = self.cfg, self.cfg.batch_slots
        assert self.last is not None, "burst requires a stepped cluster"
        prof = self.profiler
        if prof is not None:
            prof.start("host_encode")
        mask = self._dispatch_mask()
        tiers = self._tiers(max_k)
        with held(prof, self._host_lock, "dispatch_lock_wait"):
            # capacity sizing: never enqueue more than the ring can
            # take without drops, so mid-burst drops (which would
            # reorder a connection's fragments against later steps)
            # cannot occur
            reserved = self.reserved_appends()
            last = self.last
            taken, qdepth = [], []
            for idx in self._cells:
                queue = cell_of(self.pending, idx)
                n = clamp_burst_take(
                    len(queue), int(last["end"][idx]),
                    int(last["head"][idx]), cfg.n_slots,
                    tiers[-1] * B, int(reserved[idx]))
                taken.append(queue[:n])
                set_cell(self.pending, idx, queue[n:])
                qdepth.append(len(queue) - n)
            applied = self.applied.astype(np.int32)
        k_needed = max(1, max(-(-len(take) // B) for take in taken))
        K = next(k for k in tiers if k >= k_needed)
        bufs = self._burst_bufs(K)
        count = bufs["count"]
        for idx, take in zip(self._cells, taken):
            n = len(take)
            for k in range(-(-n // B)):
                pack_rows(bufs, (k,) + idx, take[k * B:(k + 1) * B],
                          cfg.slot_bytes)
            for k in range(K):
                count[(k,) + idx] = max(0, min(n - k * B, B))
        bufs["peer_mask"][:] = mask
        bufs["applied"][:] = applied
        bufs["qdepth"][:] = np.reshape(qdepth, self._lead)
        kind = "scan" if self.scan else "burst"
        fn, key = self._program(kind, K)
        if prof is not None:
            prof.stop("host_encode")
            prof.start("device_dispatch")
            prof.start("input_transfer")
        packed = self._put(bufs["packed"])
        if prof is not None:
            prof.stop("input_transfer")
        return self._dispatch(
            prof, fn, key, packed, kind, nest_cells(taken, self._lead),
            self._norm_timeouts(())[0], K, bufs,
            applied0=applied if kind == "scan" else None)

    def finish(self, ticket: StepTicket) -> Dict[str, np.ndarray]:
        """Block on ``ticket``'s outputs and run every post-step host
        rule (requeue, replay, audit, flight, rebase, spans) — tickets
        MUST finish in dispatch order. ``step()``/``step_burst()`` are
        exactly ``finish(begin_*())``; the pipelined driver finishes
        from its readback thread while the next dispatch encodes."""
        assert self._tickets and self._tickets[0] is ticket, \
            "tickets must finish in dispatch (FIFO) order"
        # NOT popped here: until ``last`` below reflects this ticket's
        # appends, a concurrent ``begin_*`` must keep counting them via
        # reserved_appends() — an early pop would let its capacity
        # clamp over-admit (and a lockless pop would mutate the deque
        # under the dispatch thread's locked iteration)
        prof = self.profiler
        out = ticket.out
        scan = ticket.kind == "scan"
        fused = ticket.kind != "step"
        if prof is not None:
            prof.sync(out)              # fenced device_sync (opt-in)
            prof.start("quorum_wait")
        res = read_scalars(ticket)      # [*lead] per key
        # what is compiled only on request keeps a read of its own
        # (``readback_rest``): none in the default programs
        reads = 1
        if prof is not None:
            prof.start("readback_rest")
        if not fused and self._txn and out.txn_vote is not None:
            # serial dispatches only: the txn lane never rides
            # burst/scan programs (their keys stay untouched)
            res["txn_vote"] = np.asarray(out.txn_vote)
            reads += 1
        if prof is not None:
            prof.stop("readback_rest")
            prof.count("readback_arrays_total", reads)
            # program steps whose full-ring rescan branch ran: its
            # predicate is reduced over the groups of one program, so
            # the column reads alike in all of them
            prof.count("cfg_rescans_total", int(res["cfg_rescanned"].max()))
            prof.stop("quorum_wait")
            prof.start("post_readback")
            for scope in self._scopes:
                count_ring(prof, self.last, res,
                           cell_of(ticket.taken, scope),
                           self.cfg.n_slots, scope)
        if self._audit:
            # ingest BEFORE _maybe_rebase: the emitted indices are raw
            # (pre-rollover), consistent with the current rebased_total
            if fused:
                # each fused step emitted its own digest window: ingest
                # them in order so the tiling property (no gaps) holds
                get = (out.__getitem__ if scan
                       else lambda k: getattr(out, "commit"
                                              if k == "audit_commit"
                                              else k))
                a_s = np.asarray(get("audit_start"))   # [K, *lead]
                a_d = np.asarray(get("audit_digest"))  # [K, *lead, W]
                a_t = np.asarray(get("audit_term"))    # [K, *lead, W]
                a_c = np.asarray(get("audit_commit"))  # [K, *lead]
                for k in range(a_s.shape[0]):
                    self._ingest_audit(a_s[k], a_d[k], a_t[k], a_c[k])
                res["audit_start"] = a_s[-1]
                res["audit_digest"] = a_d[-1]
                res["audit_term"] = a_t[-1]
            else:
                for k in ("audit_start", "audit_digest", "audit_term"):
                    res[k] = np.asarray(getattr(out, k))
                self._ingest_audit(res["audit_start"],
                                   res["audit_digest"],
                                   res["audit_term"], res["commit"])
        if self._telemetry:
            # device-truth counters: reduce the dispatch's per-step
            # vectors (sum counters / min headroom over a fused burst),
            # fold into the host accumulator, and export device_*
            # registry series — all on THIS thread, which under the
            # pipelined driver is the readback thread (finish runs
            # there), so telemetry never rides the dispatch path
            from rdma_paxos_tpu.obs import device as _device
            tv = np.asarray(out["telemetry"] if scan
                            else out.telemetry, dtype=np.int64)
            res["telemetry"] = _device.reduce_steps(tv) if fused else tv
            _device.accumulate(self.device_counters, res["telemetry"])
            _device.ingest(self.obs, res["telemetry"])
        # ring-full backpressure / deposition: the appended set is a
        # PREFIX of ``taken`` — requeue the remainder in order
        # (submissions to non-leaders are dropped by design)
        takes = [(idx, cell_of(ticket.taken, idx)) for idx in self._cells]
        noted = self.txn is not None or self.topology is not None
        notes = []
        appended = 0        # scopes whose leader appended in this dispatch
        with self._host_lock:
            for idx, take in takes:
                if take and res["role"][idx] == int(Role.LEADER):
                    acc = int(res["accepted"][idx])
                    appended += acc > 0
                    self._stamp_appends(idx, take, acc, res)
                    if noted and acc > 0:
                        # (group, replica, ...): the single group is 0
                        notes.append(((0,) + idx)[-2:] + (
                            take[:acc], int(res["term"][idx]),
                            int(res["end"][idx])
                            + int(self._rebased_total[idx[:-1]])))
                    requeue_shortfall(cell_of(self.pending, idx), take,
                                      acc)
        # OUTSIDE _host_lock: note_appends takes the coordinator (or
        # controller) lock, which client threads hold while submitting
        # (coordinator -> cluster order) — calling it from the stamp
        # loop would be the reverse order, an ABBA deadlock against
        # kvs.transact()
        for note in notes:
            if self.txn is not None:
                self.txn.note_appends(*note)
            if self.topology is not None:
                self.topology.note_appends(*note)
        if prof is not None:
            self._count_appends(prof, appended)
            prof.stop("post_readback")
            prof.start("apply")
        self._replay_committed(
            res, scan_rows=((out["replay_data"], out["replay_meta"],
                             ticket.applied0) if scan else None))
        if prof is not None:
            prof.stop("apply")
            prof.start("finish_tail")
        if self._audit:
            self._record_flight(res, ticket.taken, ticket.timeouts,
                                burst_k=ticket.K)
        # the i32 rollover rewrites offsets host-side: it must never
        # run under dispatches still in flight (their outputs carry
        # pre-rollover offsets) — defer until the pipeline drains; the
        # threshold stays crossed, so the draining finish applies it
        with self._host_lock:
            self._tickets.popleft()     # retire: last now covers it
            self.inflight_dispatches -= 1
            if not self._tickets:
                self._maybe_rebase(res)
            self.last = res
        self.step_index += ticket.K
        self._observe_spans(res)
        self._observe(res)
        # read path: renew/revoke leases from this FINISHED step's
        # verified-quorum outputs, then serve due queued reads —
        # between pipelined tickets, never inside one
        if self.leases is not None:
            self.leases.observe(self, res)
        if self.reads is not None:
            self.reads.drain(self)
        if self.streams is not None:
            self.streams.observe(self, res)
        if self.governor is not None:
            self.governor.observe(self, res)
        if self.txn is not None:
            self.txn.observe(self, res)
        if self.topology is not None:
            self.topology.observe(self, res)
        if fused:
            B = self.cfg.batch_slots
            self._staging.release(ticket.bufs, [
                ((k,) + idx, min(B, len(take) - k * B))
                for idx, take in takes
                for k in range(-(-len(take) // B))])
        else:
            self._staging.release(ticket.bufs, [
                (idx, len(take)) for idx, take in takes])
        if prof is not None:
            prof.stop("finish_tail")
        return res

    def drain(self) -> Optional[Dict[str, np.ndarray]]:
        """Finish every in-flight ticket in order; returns the final
        result (or None when nothing was in flight)."""
        res = None
        while self._tickets:
            res = self.finish(self._tickets[0])
        return res

    def step(self, timeouts=()) -> Dict[str, np.ndarray]:
        """One protocol step for EVERY cell in one device dispatch
        (``timeouts`` as :meth:`begin_step` takes them). Returns
        ``[*lead]`` result arrays."""
        require_drained(self._tickets, "step")
        return self.finish(self.begin_step(timeouts))

    def step_burst(self, max_k: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
        """Drain the pending queues through up to ``max(K_TIERS)`` fused
        protocol steps in ONE device dispatch (multi-step driver mode —
        the host-side analog of the reference's busy commit loop). No
        election timeouts fire inside the burst; the caller must only
        burst while every trafficked group has a known leader. Returns
        the final step's outputs (``accepted`` aggregated over the
        burst). With ``scan=True`` the dispatch rides the K-window scan
        tier (same step outputs, consolidated readback + in-dispatch
        replay rows). ``max_k`` caps the tier at a lower ladder rung
        (the governor's dial)."""
        require_drained(self._tickets, "step_burst")
        return self.finish(self.begin_burst(max_k=max_k))

    # ---------------- host apply / rebase ----------------

    def _apply_window(self, idx: tuple, key, wm, wd, n: int) -> None:
        """``n`` fetched rows into cell ``idx``'s replay stream.

        Force-pruned laggards: when the ring no longer PHYSICALLY holds
        entry `applied` (a newer entry recycled its slot — possible
        once forced pruning let appends run ahead of a wedged member's
        apply), replaying would feed garbage to the app. The stamped
        global index (M_GIDX) proves integrity: fetched-entry gidx ==
        expected index, else flag for snapshot recovery and stop.
        Being merely below `head` is NOT sufficient to flag — the
        benign one-step lazy-push lag puts followers there routinely
        while their slots are still intact."""
        if n > 0 and int(wm[0, M_GIDX]) != self.applied[idx]:
            self.need_recovery.add(key)         # slot recycled
            return
        decode_window(wm, wd, n, cell_of(self.replayed, idx),
                      cell_of(self.frames, idx), self.collect_frames,
                      rebase=int(self._rebased_total[idx[:-1]]))
        self.applied[idx] += n

    def _replay_committed(self, res, scan_rows=None) -> None:
        """Host apply loop: fetch newly committed entries from the device
        log and 'replay' them (tests record them; the real driver hands
        them to the proxy) — apply_committed_entries analog
        (dare_server.c:1815-1974). All cells' windows ride ONE fetch
        dispatch per sweep (``ReplayFetch`` over the lead shape). Frame
        assembly rides the same decode pass, and where there are
        groups so does each group's share of the apply phase
        (``step_phase_us{phase=apply, group=g}``).

        ``scan_rows`` (the K-window scan tier): ``(wd_fut, wm_fut,
        applied0)`` replay rows that rode the scan dispatch itself,
        starting at the pre-dispatch apply cursors — consumed FIRST, so
        a scan step whose commit delta fits the staged window pays
        ZERO standalone fetch dispatches; any remainder falls through
        to the fetch loop below (identical decode → identical
        streams)."""
        t_scope: Dict[tuple, int] = {}
        if scan_rows is not None:
            wd_fut, wm_fut, applied0 = scan_rows
            staged = int(wm_fut.shape[-2])     # K-sized, <= replay_W
            wd_all = wm_all = None
            for idx, key in self._keyed:
                if key in self._wedged or key in self.need_recovery:
                    continue
                commit = int(res["commit"][idx])
                off = int(self.applied[idx]) - int(applied0[idx])
                n = int(min(commit - self.applied[idx], staged - off))
                if n <= 0 or off < 0:
                    continue
                if wd_all is None:      # lazy: transfer only if used
                    wd_all = np.asarray(wd_fut)
                    wm_all = np.asarray(wm_fut)
                t0 = time.perf_counter_ns()
                self._apply_window(idx, key, wm_all[idx][off:off + n],
                                   wd_all[idx][off:off + n], n)
                t_scope[idx[:-1]] = (t_scope.get(idx[:-1], 0)
                                     + time.perf_counter_ns() - t0)
        while True:
            todo = [(idx, key) for idx, key in self._keyed
                    if key not in self._wedged
                    and key not in self.need_recovery
                    and self.applied[idx] < int(res["commit"][idx])]
            if not todo:
                break
            starts = self._put(self.applied.astype(np.int32))
            need = max(int(res["commit"][idx] - self.applied[idx])
                       for idx, _ in todo)
            prof = self.profiler
            if prof is not None:
                prof.start("replay_fetch")
            # bind the fetch's log argument UNDER the host lock: the
            # pipelined dispatch thread donates the current state
            # buffers into the next step's dispatch, and a fetch bound
            # after that donation reads deleted buffers. Binding first
            # is sufficient — the runtime keeps an argument buffer
            # alive for an already-enqueued program — and the newer log
            # is safe to read: committed entries are immutable, the
            # rollover is deferred while tickets are in flight, and the
            # M_GIDX integrity check still guards slot recycling. Only
            # the BIND holds the lock; the blocking result read below
            # runs outside it so the dispatch path never stalls.
            with held(prof, self._host_lock, "fetch_lock_wait"):
                if prof is not None:
                    prof.start("fetch_enqueue")
                self._replay_fetch.need = need
                wd_fut, wm_fut = self._fetch_all(self.state.log, starts)
                if prof is not None:
                    prof.stop("fetch_enqueue")
            self.fetch_dispatches += 1
            if prof is not None:
                prof.start("fetch_read")
            # wm is read last: a wrapper over _fetch_all (the
            # benchmark's span) ends inside its conversion
            wd_all, wm_all = np.asarray(wd_fut), np.asarray(wm_fut)
            W = wm_all.shape[-2]        # the width the fetch chose
            if prof is not None:
                prof.stop("fetch_read")
                prof.stop("replay_fetch")
                prof.count("fetch_rows_total", W)
                prof.start("replay_decode")
            for idx, key in todo:
                t0 = time.perf_counter_ns()
                n = int(min(int(res["commit"][idx]) - self.applied[idx],
                            W))
                self._apply_window(idx, key, wm_all[idx], wd_all[idx], n)
                t_scope[idx[:-1]] = (t_scope.get(idx[:-1], 0)
                                     + time.perf_counter_ns() - t0)
            if prof is not None:
                prof.stop("replay_decode")
        if self.obs is not None and self.profiler is not None:
            from rdma_paxos_tpu.obs.metrics import LATENCY_BUCKETS_US
            for scope, ns in sorted(t_scope.items()):
                if scope:   # the single group's is the profiler's phase
                    self.obs.metrics.observe(
                        "step_phase_us", ns / 1e3,
                        buckets=LATENCY_BUCKETS_US, phase="apply",
                        **self._labels(scope))

    def _rebase_stalled_step(self, scope: tuple, res) -> None:
        """One post-threshold step passed with the scope's rollover
        delta pinned at 0 — count it, and surface the stall once it
        persists (the i32 ceiling is approaching and nothing will
        fire)."""
        self._rebase_stall_steps[scope] += 1
        steps = int(self._rebase_stall_steps[scope])
        if steps < self.REBASE_STALL_STEPS:
            return
        self._rebase_stalled[scope] += 1
        if self.obs is not None:
            from rdma_paxos_tpu.obs import trace as _trace
            labels = self._labels(scope)
            self.obs.metrics.inc("rebase_stalled", **labels)
            if steps == self.REBASE_STALL_STEPS:
                heads = [int(h) for h in res["head"][scope]]
                self.obs.trace.record(
                    _trace.REBASE_STALLED, **labels,
                    end_max=int(res["end"][scope].max()),
                    threshold=self.cfg.rebase_threshold,
                    min_head=min(heads), heads=heads, steps=steps)

    # holds-lock: _host_lock
    def _maybe_rebase(self, res) -> None:
        """Coordinated i32-offset rollover (LogConfig.rebase_threshold),
        a scope at a time: when a scope's max end crosses the
        threshold, subtract its minimum head from EVERY offset of ITS
        replicas and from their host apply cursors — invisible to the
        protocol (offsets are relative), it restores ~threshold entries
        of headroom, and other scopes' offsets are untouched. All
        crossing scopes shift in one elementwise pass. The in-process
        driver is omniscient, so the min is over ALL replicas (not just
        heard ones) — partition-safe: a partitioned laggard's low head
        simply defers the rollover until it recovers or is evicted.
        ``res`` is adjusted in place so callers observe post-rollover
        offsets."""
        threshold = self.cfg.rebase_threshold
        if int(res["end"].max()) < threshold:
            return
        ends = res["end"].max(axis=-1)      # a scope's
        # the slot of global index g is g % n_slots and entries do NOT
        # move: the subtraction must preserve the mapping, so the delta
        # is the min head rounded DOWN to a multiple of n_slots. A
        # replica already flagged need_recovery is EXCLUDED from the
        # min: it stopped replaying (snapshot install renumbers it from
        # the donor), and letting its frozen head pin the rollover
        # would wedge the whole cluster at the i32 ceiling. Its offsets
        # may go transiently negative — benign: the gap gate keeps it
        # from absorbing windows until recovery overwrites them.
        deltas = np.zeros(self._lead[:-1], np.int64)
        for scope in self._scopes:
            if ends[scope] < threshold:
                continue
            heads = [int(res["head"][idx]) for idx, key in self._keyed
                     if idx[:-1] == scope
                     and key not in self.need_recovery]
            delta = rebase_delta_of(heads, self.cfg.n_slots)
            if delta <= 0:
                self._rebase_stalled_step(scope, res)
                continue
            deltas[scope] = delta
        if not deltas.any():
            return
        self._apply_rebase(deltas)
        # rebound, not written in place: the packed row's views are
        # read-only. Keep the returned dict self-consistent:
        # audit_start is an index too (the ledger already ingested
        # pre-rollover)
        for k in ("head", "apply", "commit", "end", "audit_start"):
            if k in res:
                res[k] = res[k] - deltas[..., None].astype(res[k].dtype)
        for scope in self._scopes:
            d = int(deltas[scope])
            if not d:
                continue
            self.applied[scope] -= d
            self._rebases[scope] += 1
            self._rebased_total[scope] += d
            self._rebase_stall_steps[scope] = 0     # re-arm stall detection
            if self.obs is not None:
                from rdma_paxos_tpu.obs import trace as _trace
                labels = self._labels(scope)
                self.obs.metrics.inc("rebases_total", **labels)
                self.obs.metrics.inc("rebased_entries_total", d, **labels)
                self.obs.trace.record(_trace.REBASE_APPLIED, **labels,
                                      delta=d,
                                      rebases=int(self._rebases[scope]))

    # holds-lock: _host_lock
    def _apply_rebase(self, deltas: np.ndarray) -> None:
        """Elementwise per-scope offset subtraction
        (``consensus.snapshot.rebase_offsets``; invariants: delta <=
        that scope's min head, multiple of n_slots). Called from
        ``_maybe_rebase`` under the host lock. That one program over
        the state where it lies: the deltas go out through the put, a
        scope's in each of its rows, so that nothing moves between
        chips (eager operations would put their constants on one chip
        and spread them over the mesh)."""
        from rdma_paxos_tpu.consensus.snapshot import rebase_offsets
        rows = self._put(np.broadcast_to(
            deltas.astype(np.int32)[..., None], self._lead))
        self.state = rebase_offsets(self.state, rows)
        if self._state_sharding is not None:
            # the program's outputs follow its inputs; re-place all the
            # same so the next donated dispatch can pay no reshard
            # (rebases are rare — deferred until the pipeline drains)
            self.state = jax.device_put(self.state, self._state_sharding)

    # ------------------------------------------------------------------
    # silent-divergence auditing (obs/audit.py; audit=True clusters)
    # ------------------------------------------------------------------

    def _ingest_audit(self, starts, digests, terms, commits) -> None:
        """Feed one step's per-cell digest windows to the ledger,
        converted to ABSOLUTE indices (raw + the scope's own
        rebased_total: groups rebase independently — callers run this
        before _maybe_rebase so the two stay consistent)."""
        led = self.auditor
        led.obs = self.obs              # pick up a late-attached facade
        W = self.cfg.window_slots
        for scope in self._scopes:
            reb = int(self._rebased_total[scope])
            labels = self._labels(scope)
            s_l, c_l = starts[scope].tolist(), commits[scope].tolist()
            for r in range(self.R):
                start, commit = s_l[r], c_l[r]
                n = commit - start
                if n <= 0:
                    continue
                off = start - (commit - W)
                led.record_window(r, start + reb,
                                  digests[scope + (r,)][off:off + n],
                                  terms[scope + (r,)][off:off + n],
                                  commit + reb, step=self.step_index,
                                  **labels)

    def _record_flight(self, res, taken, timeouts,
                       burst_k: int = 1) -> None:
        """One flight-recorder entry per dispatch: the step's inputs
        (per-cell submitted batches), scalar outputs, host apply
        cursors, and per-cell digest heads — raw offsets plus the
        rebased_total in force, so the dump is self-describing.
        Values stay numpy arrays / payload bytes (fresh per step,
        copied where a later in-place mutation could reach them); the
        recorder converts to plain JSON data at dump time only."""
        entry = dict(
            step=self.step_index, burst_k=burst_k, timeouts=timeouts,
            rebased_total=self._rebased_total.copy(),
            inputs=taken,
            outputs={k: res[k].copy()
                     for k in ("term", "role", "leader_id", "head",
                               "apply", "commit", "end", "accepted")},
            applied=self.applied.copy(),
            digests=dict(start=res["audit_start"].copy(),
                         commit=res["commit"].copy(),
                         window=res["audit_digest"]))
        self.flight.record(entry)

    # ------------------------------------------------------------------
    # span hooks (host-side causal tracing — obs.spans; all no-ops
    # when no recorder is attached or nothing is sampled)
    # ------------------------------------------------------------------

    def _span_recorder(self):
        from rdma_paxos_tpu.obs.spans import active_recorder
        return active_recorder(self.obs)

    def _stamp_appends(self, idx: tuple, take, acc: int, res) -> None:
        """The accepted PREFIX of ``take`` landed at absolute indices
        ``[end-acc, end)`` on the leader at cell ``idx`` — stamp each
        sampled span with its ``(group, term, index)`` correlation
        key."""
        spans = self._span_recorder()
        if spans is None or not spans.open_count or acc <= 0:
            return
        scope = idx[:-1]
        end_abs = int(res["end"][idx]) + int(self._rebased_total[scope])
        term = int(res["term"][idx])
        # the loop below is the only per-OPERATION work of the
        # post-readback rules: everything about the cell is worked out
        # before it, and the call is positional (``**labels`` costs
        # half a microsecond an entry, a keyword 50 ns)
        rep = self._span_rep(*idx)
        group = scope[0] if scope else -1       # -1: the unsharded key
        replicas = [self._span_rep(*scope, r) for r in range(self.R)]
        for i, (_t, conn, req, _p) in enumerate(take[:acc]):
            spans.stamp_append(conn, req, term, end_abs - acc + i, rep,
                               replicas, group)

    def _observe_spans(self, res) -> None:
        """Advance every cell's commit/apply span frontiers (absolute,
        rebase-corrected — runs after ``_maybe_rebase`` so the offsets
        and ``rebased_total`` are mutually consistent)."""
        spans = self._span_recorder()
        if spans is None or not spans.open_count:
            return
        for idx in self._cells:
            rebased = int(self._rebased_total[idx[:-1]])
            rep = self._span_rep(*idx)
            spans.commit_advance(rep, int(res["commit"][idx]) + rebased)
            spans.apply_advance(rep, int(self.applied[idx]) + rebased)


class SimCluster(ClusterEngine):
    """N-replica protocol simulation of ONE consensus group: the
    engine's front end over the lead shape ``(R,)``, addressed by
    replica."""

    _PROGRAMS = {
        "step": ((), build_sim_step, build_spmd_step),
        "burst": (("burst",), build_sim_burst, build_spmd_burst),
        "scan": (("scan",), build_sim_scan, build_spmd_scan),
    }

    def __init__(self, cfg: LogConfig, n_replicas: int,
                 group_size: Optional[int] = None, *, mode: str = "sim",
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False,
                 fanout: str = "gather", stable_fast_path: bool = True,
                 audit: bool = False, flight_capacity: int = 64,
                 telemetry: bool = False, scan: bool = False,
                 txn: bool = False):
        mesh = sharding = None
        if mode == "spmd":
            mkey = (cfg, n_replicas, "mesh")
            if mkey not in STEP_CACHE:
                STEP_CACHE[mkey] = make_replica_mesh(n_replicas)
            mesh = STEP_CACHE[mkey]
            sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("replica"))
        # pluggable per-link fault model (rdma_paxos_tpu.chaos.faults
        # .LinkModel): when attached, each step's peer_mask INPUT is
        # rewritten host-side into the effective hear-matrix
        # (asymmetric breaks, seeded drop/delay/dup, crashed
        # replicas). Purely a data rewrite — compiled-step cache keys
        # are unchanged (tests/test_chaos.py guards it). The
        # dispatch-side step clock is what the model's per-step
        # randomness keys on.
        self.link_model = None
        super().__init__(
            cfg, (n_replicas,),
            stack_states(cfg, n_replicas, group_size or n_replicas),
            mode=mode, mesh=mesh, key_mesh=(), state_sharding=sharding,
            group_size=group_size, use_pallas=use_pallas,
            interpret=interpret, fanout=fanout,
            stable_fast_path=stable_fast_path, audit=audit,
            flight_capacity=flight_capacity, telemetry=telemetry,
            scan=scan, txn=txn)

    # ---------------- the engine's hooks ----------------

    def _norm_timeouts(self, timeouts) -> tuple:
        kept = [int(r) for r in timeouts]   # may be a one-shot iterable
        return kept, kept

    def _link_models(self) -> dict:
        return {} if self.link_model is None else {(): self.link_model}

    def _span_rep(self, r: int) -> int:
        return r

    def _count_appends(self, prof, appended: int) -> None:
        pass        # one group: nothing to tell apart

    def _observe(self, res) -> None:
        pass        # no per-group series

    # ---------------- client-side API ----------------

    def submit(self, replica: int, payload: bytes,
               etype: EntryType = EntryType.SEND, conn: int = 1,
               req_id: int = 0) -> None:
        """Queue a client entry for the next step on `replica` (it only
        enters the log if that replica is leader — proxy semantics)."""
        self._submit((replica,), [(int(etype), conn, req_id, payload)])

    def submit_many(self, replica: int,
                    entries: Sequence[Tuple[int, int, int, bytes]]
                    ) -> None:
        """Queue a whole intake batch of ``(etype, conn, req_id,
        payload)`` rows in one locked extend — the drivers' batched
        intake (a per-entry ``submit`` loop was a measurable share of
        the pump under full windows)."""
        self._submit((replica,), entries)

    def set_txn_watch(self, index: int, term: int) -> None:
        """Arm the prepare watch: every subsequent serial step reports a
        per-replica vote for whether ABSOLUTE log index ``index`` is
        committed under ``term`` (txn=True clusters only). The watch is
        sticky until :meth:`clear_txn_watch` — the coordinator re-reads
        the vote matrix each step while a prepare is outstanding."""
        self._arm_txn_watch((), index, term)

    def clear_txn_watch(self) -> None:
        self._disarm_txn_watch(())

    def partition(self, groups: Sequence[Sequence[int]]) -> None:
        """Split the cluster: replicas hear only same-group peers."""
        if self._fanout == "psum":
            self._check_psum_split(groups)
        self._partition((), groups)
        self._psum_split = self._fanout == "psum"

    def _check_psum_split(self, groups) -> None:
        """The O(W) psum fan-out SUMS every self-claimed leader's
        window (see replica_step's fanout docstring), so under it a
        split is sound only while a second leader cannot come to be:
        every leader of now is in ONE group, and no other group holds
        a majority of that leader's configuration, old or new (a
        member cut off alone: a lost machine). Anything else is
        rejected loudly."""
        last = self.last
        leaders = ([] if last is None else
                   [r for r in range(self.R)
                    if last["role"][r] == int(Role.LEADER)])
        home = [set(g) for g in groups if leaders and leaders[0] in g]
        ok = len(home) == 1 and home[0].issuperset(leaders)
        if ok:
            lead = leaders[0]
            for key in ("bitmask_old", "bitmask_new"):
                mask = int(last[key][lead])
                members = {r for r in range(self.R) if (mask >> r) & 1}
                ok = ok and all(
                    len(members & set(g)) <= len(members) // 2
                    for g in groups if set(g) != home[0])
        if not ok:
            raise ValueError(
                "partitions cannot be modeled with fanout='psum' "
                "unless every leader stays in one group and no other "
                "group could elect one; build the cluster with "
                "fanout='gather'")

    def heal(self) -> None:
        self._heal(())

    def wedge_apply(self, r: int) -> None:
        """Freeze replica ``r``'s apply progress (models a wedged app:
        the host stops consuming committed entries while the replica
        keeps acking windows)."""
        self._wedged.add(r)

    def unwedge_apply(self, r: int) -> None:
        self._wedged.discard(r)

    def redigest(self, replica: int, lo: int, hi: int) -> int:
        """Range re-digest backfill: recompute the digest chain of
        replica ``replica``'s committed entries ``[lo, hi)`` (raw
        offsets) on device and feed it to the ledger — the repair
        pipeline's coverage-restoration pass. Serial-path only (the
        shared ``require_drained`` rule applies)."""
        return run_redigest(self, self.state.log.buf[replica], lo, hi,
                            group=0, rebased_total=self.rebased_total,
                            replica=replica)

    # ---------------- inspection ----------------

    def replica_device(self, r: int):
        """The chip holding replica ``r``'s state rows (``mode="spmd"``:
        one replica per chip), or None when every replica is a vmap
        row on the default device. Host-side state that belongs to one
        replica (its state-machine table) is placed by this."""
        if self._mode != "spmd":
            return None
        return self.mesh.devices[r]

    def leader(self) -> int:
        return self._leader(())

    def run_until_elected(self, candidate: int, max_steps: int = 5) -> int:
        return self._elect((candidate,), [candidate], max_steps)
