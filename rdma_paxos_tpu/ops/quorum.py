"""Quorum commit scan — the hot op of the consensus core.

Reference: on every iteration of the replication loop the DARE leader decides
commit by scanning entries in ``(commit, end]`` and counting per-entry ACK
bytes that followers RDMA-wrote into the entry's ``reply[]`` array; an entry
is committed iff the count reaches a majority, and during membership
transitions iff it reaches *both* majorities (``dare_ibv_rc.c:1725-1758``,
dual-quorum ``:2799-2957``; ``wait_for_majority`` ``:2768-2964``).

TPU-native formulation: followers acknowledge by advertising their ``end``
offset (an ``all_gather``), so the per-entry ACK bitmap is implicit:
``ack[j, r] = (end_r > commit + j)``. The scan materializes that bitmap as a
``[W, R_PAD]`` tile in VMEM, popcounts each row under the member bitmask(s),
takes the contiguous committed prefix, and applies the Raft current-term
guard (a leader only commits entries of its own term; earlier-term entries
commit transitively — the reason the reference leader appends a blank NOOP
entry on election, ``dare_server.c:1403-1491``). The result is a **monotone**
commit-index advance.

Two interchangeable implementations:

* :func:`commit_scan_ref` — pure ``jax.numpy``; runs anywhere, used as the
  test oracle and the CPU-simulation path.
* :func:`commit_scan_pallas` — Pallas TPU kernel; one VMEM tile, VPU-only.

Both are pure element-wise/reduction code on a ``[W, R_PAD]`` tile, so XLA
also fuses the reference version well; the kernel exists to keep the scan in
a single VMEM-resident pass. Production paths (SimCluster,
HostReplicaDriver) default to the Pallas kernel on TPU — the same code
path as the benches.

FUSION RESULT (measured, round 3): extending the kernel across the whole
ack-aggregate + window-select + commit stage is a NULL result by
construction and by measurement. The ack aggregate is a
``lax.all_gather`` and the window select consumes another gather's
output — cross-replica collectives that cannot live inside a
single-replica Pallas kernel without remote DMAs; everything element-wise
around them is already fused by XLA into the collectives' prologue/
epilogue. Measured on TPU v5e (64-step scans, batch 1024, R=3): full
step 479 µs with the Pallas scan vs 465 µs with the jnp scan — parity
within run-to-run noise (~3%), confirming the scan tile ([W, 128] i32)
is nowhere near the step's critical path (the window gather/scatter and
ring scans are). The kernel is kept as the single-VMEM-pass form and the
seed for a future multi-chip kernel that overlaps the quorum scan with
the window DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R_PAD = 128   # lane-width padding of the replica axis (MAX_SERVER_COUNT=13)


def _scan_math(ends, commit, my_term, my_end, terms_win, bm_old, bm_new,
               transit, maj_old, maj_new, W):
    """Shared scan body: ends [R_PAD] i32 (non-members already zeroed) ->
    new commit (scalar i32, >= commit)."""
    with jax.named_scope("commit_scan"):
        j = jax.lax.broadcasted_iota(jnp.int32, (W, R_PAD), 0)  # entry row
        r = jax.lax.broadcasted_iota(jnp.int32, (W, R_PAD), 1)  # replica col

        in_old = jnp.bitwise_and(
            jnp.right_shift(bm_old, r.astype(jnp.uint32)),
            1).astype(jnp.int32)
        in_new = jnp.bitwise_and(
            jnp.right_shift(bm_new, r.astype(jnp.uint32)),
            1).astype(jnp.int32)

        ack = (ends[None, :] > commit + j).astype(jnp.int32)  # [W, R_PAD]
        cnt_old = jnp.sum(ack * in_old, axis=1)               # [W]
        cnt_new = jnp.sum(ack * in_new, axis=1)

        jcol = jnp.arange(W, dtype=jnp.int32)
        ok = (cnt_new >= maj_new) & (commit + jcol < my_end)
        # boolean algebra, not where-on-bool (Mosaic can't legalize i1
        # selects)
        ok = ok & ((transit <= 0) | (cnt_old >= maj_old))

        # contiguous committed prefix length = first False position (plain
        # min reduction — integer arg-reductions don't lower on the TPU VPU)
        prefix = jnp.min(jnp.where(ok, W, jcol))

        # Raft term guard: commit only up to the last current-term entry in
        # the prefix (entries of older terms commit transitively below it).
        eligible = (jcol < prefix) & (terms_win == my_term)
        lastj = jnp.max(jnp.where(eligible, jcol, -1))
        return jnp.where(lastj >= 0, commit + lastj + 1,
                         commit).astype(jnp.int32)


def commit_scan_ref(
    ends: jax.Array,        # [R_PAD] i32 — gathered end offsets, 0 for
                            #   non-members / unreachable replicas
    commit: jax.Array,      # scalar i32 — current commit index
    my_term: jax.Array,     # scalar i32 — leader's term
    my_end: jax.Array,      # scalar i32 — leader's end
    terms_win: jax.Array,   # [W] i32 — terms of entries commit .. commit+W-1
    bitmask_old: jax.Array,  # scalar u32
    bitmask_new: jax.Array,  # scalar u32
    transit: jax.Array,     # scalar i32 — 1 if joint consensus active
    maj_old: jax.Array,     # scalar i32
    maj_new: jax.Array,     # scalar i32
) -> jax.Array:
    W = terms_win.shape[0]
    return _scan_math(ends, commit, my_term, my_end, terms_win,
                      bitmask_old, bitmask_new, transit, maj_old, maj_new, W)


def _kernel(scal_ref, ends_ref, terms_ref, out_ref):
    W = terms_ref.shape[1]
    result = _scan_math(
        ends=ends_ref[0, :],
        commit=scal_ref[0, 0],
        my_term=scal_ref[0, 1],
        my_end=scal_ref[0, 2],
        terms_win=terms_ref[0, :],
        bm_old=scal_ref[0, 3].astype(jnp.uint32),
        bm_new=scal_ref[0, 4].astype(jnp.uint32),
        transit=scal_ref[0, 5],
        maj_old=scal_ref[0, 6],
        maj_new=scal_ref[0, 7],
        W=W,
    )
    # VPU stores are vector-shaped: broadcast the scalar across the row
    out_ref[:, :] = jnp.broadcast_to(result, (1, out_ref.shape[1]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit_scan_pallas(ends, commit, my_term, my_end, terms_win,
                       bitmask_old, bitmask_new, transit, maj_old, maj_new,
                       *, interpret: bool = False) -> jax.Array:
    """Pallas TPU version of :func:`commit_scan_ref` (same signature).

    All operands ride in VMEM as (1, lane)-shaped i32 rows — no SMEM
    blocks — so the call stays batchable: under ``vmap`` (the single-chip
    multi-replica simulation) the batch dim lifts into the Pallas grid.
    """
    W = terms_win.shape[0]
    scal = jnp.zeros((1, R_PAD), jnp.int32)
    for i, v in enumerate([commit, my_term, my_end, bitmask_old,
                           bitmask_new, transit, maj_old, maj_new]):
        scal = scal.at[0, i].set(v.astype(jnp.int32))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, R_PAD), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(scal, ends.reshape(1, R_PAD), terms_win.reshape(1, W))
    return out[0, 0]


def commit_scan(*args, use_pallas: bool = False, interpret: bool = False):
    """Dispatcher: Pallas on TPU, jnp elsewhere (same semantics)."""
    if use_pallas:
        return commit_scan_pallas(*args, interpret=interpret)
    return commit_scan_ref(*args)
