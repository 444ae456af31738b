"""The step touches the rows it needs, not the ring.

In a steady step nothing reads or writes more than O(window_slots)
rows of a replica's ring: every read goes rows first
(``consensus.log.rows_at``), and the one full-ring pass, the config
rescan, sits in a branch of a REAL ``cond`` in every mapping (under
``vmap`` too: its predicate is reduced over the replica axis, and under
the group engines' second ``vmap`` over the group batch axis as well,
so it is unbatched). Checked on the jaxpr of each builder the
benchmark's cells and the other engines use, so no chip is needed:
outside a ``cond`` branch the ring may only be gathered from, scattered
into or reshaped whole.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import META_W
from rdma_paxos_tpu.consensus.step import GROUP_BATCH_AXIS, make_step_input
from rdma_paxos_tpu.parallel import mesh as pm

# n_slots appears in no other dimension of any program below
CFG = LogConfig(n_slots=512, slot_bytes=64, window_slots=16, batch_slots=4)
N = CFG.n_slots
K = 2

# what may touch the ring outside a cond branch: row-indexed reads and
# writes. A size-preserving slice / reshape / broadcast is the mappings'
# own unit-axis bookkeeping (``x[0]``, ``x[None]``), not a view.
ROW_OPS = {"gather", "scatter", "dynamic_slice", "dynamic_update_slice"}
WHOLE_OPS = {"slice", "squeeze", "reshape", "broadcast_in_dim",
             "expand_dims", "copy"}
# the rescan's signature: a reduction over every slot
RESCAN_OPS = {"reduce_max", "reduce_or", "argmax"}


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _is_ring(v):
    return N in getattr(getattr(v, "aval", None), "shape", ())


def _walk(jaxpr, in_cond, seen):
    """Every leaf equation with a ring-sized operand, as ``(inside a
    cond branch, primitive, operand shapes, output shapes)``."""
    for e in jaxpr.eqns:
        subs = list(_subjaxprs(e))
        ring = [v for v in e.invars if _is_ring(v)]
        if not subs and ring:
            seen.append((in_cond, e.primitive.name,
                         [v.aval.shape for v in ring],
                         [v.aval.shape for v in e.outvars]))
        for s in subs:
            _walk(s, in_cond or e.primitive.name == "cond", seen)


def _eqns(jaxpr, name):
    """Every equation of primitive ``name``, nested ones included."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            found.append(e)
        for s in _subjaxprs(e):
            found += _eqns(s, name)
    return found


def _state(R):
    return jax.eval_shape(lambda: pm.stack_states(CFG, R, R))


def _step_args(R):
    inp = jax.eval_shape(lambda: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (R,) + x.shape),
        make_step_input(CFG, R)))
    return _state(R), inp


def _burst_args(R):
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    return (_state(R),
            sds((K, R, CFG.batch_slots, CFG.slot_words), i32),
            sds((K, R, CFG.batch_slots, META_W), i32),
            sds((K, R), i32), sds((R, R), i32), sds((R,), i32),
            sds((R,), i32))


G = 3   # groups a device holds in the group mappings


def _grouped(args, n_groups):
    """A single-group builder's abstract arguments with the group axis
    put before the replica axis of every leaf."""
    def lead(x, at):
        return jax.ShapeDtypeStruct(
            x.shape[:at] + (n_groups,) + x.shape[at:], x.dtype)
    if len(args) == 2:          # (state, StepInput)
        return jax.tree.map(lambda x: lead(x, 0), args)
    # state, then the K-stacked inputs, then the per-replica ones
    return (jax.tree.map(lambda x: lead(x, 0), args[0]),
            *(lead(x, 1) for x in args[1:4]),
            *(lead(x, 0) for x in args[4:]))


def _group_program(kind, R):
    """The six group mappings: ``G`` groups a device, and for the
    ``spmd_group_*`` ones as many device rows of a ``(group, replica)``
    CPU mesh as the devices allow."""
    step = kind.endswith("_step")
    args = _step_args(R) if step else _burst_args(R)
    kw = dict(fanout="psum")
    if kind.endswith("_scan"):
        kw["replay_slots"] = 32
    if kind.startswith("sim_"):
        return getattr(pm, "build_" + kind)(CFG, R, **kw), _grouped(args, G)
    shards = min(2, len(jax.devices()) // R)
    if shards < 1:
        pytest.skip(f"a (group, replica) mesh needs {R} devices")
    if step:
        kw["elections"] = False
    mesh = pm.build_mesh_2d(shards, R)
    return (getattr(pm, "build_" + kind)(CFG, R, mesh, **kw),
            _grouped(args, G * shards))


def _program(kind, R):
    """``(jitted program, abstract arguments)`` of one builder."""
    if "_group_" in kind:
        return _group_program(kind, R)
    if kind == "sim_step":
        return pm.build_sim_step(CFG, R, fanout="psum"), _step_args(R)
    if kind == "sim_stable_step":
        return (pm.build_sim_step(CFG, R, fanout="psum", elections=False),
                _step_args(R))
    if kind == "sim_burst":
        return pm.build_sim_burst(CFG, R, fanout="psum"), _burst_args(R)
    if kind == "sim_scan":
        return (pm.build_sim_scan(CFG, R, replay_slots=32, fanout="psum"),
                _burst_args(R))
    mesh = pm.make_replica_mesh(R)
    if kind == "spmd_step":
        return (pm.build_spmd_step(CFG, R, mesh, fanout="psum",
                                   elections=False), _step_args(R))
    assert kind == "spmd_burst", kind
    return pm.build_spmd_burst(CFG, R, mesh, fanout="psum"), _burst_args(R)


KINDS = ("sim_step", "sim_stable_step", "sim_burst", "sim_scan",
         "spmd_step", "spmd_burst",
         "sim_group_step", "sim_group_burst", "sim_group_scan",
         "spmd_group_step", "spmd_group_burst", "spmd_group_scan")


@pytest.mark.parametrize("R", [3, 7])
@pytest.mark.parametrize("kind", KINDS)
def test_steady_program_reads_rows_and_rescans_under_a_cond(kind, R):
    fn, args = _program(kind, R)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr

    seen = []
    _walk(jaxpr, False, seen)
    assert any(not c and p in ("gather", "dynamic_slice")
               for c, p, _i, _o in seen), "the walk found no ring read"
    for in_cond, prim, ins, outs in seen:
        if in_cond or prim in ROW_OPS:
            continue
        whole = (prim in WHOLE_OPS and len(outs) == 1
                 and math.prod(outs[0]) == math.prod(ins[0]))
        assert whole, (
            f"{kind} R={R}: `{prim}` over the whole ring {ins} -> {outs} "
            "outside a cond branch: read rows first (log.rows_at)")

    # the conditional is real in this mapping, and the rescan is in it
    conds = _eqns(jaxpr, "cond")
    rescans = []
    for e in conds:
        for br in e.params["branches"]:
            inside = []
            _walk(br.jaxpr, True, inside)
            if {p for _c, p, _i, _o in inside} >= RESCAN_OPS:
                rescans.append(e)
    assert len(rescans) == 1, (
        f"{kind} R={R}: {len(conds)} cond(s), {len(rescans)} holding the "
        "full-ring config rescan: a batched predicate turns it into a "
        "select_n that runs every step")
    # and nothing else in the program reduces over every slot
    assert not [p for c, p, _i, _o in seen if not c and p in RESCAN_OPS]


@pytest.mark.parametrize("R", [3, 7])
@pytest.mark.parametrize("kind", KINDS)
def test_only_group_mappings_reduce_over_the_group_batch(kind, R):
    """The predicate's reduction over groups is the one ``pmax`` of any
    program: the single-group builders trace none and name the group
    batch axis nowhere, the group mappings trace exactly one, of one
    scalar a group, over the ``vmap``'s own axis alone: on a (group,
    replica) mesh nothing crosses chips for it."""
    fn, args = _program(kind, R)
    closed = jax.make_jaxpr(fn)(*args)
    text = str(closed)
    # vmap has resolved its own name to a position, so it is in no
    # program's text: not as a mesh axis, not as a collective's
    assert GROUP_BATCH_AXIS not in text
    pmaxes = _eqns(closed.jaxpr, "pmax")
    if "_group_" not in kind:
        assert not pmaxes and "pmax" not in text
        return
    assert len(pmaxes) == 1, pmaxes
    (e,) = pmaxes
    assert all(isinstance(a, int) for a in e.params["axes"]), e.params
    (v,) = e.invars
    # a device's own G groups, whatever the mesh's group axis holds
    assert v.aval.shape == (G,) and v.aval.dtype == jnp.int32


@pytest.mark.parametrize("lead", [(3,), (7,), (G, 3)],
                         ids=["R3", "R7", "G3R3"])
def test_replay_fetch_slices_its_rows_out_of_the_ring(lead):
    """The standalone replay fetch (``runtime.sim.ReplayFetch``, both
    engines) reads a window as SLICES of consecutive slots, at every
    width: each operation that touches the ring takes ``W`` slots a
    ring row from ONE start index a row, and hands on ``W`` rows. A
    gather of ``W`` single rows (``rows_at``) makes the v5e convert the
    whole slot-minor ring before it (``copy.4``: PERF.md section 6, PR
    48); what the chip's compiler makes of this program is checked
    there, this is what can be seen without it."""
    from rdma_paxos_tpu.consensus.log import Log
    from rdma_paxos_tpu.runtime.sim import ReplayFetch
    cols = CFG.slot_words + META_W
    log = Log(buf=jax.ShapeDtypeStruct(lead + (N, cols), jnp.int32))
    starts = jax.ShapeDtypeStruct(lead, jnp.int32)
    fetch = ReplayFetch(64, len(lead))
    assert fetch.widths == (4, 16, 64)
    for W, fn in fetch.programs.items():
        jaxpr = jax.make_jaxpr(fn)(log, starts).jaxpr
        seen = []
        _walk(jaxpr, False, seen)
        assert {p for _c, p, _i, _o in seen} == {"gather", "slice"}, seen
        for _c, prim, ins, outs in seen:
            assert ins == [lead + (N, cols)]
            assert outs == [lead + (W, cols)], (W, prim, outs)
        (g,) = [e for e in _eqns(jaxpr, "gather") if _is_ring(e.invars[0])]
        # one (slot, column) start a ring row: W slots from there
        assert g.params["slice_sizes"] == (1,) * len(lead) + (W, cols)
        assert g.invars[1].aval.shape == lead + (2,)
