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
into or reshaped whole, and every such read or write takes WHOLE rows
(no column view of the ring: ``Log.data`` / ``Log.meta`` went with PR
50).

A ring row is ``[payload | metadata | zero pad]``, a multiple of 128
words wide, so that the v5e rests the ring row-major and the step
compiles no layout copy of it: checked on the program compiled for a
DESCRIBED v5e (no chip needed) at the cells' geometry.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import META_W, ROW_ALIGN, Log, row_words
from rdma_paxos_tpu.consensus.step import GROUP_BATCH_AXIS, arg_layout
from rdma_paxos_tpu.parallel import mesh as pm

# n_slots appears in no other dimension of any program below
CFG = LogConfig(n_slots=512, slot_bytes=64, window_slots=16, batch_slots=4)
N = CFG.n_slots
COLS = row_words(CFG.slot_words)
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


def _ring_eqns(jaxpr, in_cond=False):
    """Every leaf equation with a ring-sized operand, as ``(inside a
    cond branch, equation)``."""
    for e in jaxpr.eqns:
        subs = list(_subjaxprs(e))
        if not subs and any(_is_ring(v) for v in e.invars):
            yield in_cond, e
        for s in subs:
            yield from _ring_eqns(s, in_cond or e.primitive.name == "cond")


def _walk(jaxpr, in_cond, seen):
    """:func:`_ring_eqns` as ``(inside a cond branch, primitive, operand
    shapes, output shapes)``."""
    for c, e in _ring_eqns(jaxpr, in_cond):
        seen.append((c, e.primitive.name,
                     [v.aval.shape for v in e.invars if _is_ring(v)],
                     [v.aval.shape for v in e.outvars]))


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


def _packed(R, k):
    """The ONE host-fed argument of a ``k``-step dispatch, abstract."""
    return jax.ShapeDtypeStruct(arg_layout(CFG, R, k).shape((R,)), jnp.int32)


def _step_args(R):
    return _state(R), _packed(R, 1)


def _burst_args(R):
    return _state(R), _packed(R, K)


G = 3   # groups a device holds in the group mappings


def _grouped(args, n_groups):
    """A single-group builder's abstract arguments with the group axis
    put before the replica axis of every leaf."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_groups,) + x.shape, x.dtype),
        args)


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
    from rdma_paxos_tpu.runtime.sim import ReplayFetch
    cols = COLS
    log = Log(buf=jax.ShapeDtypeStruct(lead + (N, cols), jnp.int32),
              slot_words=CFG.slot_words)
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
        # what leaves the device is the live columns, not the pad
        out = jax.eval_shape(fn, log, starts)
        assert out.shape == lead + (W, CFG.slot_words + META_W)


# ---------------------------------------------------------------------------
# the row's width (PR 50)
# ---------------------------------------------------------------------------

CELLS = LogConfig(n_slots=131072, slot_bytes=512, window_slots=1024,
                  batch_slots=1024)


@pytest.mark.parametrize("slot_bytes,want", [
    (512, 256), (64, 128), (32, 128), (480, 128), (484, 256), (992, 256),
    (996, 384)])
def test_ring_row_is_a_multiple_of_128_words(slot_bytes, want):
    """``META_W`` stays 8 (the host's batch arrays and the benchmark's
    ``entry_bytes`` are ``slot + 4 * META_W``); the row is ``slot_words
    + META_W`` rounded UP to the lanes, and not padded where it already
    is a multiple (480 B: 120 + 8 = 128)."""
    assert META_W == 8 and ROW_ALIGN == 128
    cfg = LogConfig(n_slots=64, slot_bytes=slot_bytes, window_slots=16,
                    batch_slots=8)
    cols = row_words(cfg.slot_words)
    assert cols == want and cols % ROW_ALIGN == 0
    assert 0 <= cols - (cfg.slot_words + META_W) < ROW_ALIGN
    st = jax.eval_shape(lambda: pm.stack_states(cfg, 3, 3))
    assert st.log.buf.shape == (3, 64, want)
    assert st.log.slot_words == cfg.slot_words
    # static: no leaf, so the host's state row count is the parent's
    assert len(jax.tree.leaves(st.log)) == 1
    assert row_words(CELLS.slot_words) == 256


@pytest.mark.parametrize("R", [3, 7])
@pytest.mark.parametrize("kind", KINDS)
def test_no_column_view_of_the_ring_outside_the_rescan(kind, R):
    """Outside a ``cond`` branch every gather, slice and scatter over
    the ring takes or writes rows of the ring's FULL width: the live
    columns are taken from what was gathered (``log._split``,
    ``log.live_rows``), the pad is written with the row (``log._fuse``).
    A read of fewer columns is a column view of the ring, which the
    v5e materialises ring-sized before the gather it feeds."""
    fn, args = _program(kind, R)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr

    found = [e for in_cond, e in _ring_eqns(jaxpr)
             if not in_cond and _is_ring(e.invars[0])]
    assert found
    for e in found:
        prim, ring = e.primitive.name, e.invars[0].aval.shape
        assert ring[-1] == COLS, (prim, ring)
        if prim in ("gather", "dynamic_slice"):
            assert e.params["slice_sizes"][-1] == COLS, (
                f"{kind} R={R}: `{prim}` reads {e.params['slice_sizes']} "
                f"of the ring {ring}: a column view")
        elif prim == "scatter":
            assert e.invars[2].aval.shape[-1] == COLS, (
                kind, R, e.invars[2].aval.shape)
        elif prim == "dynamic_update_slice":
            assert e.invars[1].aval.shape[-1] == COLS
        else:
            assert e.outvars[0].aval.shape[-1] == COLS, (prim, ring)


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described v5e:2x2 (the TPU compiler is installed
    here; nothing runs). Executables compiled for it cannot be read
    back from the persistent cache without a chip, so the cache is off
    round these compiles."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _computations(hlo):
    """``({name: lines}, the entry's name)`` of an optimised HLO
    module's computations."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        if line.startswith("}"):
            cur = None
        elif line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            cur = words[words[0] == "ENTRY"].lstrip("%")
            entry = cur if words[0] == "ENTRY" else entry
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _under_a_conditional(comps):
    """Names of the computations reachable from a ``conditional``'s
    branches (the rescan's taken branch and what it calls)."""
    calls = re.compile(
        r"(?:branch_computations|true_computation|false_computation|"
        r"calls|to_apply|body|condition)=\{?([%\w.\-, ]+)\}?")

    def callees(line):
        return [n.strip().lstrip("%") for m in calls.finditer(line)
                for n in m.group(1).split(",")]
    todo = [n for ls in comps.values() for line in ls
            if " conditional(" in line for n in callees(line)]
    under = set()
    while todo:
        n = todo.pop()
        if n in under or n not in comps:
            continue
        under.add(n)
        todo += [c for line in comps[n] for c in callees(line)]
    return under


def test_compiled_for_a_v5e_the_step_copies_no_ring(v5e):
    """The sim burst at the cells' geometry, compiled for the chip: the
    ring parameter rests row-major (``{2,1,0}``: a 256-word row is a
    whole number of 128-lane tiles), and outside the rescan's
    ``conditional`` no ``copy`` or ``transpose`` is ring-sized. At 136
    columns the runtime rested the ring slot-minor (``{1,2,0}``) and
    the step converted all of it on entry and back on exit, every
    dispatch: 1.87 of c50's 3.1 ms step (PERF.md section 6, PR 50).
    The program is ``jit_burst`` and takes ONE host-fed parameter
    beside the state (PR 51: six arrays a put cost 1.65 ms a
    dispatch, whatever they held)."""
    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e)
    R = 3
    state = jax.tree.map(on_chip, jax.eval_shape(
        lambda: pm.stack_states(CELLS, R, R)))
    shape = arg_layout(CELLS, R, K).shape((R,))
    args = (state, on_chip(jax.ShapeDtypeStruct(shape, jnp.int32)))
    fn = pm.build_sim_burst(CELLS, R, fanout="psum", use_pallas=True)
    hlo = fn.lower(*args).compile().as_text()
    # the name the trace readers select the step's program by
    assert hlo.startswith("HloModule jit_burst,"), hlo[:80]
    comps, entry = _computations(hlo)
    # ONE host-fed parameter beside the state: the packed argument
    n_state = len(jax.tree.leaves(state))
    params = [line for line in comps[entry] if " parameter(" in line]
    assert len(params) == n_state + 1, len(params)
    (packed,) = [line for line in params if "s32[%d,%d,%d]" % shape in line]
    assert "packed" in packed, packed
    ring = "s32[%d,%d,%d]" % (R, CELLS.n_slots, row_words(CELLS.slot_words))
    (param,) = [line for line in comps[entry]
                if " parameter(" in line and ring in line]
    assert ring + "{2,1,0:" in param, param
    under = _under_a_conditional(comps)
    assert under, "no conditional: where is the rescan?"
    moved = [(n, line.strip()[:120]) for n, ls in comps.items()
             for line in ls
             if re.search(r" = s32\[%d,%d,\d+\]\S* (copy|transpose)\("
                          % (R, CELLS.n_slots), line)]
    assert [m for m in moved if m[0] not in under] == [], moved
