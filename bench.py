"""Headline benchmark: committed client entries per second through the full
consensus hot path (append → fan-out → ack → quorum scan → commit), run on
real TPU hardware.

Methodology mirrors the reference's ``redis-benchmark -t set`` against the
leader (``benchmarks/run.sh:73-82``) at the consensus layer: every committed
entry corresponds to one replicated client operation. A 3-replica group runs
on one chip via the vmapped protocol step (identical collective semantics to
the multi-chip shard_map path); K steps are driven per jit call through
``lax.scan`` with the host apply echo folded into the carry, so the number
printed is device-side protocol throughput including quorum scan and commit
advance — the north-star metric of BASELINE.md (target ≥1M ops/s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp

from rdma_paxos_tpu.config import LogConfig
from rdma_paxos_tpu.consensus.log import M_LEN, M_TYPE, META_W, EntryType
from rdma_paxos_tpu.consensus.step import StepInput, replica_step
from rdma_paxos_tpu.parallel.mesh import REPLICA_AXIS, stack_states

K = 64          # protocol steps per jit call
# ring sized 4x the window: gather/scatter cost scales with ring rows (a
# right-sized ring nearly doubles throughput vs a 16k-slot ring), while the
# ring must absorb one full batch per step plus the one-step apply lag
# without hitting the capacity clamp. Geometry swept on hardware
# (round 3): 2048-entry batches at 128-byte slots measure ~1.6x the
# round-2 1024/256 shape back-to-back in one session; 8192-entry windows
# exceed the Pallas kernel's scoped-VMEM tile limit.
CFG = LogConfig(n_slots=8192, slot_bytes=128, window_slots=2048,
                batch_slots=2048)
BASELINE_OPS = 1_000_000.0   # BASELINE.md north-star: 1M Redis SET ops/s


def build(R, cfg=None):
    cfg = cfg or CFG
    use_pallas = jax.default_backend() == "tpu"
    # full-connectivity bench: the O(W) psum fan-out is the production
    # configuration (see replica_step's fanout docstring)
    core = functools.partial(replica_step, cfg=cfg, n_replicas=R,
                             axis_name=REPLICA_AXIS, use_pallas=use_pallas,
                             fanout="psum")
    vstep = jax.vmap(core, in_axes=(0, 0), axis_name=REPLICA_AXIS)

    B = cfg.batch_slots
    # batch arrays are PASSED AS ARGUMENTS, never closure-captured: a
    # captured jnp array is embedded in the lowered module as a literal,
    # so compile time, the executable and its cache entry all grow by
    # the array (here [R, B, slot_words] words per program)
    batch_data = jnp.zeros((R, B, cfg.slot_words), jnp.int32).at[0, :, 0].set(
        jnp.arange(B))  # "SET k v" payload stand-in
    batch_meta = jnp.zeros((R, B, META_W), jnp.int32)
    batch_meta = batch_meta.at[:, :, M_TYPE].set(int(EntryType.SEND))
    batch_meta = batch_meta.at[:, :, M_LEN].set(16)
    peer = jnp.ones((R, R), jnp.int32)

    def one(carry, _):
        # host apply echo folded into the carry: applies track commit, so
        # pruning frees ring space exactly as the real driver does
        state, batch_data, batch_meta, peer = carry
        inp = StepInput(
            batch_data=batch_data,
            batch_meta=batch_meta,
            batch_count=jnp.full((R,), B, jnp.int32),
            timeout_fired=jnp.zeros((R,), jnp.int32),
            peer_mask=peer,
            apply_done=state.commit,
            queue_depth=jnp.zeros((R,), jnp.int32),
        )
        state, out = vstep(state, inp)
        return (state, batch_data, batch_meta, peer), out.commit[0]

    @jax.jit
    def run_k(state, batch_data, batch_meta, peer):
        carry, commits = jax.lax.scan(
            one, (state, batch_data, batch_meta, peer), None, length=K)
        return carry[0], commits

    @jax.jit
    def elect(state, batch_data, batch_meta, peer):
        inp = StepInput(
            batch_data=batch_data, batch_meta=batch_meta,
            batch_count=jnp.zeros((R,), jnp.int32),
            timeout_fired=jnp.zeros((R,), jnp.int32).at[0].set(1),
            peer_mask=peer, apply_done=state.commit,
            queue_depth=jnp.zeros((R,), jnp.int32))
        state, _ = vstep(state, inp)
        return state

    return elect, run_k, (batch_data, batch_meta, peer)


def run_group(R, cfg=None, reps=32):
    elect, run_k, consts = build(R, cfg)
    state = stack_states(cfg or CFG, R, R)
    state = elect(state, *consts)
    # compile outside the timed region; dispatch is asynchronous, so the
    # region ENDS WITH a value read of the final commit, which cannot
    # return before every enqueued step has run
    run_k = run_k.lower(state, *consts).compile()
    state_pre = jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(reps):
        state, commits = run_k(state, *consts)
    final = int(state.commit[0])                # timed: forces the drain
    dt = time.perf_counter() - t0
    committed = final - int(state_pre.commit[0])
    return committed / dt, dt / (reps * K) * 1e6, committed


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind!r})")
    # headline: 3-replica group (BASELINE config #1); detail adds the 5-
    # and 7-replica groups of BASELINE configs #3/#4 and the reference's
    # maximum sizes 9/11/13 (MAX_SERVER_COUNT = 13, dare.h:26)
    per_group = {R: run_group(R) for R in (3, 5, 7, 9, 11, 13)}
    ops, step_us, committed = per_group[3]
    print(json.dumps({
        "metric": "consensus_committed_ops_per_sec",
        "value": round(ops, 1),
        "unit": "ops/s",
        "vs_baseline": round(ops / BASELINE_OPS, 4),
        "detail": {
            "replicas": 3, "batch": CFG.batch_slots,
            "committed": committed, "step_latency_us": round(step_us, 2),
            "ops_5_replicas": round(per_group[5][0], 1),
            "ops_7_replicas": round(per_group[7][0], 1),
            "ops_9_replicas": round(per_group[9][0], 1),
            "ops_11_replicas": round(per_group[11][0], 1),
            "ops_13_replicas": round(per_group[13][0], 1),
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            # all R replicas' device work runs on ONE chip here (vmapped
            # axis), so ops/s ~ 1/R is the simulation topology, not the
            # protocol: per-replica work is R-invariant outside O(R)
            # scalar gathers — see ANALYSIS_R_SCALING.md
            "topology": "single-chip vmap simulation (R rings, 1 chip)",
        },
    }))


if __name__ == "__main__":
    main()
