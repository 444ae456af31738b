"""``interposed_app_cluster`` with its machines made chips: the same G
groups on the same R replicas behind ``ShardedClusterDriver``, built
with ``mesh=(group_shards, R)``, so that replica r's ring row of EVERY
group rests on chip r (``mapping.kind`` ``group_rows_per_chip``). The
replica collectives of all the groups then cross chips in one program
step, from as many source chips as there are leaders; nothing crosses
the group axis.

Only what the mapping changes is here: the mesh handed to the driver,
the placement rule ``boot`` holds the run to, which chip the device
readers take, and how many chips hold state. The check, the faults,
the routing table handed to the generator and the probes are
``interposed_app_cluster``'s, inherited.

The placement rule: the run FAILS unless R distinct chips each hold
exactly one replica's row of all G groups, replica r on the mesh's
chip r, and every other chip of the host holds no ring row (with
``group_shards`` above 1: chip (s, r) replica r's row of shard s's
groups).

"The leader's chip": with round-robin placement every chip leads one
group and follows the others, so the chips are alike; the device
readers are given chip 0 (replica 0, which leads group 0).
"""

from __future__ import annotations

from perfbench.deployments import interposed_app_cluster

MAPPING_KIND = "group_rows_per_chip"


def mesh_of(config: dict) -> tuple:
    """``mapping.mesh`` as the tuple ``ShardedCluster`` builds a mesh
    from, or an exit naming what does not fit: before an app is
    started or a chip is touched."""
    mapping = config["mapping"]
    mesh = mapping.get("mesh")
    ok = (mapping.get("kind") == MAPPING_KIND
          and isinstance(mesh, list) and len(mesh) == 2
          and all(isinstance(n, int) and n > 0 for n in mesh))
    if ok:
        shards, replicas = mesh
        ok = (replicas == int(config["replicas"])
              and int(config["groups"]) % shards == 0
              and shards * replicas <= int(config["chips"]))
    if not ok:
        raise SystemExit(
            f"perfbench: configuration {config['name']!r} maps "
            f"{config['groups']} groups of {config['replicas']} replicas "
            f"on {config['chips']} chips as {mapping.get('kind')!r} with "
            f"mesh {mesh!r}: want kind {MAPPING_KIND!r} and mesh "
            f"[group_shards, replicas], the groups a multiple of the "
            f"shards, the chips enough for both")
    return tuple(mesh)


class Deployment(interposed_app_cluster.Deployment):

    def __init__(self, config: dict, ctx):
        self.mesh_shape = mesh_of(config)
        super().__init__(config, ctx)

    def driver_kwargs(self) -> dict:
        return dict(super().driver_kwargs(), mesh=self.mesh_shape)

    def boot(self) -> None:
        super().boot()
        self.hold_placement()

    def hold_placement(self) -> None:
        import jax
        c = self.driver.cluster
        held = {}               # device -> (groups, replicas) it holds
        for shard in c.state.log.buf.addressable_shards:
            groups = range(*shard.index[0].indices(c.G))
            replicas = range(*shard.index[1].indices(c.R))
            held.setdefault(shard.device, []).append(
                (list(groups), list(replicas)))
        shards = self.mesh_shape[0]
        per = c.G // shards     # groups a shard: all of them at [1, R]
        want = {c.mesh.devices[s, r]:
                [(list(range(s * per, (s + 1) * per)), [r])]
                for s in range(shards) for r in range(c.R)}
        if held != want or len(want) != shards * c.R:
            raise RuntimeError(
                f"ring rows rest on {self.log_devices()}: want replica "
                f"r's row of every group of shard s on chip (s, r) of "
                f"{[str(d) for d in c.mesh.devices.flat]} and on no "
                f"other")
        empty = [str(d) for d in jax.devices() if d not in want]
        self.ctx.say("deploy", f"each of {len(want)} chips holds one "
                     f"replica's row of {per} of {c.G} groups; chips "
                     f"without a ring row: {empty}")

    def leader_device_id(self):
        return int(self.driver.cluster.mesh.devices[0, 0].id)

    def shapes(self) -> dict:
        return dict(super().shapes(), chips_holding_state=self.R)


def build(config: dict, ctx) -> Deployment:
    return Deployment(config, ctx)
