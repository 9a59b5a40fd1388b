"""Device-mesh design exploration: the session's sharded explore engine.

Two mesh execution modes behind one entry point (`explore_cells_mesh`),
both first-class engines of `repro.api.session.DesignSession.explore_
stage` (a request opts in with `DesignRequest.islands > 1`; a session
opts in with `DesignSession(mesh=...)`):

  * **sharded cells** (`islands == 1`) — the coalesced (array_size x
    seed) cell list is sharded over the mesh's device axis and each
    device vmaps the very same operand-traced `nsga2.run_cell` the
    batched explorer uses, with the *identical* per-cell key and
    operands.  Per-cell fronts are therefore bit-equal to the
    single-device engine (`repro.core.batched_explorer.explore_cells`)
    — asserted by `tests/test_distributed_explorer.py` — so a fleet
    can turn the mesh on and off without invalidating any cache tier.

  * **island model** (`islands > 1`) — every island evolves an
    independent NSGA-II population per cell (island i's stream is
    `fold_in(key(seed), i)`), with periodic **ring migration** of
    Pareto elites: island i's top-k elites replace island i+1's worst-k
    (mod I).  The ring is realized as a local shift of the per-device
    island block plus ONE `jax.lax.ppermute` of the boundary elite
    block, so per-round comms are O(elites), not the O(islands x pop)
    of the all-gather scheme this engine replaced.  Migration is fully
    deterministic (rank/crowding-ordered, no random partner choice)
    and the key schedule is a function of *global* island ids only, so
    the merged result is bit-identical for ANY device count dividing
    the island count — an 8-device pod and a 1-device laptop produce
    the same front (also asserted by the tests).

The per-device program composes the same `run_cell` / `evolve_from`
building blocks as the single-device explorers, so the one-compile
sweep contract carries over: one jit-compiled program per (mesh,
statics, schedule) — rounds are unrolled inside it — and `run_cell` is
traced once per program build (`nsga2.TRACE_COUNTS` probe).

The merged front of an island run is the deduplicated Pareto front of
the union of the island populations (`explorer.pareto_result_from_
population` over the flattened island axis) — it can only gain points
over a lone island, never lose dominance, and the session records the
migration provenance (device count, topology, rounds) in the artifact.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import batched_explorer as bx
from repro.core import explorer, nsga2
from repro.core.constants import CAL28
from repro.runtime.lock_sanitizer import make_lock
from repro.telemetry.spans import trace_span

DEFAULT_MIGRATE_EVERY = 20
MESH_AXIS = "islands"

# Compiled mesh programs, keyed by everything that shapes them.  Session
# explore stages on several service threads may race the first build;
# the lock makes the cache insert atomic (compilation itself is
# jax-level cached by function identity, so a lost race costs nothing).
_PROGRAM_LOCK = make_lock("parallel.distributed_explorer._PROGRAM_LOCK")
_PROGRAMS: dict = {}


def default_mesh(max_devices: int | None = None) -> Mesh:
    """A 1-D mesh over the local devices (optionally capped), the shape
    both mesh modes consume.  One flat axis: island/cell sharding is
    1-D by construction (redco-style `mesh_utils` flattening)."""
    devices = jax.devices()
    n = len(devices)
    if max_devices is not None:
        if max_devices <= 0:
            raise ValueError("max_devices must be positive")
        n = min(n, max_devices)
    return Mesh(np.asarray(devices[:n]), (MESH_AXIS,))


def mesh_size(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def devices_for_islands(mesh: Mesh, islands: int) -> int:
    """Devices the island engine will actually use: the largest divisor
    of `islands` that fits the mesh.  Using a divisor (instead of
    padding) keeps the island->device block map exact, which is what
    makes the result independent of the device count."""
    n_dev = mesh_size(mesh)
    return max(d for d in range(1, min(islands, n_dev) + 1)
               if islands % d == 0)


def _submesh(mesh: Mesh, n: int) -> Mesh:
    if n == mesh_size(mesh):
        return mesh
    axis = mesh.axis_names[0]
    return Mesh(np.asarray(mesh.devices).reshape(-1)[:n], (axis,))


def _round_schedule(generations: int, migrate_every: int) -> tuple[int, ...]:
    """Per-round generation counts: migration fires between rounds, so
    `len(schedule) - 1` migrations happen in total."""
    if migrate_every <= 0:
        raise ValueError("migrate_every must be positive")
    full, rem = divmod(generations, migrate_every)
    gens = [migrate_every] * full + ([rem] if rem else [])
    return tuple(gens) or (generations,)


def _elite_count(pop_size: int) -> int:
    return min(max(2, pop_size // 8), pop_size // 2)


# ----------------------------------------------------------------------
# Compiled mesh programs
# ----------------------------------------------------------------------
def _sharded_cells_program(mesh: Mesh, statics: nsga2.EvolveStatics,
                           n_gens: int):
    """jit(shard_map(vmap(run_cell))) over the cell axis: each device
    runs its block of cells with the exact single-engine key/operands."""
    key = ("cells", mesh, statics, n_gens)
    with _PROGRAM_LOCK:
        prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    axis = mesh.axis_names[0]
    cell = functools.partial(nsga2.run_cell, statics=statics, n_gens=n_gens)

    def body(keys, spaces):
        return jax.vmap(cell)(keys, spaces)

    prog = jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(axis), P(axis)),
                                 out_specs=(P(axis), P(axis)),
                                 check_vma=False))
    with _PROGRAM_LOCK:
        _PROGRAMS[key] = prog
    return prog


def _island_program(mesh: Mesh, statics: nsga2.EvolveStatics,
                    schedule: tuple[int, ...], n_elite: int):
    """The island engine's one compiled program: per-device island
    blocks, cells vmapped inside, migration rounds unrolled, ring
    links via a single boundary `ppermute` per round."""
    key = ("islands", mesh, statics, schedule, n_elite)
    with _PROGRAM_LOCK:
        prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    axis = mesh.axis_names[0]
    n_dev = mesh_size(mesh)
    perm = [(d, (d + 1) % n_dev) for d in range(n_dev)]
    rc = functools.partial(nsga2.rank_and_crowd, statics=statics)

    def migrate(genes, objs):
        """Ring-migrate elites across the island axis.

        Shapes: genes (k, C, P, 3), objs (k, C, P, 4) — k islands on
        this device, C cells.  Each (island, cell) population is sorted
        by (rank, -crowding); the top `n_elite` rows are this island's
        emigrants and the bottom `n_elite` rows are replaced by the
        previous island's.  The ring crosses the device boundary once:
        the local block shifts down by one island and the last island's
        elites `ppermute` to the next device — O(n_elite) bytes per
        link instead of an O(islands x pop) all-gather.  The sorted
        order (and hence the returned layout) depends only on island-
        local data, so the result is identical for every device count."""
        ranks, crowd = jax.vmap(jax.vmap(lambda o: rc(o)))(objs)
        order = jnp.lexsort((-crowd, ranks), axis=-1)
        sorted_g = jnp.take_along_axis(genes, order[..., None], axis=2)
        sorted_o = jnp.take_along_axis(objs, order[..., None], axis=2)
        elite_g, elite_o = sorted_g[:, :, :n_elite], sorted_o[:, :, :n_elite]
        recv_g = jnp.concatenate(
            [jax.lax.ppermute(elite_g[-1:], axis, perm), elite_g[:-1]], 0)
        recv_o = jnp.concatenate(
            [jax.lax.ppermute(elite_o[-1:], axis, perm), elite_o[:-1]], 0)
        return (sorted_g.at[:, :, -n_elite:].set(recv_g),
                sorted_o.at[:, :, -n_elite:].set(recv_o))

    def body(init_keys, evolve_keys, spaces):
        # init_keys (k, C); evolve_keys (max(R-1,1), k, C); spaces
        # replicated (C, ...).  Rounds are unrolled: ONE device program
        # regardless of the migration cadence.
        cell = functools.partial(nsga2.run_cell, statics=statics,
                                 n_gens=schedule[0])
        genes, objs = jax.vmap(
            lambda krow: jax.vmap(cell)(krow, spaces))(init_keys)
        for r, g in enumerate(schedule[1:]):
            genes, objs = migrate(genes, objs)

            def step(k, ge, ob, sp, g=g):
                return nsga2.evolve_from(k, ge, ob, sp, statics, g)

            genes, objs = jax.vmap(
                lambda kr, gr, orow: jax.vmap(step)(kr, gr, orow, spaces)
            )(evolve_keys[r], genes, objs)
        return genes, objs

    prog = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(None, axis), P()),
        out_specs=(P(axis), P(axis)), check_vma=False))
    with _PROGRAM_LOCK:
        _PROGRAMS[key] = prog
    return prog


@functools.partial(jax.jit, static_argnames=("islands", "n_rounds"))
def _island_keys(base, islands: int, n_rounds: int):
    """Island i's stream per cell, `fold_in(base, i)` (I, C), and each
    migration round's keys drawn from it (R, I, C)."""
    fold = jax.vmap(jax.random.fold_in, in_axes=(0, None))
    init_keys = jax.vmap(lambda i: fold(base, i))(jnp.arange(islands))
    evolve_keys = jax.vmap(
        lambda r: jax.vmap(jax.vmap(
            lambda k: jax.random.fold_in(k, 0x5EED0000 + r)))(init_keys)
    )(jnp.arange(n_rounds))
    return init_keys, evolve_keys


@functools.partial(jax.jit, static_argnames=("cal",))
def _island_front_program(genes, objs, spaces, *, cal):
    """Merge each cell's island populations, island by island, into one
    (C, I*P) population and run `explorer.front_program` over it.
    Returns the merged (genes, objs) and the front program's output."""
    def merge(x):                    # (I, C, P, k) -> (C, I*P, k)
        return jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1, x.shape[-1])

    genes, objs = merge(genes), merge(objs)
    return genes, objs, explorer.front_program(genes, objs, spaces, cal=cal)


# ----------------------------------------------------------------------
# Entry point
# ----------------------------------------------------------------------
def explore_cells_mesh(cells, *, mesh: Mesh | None = None, islands: int = 1,
                       migrate_every: int = DEFAULT_MIGRATE_EVERY,
                       pop_size: int = 256, generations: int = 80,
                       crossover_prob: float = nsga2.DEFAULT_CROSSOVER_PROB,
                       mutation_prob: float = nsga2.DEFAULT_MUTATION_PROB,
                       cal=CAL28, use_pallas_dominance: bool = False,
                       use_pallas_rank: bool = False,
                       timings: dict | None = None):
    """Explore an (array_size, seed) cell list over a device mesh.

    Returns `({(array_size, seed): ParetoResult}, facts)` — the same
    front mapping as `batched_explorer.explore_cells` plus a facts dict
    (`mesh_devices`, `islands`, `migration_topology`,
    `migration_rounds`) the session stamps into artifact provenance.

    `islands == 1` shards the cell list (bit-equal per-cell fronts to
    the single-device engine); `islands > 1` runs ring-migrating island
    evolution per cell and merges the union front.  Either way the
    result is independent of the mesh's device count.  Both run
    `explorer.front_program` after the explore program, as
    `batched_explorer.explore_cells` does; `timings` gets the same
    `host_s`.
    """
    if islands < 1:
        raise ValueError("islands must be >= 1")
    cells = list(dict.fromkeys((int(s), int(sd)) for s, sd in cells))
    if not cells:
        raise ValueError("explore_cells_mesh needs at least one cell")
    if mesh is None:
        mesh = default_mesh()
    statics = nsga2.EvolveStatics(
        pop_size=pop_size, crossover_prob=crossover_prob,
        mutation_prob=mutation_prob,
        use_pallas_dominance=use_pallas_dominance,
        use_pallas_rank=use_pallas_rank)

    t0 = time.perf_counter()
    if islands == 1:
        n_dev = mesh_size(mesh)
        pad = (-len(cells)) % n_dev
        prog = _sharded_cells_program(mesh, statics, generations)
        with trace_span("launch", cat="explore", cells=len(cells)):
            keys, spaces_b = bx.launch_operands(cells + cells[:1] * pad, cal)
            genes_b, objs_b = prog(keys, spaces_b)
            front_b = explorer.front_program(genes_b, objs_b, spaces_b,
                                             cal=cal)
        facts = {"mesh_devices": n_dev, "islands": 1,
                 "migration_topology": "sharded", "migration_rounds": 0}
    else:
        n_dev = devices_for_islands(mesh, islands)
        sub = _submesh(mesh, n_dev)
        schedule = _round_schedule(generations, migrate_every)
        n_elite = _elite_count(pop_size)
        prog = _island_program(sub, statics, schedule, n_elite)
        with trace_span("launch", cat="explore", cells=len(cells)):
            base, spaces_b = bx.launch_operands(cells, cal)          # (C,)
            init_keys, evolve_keys = _island_keys(
                base, islands, max(len(schedule) - 1, 1))
            genes_i, objs_i = prog(init_keys, evolve_keys, spaces_b)
            genes_b, objs_b, front_b = _island_front_program(
                genes_i, objs_i, spaces_b, cal=cal)
        facts = {"mesh_devices": n_dev, "islands": islands,
                 "migration_topology": "ring",
                 "migration_rounds": len(schedule) - 1}
    fronts = bx.collect_fronts(cells, (genes_b, objs_b, front_b), cal=cal,
                               launched_at=t0, timings=timings)
    return fronts, facts
