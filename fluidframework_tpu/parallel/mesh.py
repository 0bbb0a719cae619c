"""Device mesh + sharding for the batched merge state.

The distribution axis is documents (SURVEY §2.9: the reference's total
order is per-document; docs shard statelessly over Kafka partitions —
here over a ``jax.sharding.Mesh`` doc axis). Segment tables and op
batches shard on dim 0; within a document the op window is a dependent
scan, so no intra-doc sharding is needed until the long-document
sequence-parallel path (SURVEY §5.7) lands.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DOC_AXIS = "docs"


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.array(devices), (DOC_AXIS,))


def doc_sharding(mesh: Mesh, axis: int = 0) -> NamedSharding:
    """Document sharding: dim 0 for tables and op batches; ``axis=1``
    for a packed ``[fields, docs, window]`` buffer."""
    return NamedSharding(mesh, P(*[None] * axis, DOC_AXIS))


def scalar_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_pytree(tree, mesh: Mesh):
    """Place every leaf with dim 0 = docs on the doc axis."""
    sharding = doc_sharding(mesh)
    return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)


_programs: dict = {}


def doc_program(fn, mesh: Mesh, arg_axes: tuple,
                donate_argnums: tuple = (), static: tuple = ()):
    """``fn`` as one program over the mesh's documents: each device
    runs it on its own rows (``shard_map``: no collective, a
    ``while`` trip count stays per device), every argument in and the
    table out placed by ``doc_sharding``. ``arg_axes`` gives each
    argument's document axis; ``static`` trails the arguments as
    constants. ``donate_argnums`` alias into the output. Cached per
    (fn, mesh, ...), and named as ``fn``, so a trace files the program
    under the module name of ``fn``'s jit."""
    key = (fn, mesh, arg_axes, donate_argnums, static)
    if key not in _programs:
        body = fn
        if static:
            def body(*args):
                return fn(*args, *static)

            body.__name__ = body.__qualname__ = fn.__name__
        placed = tuple(doc_sharding(mesh, axis) for axis in arg_axes)
        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=tuple(s.spec for s in placed),
            out_specs=P(DOC_AXIS), check_vma=False)
        # a donated argument the body never reads (the ping-pong's dead
        # table) is kept as a parameter: pruned, it could not alias the
        # output, and the donation would free nothing
        _programs[key] = jax.jit(
            mapped, in_shardings=placed,
            out_shardings=doc_sharding(mesh),
            donate_argnums=donate_argnums,
            keep_unused=bool(donate_argnums))
    return _programs[key]


def global_window_floor(min_seq, mesh: Mesh):
    """Cross-device collab-window reduction: the global minimum msn
    over every document shard, computed with a real ICI collective
    (``lax.pmin`` under shard_map) and replicated to all devices.

    Service analogue: aggregating deli's per-partition
    durableSequenceNumber into a service-wide durable floor (the op
    log can truncate at/below it across every partition —
    deli/lambda.ts:342 area, kafka-service checkpointManager.ts:10).
    This is the mesh's first non-embarrassingly-parallel operation:
    doc shards are otherwise independent vmap lanes.
    """
    import jax.numpy as jnp

    def local(ms):  # [docs_shard] on each device
        return jax.lax.pmin(jnp.min(ms), DOC_AXIS)

    return jax.shard_map(
        local, mesh=mesh, in_specs=P(DOC_AXIS), out_specs=P(),
    )(min_seq)
