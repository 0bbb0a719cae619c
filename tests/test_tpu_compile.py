"""Compile the main path's kernels for a described TPU v5e, with no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: what it refuses here (a layout, a VMEM budget,
a kernel that cannot be partitioned) would be refused on the chip. A
passing compile says nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file. Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from fluidframework_tpu.ops import make_table
from fluidframework_tpu.ops.host_bridge import pack_rows
from fluidframework_tpu.ops.segment_table import KIND_NOOP, OpBatch

NOOP = dict(kind=KIND_NOOP, pos1=0, pos2=0, seq=0, refseq=0, client=0,
            op_id=0, length=0, is_marker=0, prop_key=0, prop_val=0,
            min_seq=0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        if log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quad(topo):
    return list(topo.devices)[:4]


def _shapes(tree, sharding):
    """Shape stand-ins of ``tree``'s leaves, placed by ``sharding``
    (a Sharding, or a callable from leaf to Sharding)."""
    def one(a):
        s = sharding(a) if callable(sharding) else sharding
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=s)

    return jax.tree_util.tree_map(one, tree)


def _window(docs, capacity, bucket):
    table = jax.eval_shape(lambda: make_table(docs, capacity))
    arrays = pack_rows(docs, {0: [NOOP]}, bucket_floor=bucket)
    return table, arrays


def test_scan_window_compiles_at_serving_size(one_chip):
    from fluidframework_tpu.ops.merge_kernel import apply_window_impl

    table, arrays = _window(1024, 1024, 64)
    batch = OpBatch(**{f: arrays[f] for f in OpBatch._fields})
    compiled = jax.jit(apply_window_impl).lower(
        _shapes(table, one_chip), _shapes(batch, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 1024 * 1024 * 4 * 12


@pytest.mark.parametrize("route", ["egwalker"])
def test_macro_step_routes_compile(one_chip, route):
    """The smallest size that shows the walker's macro-step lowering
    (chip_smoke.py compiles it at serving size)."""
    from fluidframework_tpu.ops import event_graph

    table = make_table(16, 256)
    arrays = pack_rows(16, {0: [NOOP]}, bucket_floor=16)
    jitted, args = event_graph.compiled_window(
        table, event_graph.build_event_graph(
            arrays, k_max=event_graph.EG_K)["prefix"])
    jitted.lower(*_shapes(args, one_chip)).compile()


def _pool_args(docs, capacity, bucket, mesh, state_spec, op_spec):
    from fluidframework_tpu.ops.merge_step import (
        batch_to_window,
        table_to_state,
    )

    table, arrays = _window(docs, capacity, bucket)
    batch = OpBatch(**{f: jnp.asarray(arrays[f])
                       for f in OpBatch._fields})
    st = jax.eval_shape(table_to_state, table)
    ops = jax.eval_shape(batch_to_window, batch)
    return (
        _shapes(st, lambda a: NamedSharding(mesh, state_spec(a))),
        _shapes(ops, NamedSharding(mesh, op_spec)),
    ), st


def test_mesh_pool_program_compiles_on_four_chips(quad):
    from fluidframework_tpu.parallel.mesh import DOC_AXIS
    from fluidframework_tpu.parallel.mesh_pool import _compiled_window

    mesh = Mesh(np.array(quad), (DOC_AXIS,))
    args, st = _pool_args(8, 4096, 16, mesh,
                          lambda a: P(DOC_AXIS, None),
                          P(None, DOC_AXIS, None))
    compiled = _compiled_window(mesh, DOC_AXIS, tuple(sorted(st))) \
        .lower(*args).compile()
    # documents never read across the docs axis: no collectives
    hlo = compiled.as_text()
    assert "all-gather" not in hlo and "collective-permute" not in hlo


def test_seq_pool_program_compiles_with_its_collectives(quad):
    from fluidframework_tpu.ops.merge_step import DOC_FIELDS
    from fluidframework_tpu.parallel.seq_shard import (
        SEQ_AXIS,
        _compiled_window,
    )

    mesh = Mesh(np.array(quad).reshape(1, 4), ("docs", SEQ_AXIS))
    args, st = _pool_args(
        4, 4096, 16, mesh,
        lambda a: P("docs", None) if a.shape[-1] == 1
        else P("docs", SEQ_AXIS),
        P(None, "docs", None))
    assert {f for f, a in st.items() if a.shape[-1] == 1} == \
        set(DOC_FIELDS)
    compiled = _compiled_window(mesh, SEQ_AXIS, "docs",
                                tuple(sorted(st))).lower(*args).compile()
    hlo = compiled.as_text()
    # the cross-shard reductions (prefix-sum offsets, psum/pmin
    # lookups; the compiler lowers the totals all_gather to an
    # all-reduce too) and the ppermute boundary exchange
    assert "all-reduce" in hlo
    assert "collective-permute" in hlo


def test_doc_sharded_plane_compiles_on_four_chips(quad):
    """The sidecar's primary plane split by document over a v5e-4: the
    donated walker, the suffix scan and compaction each compile with no
    collective (every chip runs its own documents)."""
    from fluidframework_tpu.ops import event_graph
    from fluidframework_tpu.ops.merge_kernel import compact
    from fluidframework_tpu.parallel.mesh import DOC_AXIS, doc_program

    mesh = Mesh(np.array(quad), (DOC_AXIS,))
    table, arrays = _window(64, 1024, 16)
    program = event_graph.build_event_graph(arrays, k_max=event_graph.EG_K)
    prefix = jax.ShapeDtypeStruct(program["prefix"].shape, jnp.int32)
    suffix = jax.ShapeDtypeStruct(
        (len(OpBatch._fields), 64, 16), jnp.int32)
    walker = event_graph._get_jit_pingpong(event_graph.EG_K).__wrapped__
    for jitted, args in (
            (doc_program(walker, mesh, (0, 0, 1), (0,)),
             (table, table, prefix)),
            (doc_program(event_graph._packed_scan, mesh, (0, 1)),
             (table, suffix)),
            (doc_program(compact.__wrapped__, mesh, (0,)), (table,))):
        hlo = jitted.lower(*args).compile().as_text()
        for op in ("all-gather", "all-to-all", "collective-permute",
                   "all-reduce"):
            assert op not in hlo, op


def test_doc_sharded_pingpong_aliases_the_dead_table(quad):
    """The donated walker on a v5e-4 document mesh writes its output
    into the dead table's buffers: the compiled program aliases a whole
    table's bytes per chip, though its body never reads that table."""
    from fluidframework_tpu.ops import event_graph
    from fluidframework_tpu.parallel.mesh import DOC_AXIS, doc_program

    mesh = Mesh(np.array(quad), (DOC_AXIS,))
    table, arrays = _window(64, 1024, 16)
    program = event_graph.build_event_graph(arrays, k_max=event_graph.EG_K)
    prefix = jax.ShapeDtypeStruct(program["prefix"].shape, jnp.int32)
    walker = event_graph._get_jit_pingpong(event_graph.EG_K).__wrapped__
    memory = doc_program(walker, mesh, (0, 0, 1), (0,)).lower(
        table, table, prefix).compile().memory_analysis()
    # the chip pads the [docs] fields to its tiles: a little over the
    # table's logical bytes, never more than the output
    per_chip = sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(table)) // 4
    assert per_chip <= memory.alias_size_in_bytes \
        <= memory.output_size_in_bytes
