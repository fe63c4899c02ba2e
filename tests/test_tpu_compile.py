"""Compile the dataplane's kernels and shard_map steps for a TPU v5e, without one.

The TPU compiler ships with libtpu and compiles for a described, unattached
``v5e:2x2`` topology. Interpret-mode tests cannot see what it refuses (block
shapes off the chip's tiling, primitives Mosaic cannot lower, more VMEM than a
kernel may use); these tests can. Nothing runs: they only compile.

This is the only test file that describes the topology, and it does so inside a
module fixture, so that under pytest-xdist only the worker that runs this file
loads libtpu. The platform check of ``repro.kernels.ops`` is patched to say
"TPU" so that the Pallas branch is traced with ``interpret=False``.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops

AXIS = "join"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no libtpu / compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache but
        # cannot be read back without one: keep it out of the cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()
    finally:
        mp.undo()


@pytest.fixture
def on_chip(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), (AXIS,))


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# (name, function, argument shapes) at the widths of the chip smoke's probes
KERNEL_CASES = [
    ("merge_join_counts", lambda a, b: ops.merge_join_counts(a, b), [(1 << 16,), (1 << 18,)]),
    ("merge_join_pairs", lambda lo, st: ops.merge_join_pairs(lo, st, 1 << 18),
     [(1 << 16,), (1 << 16,)]),
    ("hash_partition-p1", lambda k: ops.hash_partition(k, 1), [(1 << 18,)]),
    ("hash_partition-p4", lambda k: ops.hash_partition(k, 4), [(1 << 18,)]),
    ("hash_partition_pack-p1", lambda k, c: ops.hash_partition_pack(k, c, 1), [(1 << 18,), ()]),
    ("hash_partition_pack-p4", lambda k, c: ops.hash_partition_pack(k, c, 4), [(1 << 18,), ()]),
    # the dataplane vmaps every kernel over the stages of a bucket
    ("merge_join_counts-vmapped", jax.vmap(lambda a, b: ops.merge_join_counts(a, b)),
     [(4, 3000), (4, 5000)]),
    ("hash_partition_pack-vmapped", jax.vmap(lambda k, c: ops.hash_partition_pack(k, c, 16)),
     [(4, 5000), (4,)]),
    # the benchmark cells' largest probes, one problem each: tri-dblp.enum's
    # LocalJoin (524,288 keys against 196,608, pairs into 524,288 slots) and
    # ssb-q4.year-count's padded fact table against a dimension
    ("merge_join_counts-tri", lambda a, b: ops.merge_join_counts(a, b),
     [(524288,), (196608,)]),
    ("merge_join_pairs-tri", lambda lo, st: ops.merge_join_pairs(lo, st, 524288),
     [(196608,), (196608,)]),
    ("merge_join_counts-year", lambda a, b: ops.merge_join_counts(a, b),
     [(4194304,), (32768,)]),
    # 64 schedules of 6,142 steps outgrow the 1 MiB of SMEM: several calls
    ("merge_join_counts-vmapped-smem", jax.vmap(lambda a, b: ops.merge_join_counts(a, b)),
     [(64, 1 << 21), (64, 1 << 20)]),
]


@pytest.mark.parametrize("name,fn,shapes", KERNEL_CASES, ids=[c[0] for c in KERNEL_CASES])
def test_join_kernel_compiles_for_v5e(on_chip, one_chip, name, fn, shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
    assert "tpu_custom_call" in _hlo(fn, *args), name


def test_probe_roofline_reads_the_key_lengths(on_chip, one_chip):
    """kernel.probe_roofline takes the first two 1-D s32 operands of the counts
    kernel's custom call as A's and B's key lengths; the schedule goes first,
    and 2-D, so it is not among them."""
    import re

    shape = re.compile(r"s32\[(\d+)\]")      # the reader's pattern
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in [(524288,), (196608,)]]
    hlo = _hlo(lambda a, b: ops.merge_join_counts(a, b), *args)
    (call,) = [l for l in hlo.splitlines() if "merge_join_counts" in l and "custom-call(" in l]
    operands = re.search(r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", call).group(1)
    assert [int(x) for x in shape.findall(operands)[:2]] == [524288, 196608], operands


@pytest.mark.parametrize("kernel", ["counts", "pairs"])
def test_probe_lowering_ignores_key_values(on_chip, one_chip, kernel):
    """The TPU lowering of a probe is the same for any keys of one shape."""
    rng = np.random.default_rng(0)
    inputs = [(np.sort(rng.integers(0, 100, 17000)), np.sort(rng.integers(0, 100, 19000))),
              (np.full(17000, 3), np.full(19000, np.iinfo(np.int32).max))]
    if kernel == "counts":
        fn = jax.jit(lambda a, b: ops.merge_join_counts(a, b), in_shardings=(one_chip, one_chip))
    else:
        fn = jax.jit(lambda lo, st: ops.merge_join_pairs(lo, st, 20000),
                     in_shardings=(one_chip, one_chip))
        inputs = [(a, np.cumsum(a) - a) for a, _ in inputs]
    texts = [fn.lower(*(np.asarray(x, np.int32) for x in xs)).as_text() for xs in inputs]
    assert "tpu_custom_call" in texts[0] and texts[0] == texts[1]


def _abstract(args, mesh):
    rep = NamedSharding(mesh, P())
    return [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=rep) for a in args]


def test_batched_grid_route_step_compiles_for_v5e(on_chip, mesh4):
    from repro.dataplane.grid import HCBatchSig, batched_sharded_grid_route

    s, p, cap, fanout = 2, 4, 1 << 14, 4
    sig = HCBatchSig(cols=(0, 1), fanout=fanout)
    fn, args = batched_sharded_grid_route(
        mesh4, AXIS,
        np.zeros((s, p, cap, 2), np.int32), np.zeros((s, p), np.int32), sig,
        salts=np.ones((s, 2)), shares=np.ones((s, 2)), strides=np.zeros((s, 2)),
        table=np.zeros((s, fanout)), cap_slot=cap, cap_out=2 * cap, invoke=False,
    )
    hlo = fn.lower(*_abstract(args, mesh4)).compile().as_text()
    assert "all-to-all" in hlo


def test_batched_hash_route_semijoin_step_compiles_for_v5e(on_chip, mesh4):
    from repro.dataplane.join import batched_sharded_semijoin

    s, p, cap = 2, 4, 1 << 14
    fn, args = batched_sharded_semijoin(
        mesh4, AXIS,
        np.zeros((s, p, cap, 2), np.int32), np.zeros((s, p), np.int32), 0,
        np.zeros((s,), np.int32), np.zeros((s, p, cap), np.int32), np.zeros((s, p), np.int32),
        cap_slot=cap, cap_out=cap, invoke=False,
    )
    hlo = fn.lower(*_abstract(args, mesh4)).compile().as_text()
    assert "all-to-all" in hlo and "tpu_custom_call" in hlo


def test_batched_colocated_join_step_compiles_for_v5e(on_chip, mesh4):
    from repro.dataplane.join import batched_sharded_colocated_join

    s, p, cap = 2, 4, 1 << 14
    fn, args = batched_sharded_colocated_join(
        mesh4, AXIS,
        np.zeros((s, p, cap, 3), np.int32), np.zeros((s, p), np.int32),
        np.zeros((s, p, cap, 3), np.int32), np.zeros((s, p), np.int32),
        0, 0, cap_out=1 << 16, dup_pairs=((1, 1),),
        key_mults=np.ones((s, 1), np.int32), invoke=False,
    )
    assert "tpu_custom_call" in fn.lower(*_abstract(args, mesh4)).compile().as_text()
