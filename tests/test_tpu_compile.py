"""The replication path's Pallas kernels compile for a described TPU v5e.

Interpret mode (every other test) cannot show what the chip's compiler
refuses: unsupported primitives, layouts it cannot tile, blocks that do not
fit VMEM.  These tests lower each kernel with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology — no chip is attached, nothing
runs — at real widths: 128-path blocks, paths of L in {6, 8} objects, and
W in {1, 4} holder words (6 and 128 servers).  Each compiled program must
hold the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import combi
from repro.engine.routing import resolve_policy
from repro.kernels.path_latency import path_latency_pallas
from repro.kernels.provision_update import fused_update_pallas
from repro.kernels.routed_walk import routed_walk_pallas, scored_walk_pallas

P = 256          # two 128-path blocks
N_OBJ = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a compile
    for a chip that is not attached cannot be read back from it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(sharding, L, W, C=None):
    """Kernel input specs; ``C`` pads the candidate tables to C rows (by
    default they hold the t = 1 candidates, at most 8)."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    Sp = W * 32
    H = L - 1
    tables, counts = combi.stacked_tables(H, 1)
    if C is not None:
        tables = jax.ShapeDtypeStruct((H + 1, C, H + 1), tables.dtype)
    return {
        "home": spec((P, L), jnp.int32),
        "masks": spec((P, L, W), jnp.uint32),
        "lengths": spec((P,), jnp.int32),
        "start": spec((P,), jnp.int32),
        "load": spec((Sp,), jnp.float32),
        "scores": spec((P, L, Sp), jnp.float32),
        "words": spec((N_OBJ + 1, W), jnp.uint32),
        "objects": spec((P, L), jnp.int32),
        "shard": spec((N_OBJ,), jnp.int32),
        "f": spec((N_OBJ,), jnp.float32),
        "tables": spec(tables.shape, jnp.bool_),
        "counts": spec(counts.shape, jnp.int32),
        "t": spec((P,), jnp.int32),
    }


def _fused(policy):
    pol = None if policy is None else resolve_policy(policy)
    fn = functools.partial(fused_update_pallas, pol=pol, interpret=False)
    names = ("words", "objects", "lengths", "shard", "f", "tables", "counts",
             "t", "load")
    return fn, names


KERNELS = {
    "path_latency": (
        functools.partial(path_latency_pallas, interpret=False),
        ("home", "masks", "lengths"),
    ),
    "routed_walk_lookahead": (
        functools.partial(routed_walk_pallas, interpret=False,
                          lookahead=True),
        ("home", "masks", "lengths", "start", "load"),
    ),
    "routed_walk_home_first": (
        functools.partial(routed_walk_pallas, interpret=False,
                          lookahead=False, home_first=True),
        ("home", "masks", "lengths", "start", "load"),
    ),
    "scored_walk": (
        functools.partial(scored_walk_pallas, interpret=False),
        ("home", "masks", "lengths", "start", "scores"),
    ),
    "fused_update_none": _fused(None),
    "fused_update_nearest_copy": _fused("nearest_copy"),
    "fused_update_nearest_copy_dp": _fused("nearest_copy_dp"),
}


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("L", [6, 8])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, L, W):
    fn, names = KERNELS[kernel]
    args = _args(one_chip, L, W)
    compiled = jax.jit(fn).lower(*(args[n] for n in names)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_update_compiles_at_max_candidates(one_chip):
    """The fused kernel holds the whole candidate table in VMEM, one
    (8, 128) tile per candidate: a class at the drivers' default
    ``max_candidates`` (2048) must still fit."""
    fn, names = _fused("nearest_copy")
    args = _args(one_chip, 8, 4, C=2048)
    compiled = jax.jit(fn).lower(*(args[n] for n in names)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("policy", [None, "nearest_copy", "nearest_copy_dp"])
def test_fused_update_compiles_on_v5e_mesh(one_chip, topo, policy):
    """Path-sharded over four described chips: GSPMD cannot partition a
    Mosaic kernel, so the kernel must sit under ``shard_map``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.engine.sharding import PATH_AXIS

    mesh = Mesh(topo.devices, (PATH_AXIS,))
    fn, names = _fused(policy)
    fn = functools.partial(fn, mesh=mesh)
    rows = {"objects", "lengths", "t"}
    args = {
        n: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(
            mesh, PartitionSpec(PATH_AXIS) if n in rows else PartitionSpec()))
        for n, a in _args(one_chip, 6, 1).items()
    }
    compiled = jax.jit(fn).lower(*(args[n] for n in names)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_prune_group_step_compiles_at_sf1_size(one_chip, monkeypatch,
                                               backend):
    """The prune's one dispatch per candidate group, at the shapes the
    provisioner gives it on LDBC SNB SF1: 3,181,724 objects, 6 servers,
    paths of 8 objects, a full group of 512 over one 1,024-row bucket.
    Its bit scatters stay in place: no temporary as large as the words
    (a 2-D scatter re-lays them out, padded to 128 lanes, every round)."""
    from repro.core import replication
    from repro.engine import backends

    monkeypatch.setattr(backends, "interpret_pallas", lambda: False)
    n_obj, G, Rb, L = 3_181_724, replication._PRUNE_GROUP_MAX, 1024, 8

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    step = functools.partial(
        replication._prune_group_step.__wrapped__,
        pol=resolve_policy("nearest_copy"), backend=backend, G=G)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        spec((n_obj + 1, 1), jnp.uint32),
        spec((G,), jnp.int32), spec((G,), jnp.int32),
        spec((Rb, L), jnp.int32), spec((Rb,), jnp.int32),
        spec((Rb,), jnp.int32), spec((Rb,), jnp.int32),
        spec((n_obj,), jnp.int32), spec((32,), jnp.float32),
    ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n_obj


# The k-resilience gate at the shapes LDBC SNB SF1 gives it: 3,181,725
# objects (one word, 6 servers), D = 6 single-server loss cases, a call's
# 3,072 paths of 8 objects.
SF1_OBJ, SF1_CASES, SF1_PATHS = 3_181_725, 6, 3072


@pytest.mark.parametrize("walk", ["routed", "home"])
def test_resilient_walk_compiles_at_sf1_size(one_chip, walk):
    """The masked re-walk of every loss case in one vmapped dispatch
    (``nearest_copy`` and ``home_first``).  Its temporaries hold the D
    masked copies of the words at most: none is re-laid out with 128
    lanes of padding."""
    from repro.engine import backends

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [spec((SF1_PATHS, 8), jnp.int32), spec((SF1_PATHS,), jnp.int32),
            spec((SF1_OBJ + 1, 1), jnp.uint32),
            spec((SF1_CASES, 1), jnp.uint32),
            spec((SF1_CASES, SF1_OBJ), jnp.int32)]
    if walk == "routed":
        fn = functools.partial(backends._resilient_routed_vmap.__wrapped__,
                               lookahead=True)
        args.append(spec((32,), jnp.float32))
    else:
        fn = backends._resilient_home_vmap.__wrapped__
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        4 * SF1_OBJ * (SF1_CASES + 4))


def test_masked_update_compiles_at_sf1_size(one_chip):
    """One loss case's words masked (``mask_case_words``), then the UPDATE
    step of a 256-path batch on them, as the repair of a violating case
    runs it: no temporary as large as the words."""
    from repro.core import greedy
    from repro.engine.backends import mask_case_words

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, L, S = 256, 8, 6
    tables, counts = combi.stacked_tables(L - 1, 1)

    def masked_update(words, cmask, *rest):
        return greedy._update_batch_core(
            mask_case_words(words, cmask), *rest,
            check_capacity=False, routed_gate=True)

    compiled = jax.jit(masked_update).lower(
        spec((SF1_OBJ + 1, 1), jnp.uint32), spec((1,), jnp.uint32),
        spec((B, L), jnp.int32), spec((B,), jnp.int32),
        spec((SF1_OBJ,), jnp.int32), spec((SF1_OBJ,), jnp.float32),
        spec(tables.shape, jnp.bool_), spec(counts.shape, jnp.int32),
        spec((B,), jnp.int32), spec((B,), jnp.int32),
        spec((S,), jnp.float32), spec((S,), jnp.float32),
        spec((), jnp.float32),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * SF1_OBJ
