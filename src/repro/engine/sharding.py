"""Multi-device layout for provisioning-scale greedy (jax.sharding).

The fused UPDATE step is embarrassingly parallel over paths: every path
in a batch prices its candidates against the same packed-words snapshot
and the scatter-OR union of the chosen additions is order-free (Thm 5.3
monotonicity — the same argument that justifies the lock-free batch).
So the layout is the simplest one GSPMD supports:

  * packed scheme words, shard map, f, C(h, t) tables — **replicated**
    (``PartitionSpec()``): every device holds the full snapshot, exactly
    like every thread of the paper's 64-thread UPDATE reads the full
    scheme;
  * batch arrays (objects / lengths / budgets) — **sharded on the path
    axis** (``PartitionSpec("paths")``): each device gates + scores its
    slice of the batch;
  * the per-batch scatter-OR and stat sums are cross-device reductions
    XLA inserts automatically (bitwise-OR of the replicated words'
    per-device updates, psum of the stat vector).

The Pallas kernels are the exception: a Mosaic kernel is a custom call
that GSPMD cannot partition (the TPU compiler refuses it), so
:func:`map_paths` runs the kernel under ``shard_map``, once per device on
its slice of the path axis.  Only the kernel goes under ``shard_map``: the
scatter-OR needs a bitwise-OR across devices, which the manual-collective
API does not provide, so it stays in GSPMD, which lowers it to an
all-gather of each device's chosen additions — tiny (the chosen planes,
not the words).  The words are replicated on the mesh only for the fused
UPDATE loop and return to one device after it (:func:`one_device`): the
host-driven gate, revalidation and prune call kernels outside that loop.

CPU note: the test/CI environment exposes one device;
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` splits the host
into N devices (tests/test_provision_scale.py runs the sharded-equality
check in a subprocess with that flag, and skips in-process when only one
device is visible).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.engine.streaming import TRANSFER

PATH_AXIS = "paths"


def device_count() -> int:
    return len(jax.devices())


def round_up_rows(n: int, align: int = 128) -> int:
    """Round a batch row count up to ``align`` x the visible device count.

    The incremental dirty-set evaluator (``repro.engine.incremental``)
    pads its compacted dirty blocks with this quantum: the ``align``
    factor bounds how many jit shapes a varying dirty-set size can
    produce (the same 128-row bucketing the prune sweep uses), and the
    device factor keeps the padded block divisible across a path-sharded
    mesh — a dirty batch that lands on 8 devices must carry a row
    multiple of 8 x ``align`` or GSPMD pads it per device anyway, off the
    books.  Always returns at least one full quantum.
    """
    q = max(1, int(align)) * max(1, device_count())
    return max(q, -(-int(n) // q) * q)


def provisioning_mesh(n_devices: int | None = None) -> Mesh:
    """1-D device mesh over the path axis (all visible devices by default)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (PATH_AXIS,))


def path_sharding(mesh: Mesh) -> NamedSharding:
    """Leading axis split across devices (batch rows = paths)."""
    return NamedSharding(mesh, PartitionSpec(PATH_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Full copy on every device (scheme words, tables, f, shard map)."""
    return NamedSharding(mesh, PartitionSpec())


def replicate(x, mesh: Mesh):
    """Place ``x`` fully replicated on the mesh (no byte accounting: the
    words/tables are already device-resident; this is a device-to-device
    broadcast, not host traffic)."""
    return jax.device_put(x, replicated(mesh))


def one_device(x, mesh: Mesh):
    """Bring a mesh-placed array back to the mesh's first device."""
    return jax.device_put(x, mesh.devices.flat[0])


def map_paths(fn, mesh: Mesh, sharded: tuple[bool, ...]):
    """``fn`` once per device on its slice of the path (leading) axis.

    ``sharded[i]`` says whether argument ``i`` is split on the path axis
    (batch rows) or replicated (words, tables, ranks); every output is
    split on the path axis.
    """
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=tuple(
            PartitionSpec(PATH_AXIS) if s else PartitionSpec() for s in sharded
        ),
        out_specs=PartitionSpec(PATH_AXIS),
        check_vma=False,
    )


def batch_put(mesh: Mesh):
    """Counted host->device upload landing path-sharded on the mesh.

    Drop-in for ``streaming.to_device`` in the greedy batch loop — books
    the same TRANSFER bytes (each row goes to exactly one device, so the
    payload crosses the bus once, same as the single-device path).
    """
    sh = path_sharding(mesh)

    def put(x, payload_bytes: int | None = None):
        a = np.asarray(x)
        payload = a.nbytes if payload_bytes is None else int(payload_bytes)
        TRANSFER.h2d_bytes += payload
        TRANSFER.padded_bytes += a.nbytes - payload
        TRANSFER.h2d_calls += 1
        return jax.device_put(a, sh)

    return put
