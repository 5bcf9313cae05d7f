"""Faults planted in the program's timed path, for the readings the limits
are set from (``bench/limits.py``) and for the tests that see ``correct``
come out false.

Each wraps ``replicate_workload``, the public entry point the
provisioning driver calls.  ``unchanged`` returns the state it was given,
``half`` leaves half of the batch out, ``altered`` alters the answer where
it is produced.  One chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import contextlib

import numpy as np


def provision_unchanged(orig):
    def call(ps, shard, n_servers, t, **kw):
        from repro.core.replication import ReplicationScheme

        _, stats = orig(ps, shard, n_servers, t, **kw)
        return ReplicationScheme.from_sharding(shard, n_servers), stats
    return call


def provision_half(orig):
    def call(ps, shard, n_servers, t, **kw):
        return orig(ps.select(np.arange(ps.n_paths // 2)), shard,
                    n_servers, t, **kw)
    return call


def provision_altered(orig):
    """Every copy moved to the next server."""
    def call(ps, shard, n_servers, t, **kw):
        scheme, stats = orig(ps, shard, n_servers, t, **kw)
        scheme.mask[...] = np.roll(scheme.mask, 1, axis=1)
        return scheme, stats
    return call


PROVISION = {"unchanged": provision_unchanged, "half": provision_half,
             "altered": provision_altered}


@contextlib.contextmanager
def planted(name: str):
    """Plant fault ``name`` in ``replicate_workload``, the entry point the
    provisioning driver calls."""
    import repro.core.greedy as owner

    orig = owner.replicate_workload
    owner.replicate_workload = PROVISION[name](orig)
    try:
        yield
    finally:
        owner.replicate_workload = orig
