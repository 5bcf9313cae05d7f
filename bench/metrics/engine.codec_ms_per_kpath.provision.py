"""Milliseconds of the host codec of the packed words per 1,000 paths.

The span ``repro.engine.codec``: every whole-array encode of a bool mask
into the packed words (``pack_bool_mask``) and decode back
(``unpack_words``), wherever in a call it runs.  None on a program
without the span.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.engine.codec.ns", 1e-6)
