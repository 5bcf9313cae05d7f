"""Passes of the host codec of the packed words per 1,000 paths.

The count of the span ``repro.engine.codec``: one per encode
(``pack_bool_mask``) or decode (``unpack_words``) of a mask.  None on a
program without the span.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.engine.codec.n")
