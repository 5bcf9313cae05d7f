"""Traffic and data generators of the benchmark.

The graph generator is the program's own (``repro.graph.generators``)
with its sizes made parameters, and the sharding function a copy of
``repro.graph.partition``'s, as they stood when the benchmark was defined,
so that a later change to the program cannot change the yardstick;
``bench/tests`` pins each to the program's function at a small size for
the same seed.

Paths are returned as plain arrays ``(objects int32 [P, L] -1 padded,
lengths int32 [P], query_ids int32 [P])``; the drivers wrap them into the
program's ``PathSet``.
"""
