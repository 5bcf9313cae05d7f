"""Replicas the k-resilience gate adds per 1,000 paths.

The counter ``repro.greedy.resilience.additions``: the distinct (object,
server) copies the case repairs added to the live scheme, orphan
re-homings included.
"""
from bench.harness.counters import per_kpath


def read(ctx):
    return per_kpath(ctx, "repro.greedy.resilience.additions")
