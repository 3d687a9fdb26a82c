"""Dispatch and settle: the seconds of a set-up inside JAX's backend
compile (`stage="backend"`): the compiler, or the look-up and load of the
persistent cache where it hits."""

from benchmarks.layers._setup import setup_seconds


def read(ctx):
    return setup_seconds(ctx, ("backend",))
