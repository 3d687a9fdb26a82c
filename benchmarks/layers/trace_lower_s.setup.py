"""Dispatch and settle: the seconds of a set-up JAX spent tracing the
cell's programs to jaxprs (`stage="trace"`) and lowering them to MLIR
modules (`stage="lower"`): Python time that no compile cache saves."""

from benchmarks.layers._setup import setup_seconds


def read(ctx):
    return setup_seconds(ctx, ("trace", "lower"))
