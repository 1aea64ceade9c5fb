"""Executables compiled or loaded from the compile cache between the first
and the last traced step. A warm run reads 0."""


def read(ctx):
    return float(ctx.compiles_in_window)
