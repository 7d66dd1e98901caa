"""Seconds from the start of the process to the start of the window:
start-up, building the store, compiling or loading the compiled step,
and loading every record."""


def read(ctx):
    return ctx.setup_s
