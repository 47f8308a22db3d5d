"""Set-up: process start to the start of the measured window (host clock):
making the inputs, loading or compiling every program, warming up."""


def read(ctx):
    return ctx["setup_s"]
