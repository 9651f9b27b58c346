"""setup_s: process start to the window's first request (stack build,
traffic, warm-up), by the host clock."""


def read(ctx):
    return ctx["setup_s"]
