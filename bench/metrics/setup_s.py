"""Set-up: process start to the first due event (loading, data, fill,
warm-up, and in a cold checkout compilation)."""


def read(ctx):
    return ctx.setup_s
