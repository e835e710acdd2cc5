"""Process start to the instant the window's first gap starts."""


def read(ctx):
    return ctx["setup_s"]
