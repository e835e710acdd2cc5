"""Good replies to requests due in the window, over the window's seconds."""


def read(ctx):
    return ctx["stats"]["good"] / ctx["seconds"]
