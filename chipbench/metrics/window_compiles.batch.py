"""XLA programs compiled or loaded from the cache inside the window."""


def read(r):
    return r.window.compiles
