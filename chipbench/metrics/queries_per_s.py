"""Queries whose last node finished in the window, over the window.

The batch window runs whole batches, so every query of it finished in
it; the window spans the first submission to the last drain."""


def read(r):
    done = sum(1 for q in r.window.queries if not q["failed"])
    return done / r.window.seconds
