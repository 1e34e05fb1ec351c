"""Model switches on the engine hosts per 100 queries."""


def read(r):
    n = len(r.window.queries)
    return 100.0 * r.window.delta("switches") / n if n else None
