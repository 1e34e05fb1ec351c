"""Share of the window spent consolidating and solving plans (%)."""


def read(r):
    w = r.window
    t = sum(r.spans.total(n, w.t0, w.t1) for n in ("consolidate",
                                                    "solve_plan"))
    return 100.0 * t / w.seconds
