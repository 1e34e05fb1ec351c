"""The load drivers a mix file names (``"driver"``), by name in ``DRIVERS``.

``batch_loop`` is a closed loop of whole batches: each batch is
consolidated, planned and run by one ``RealProcessor.run`` on the
persistent engine hosts, and the next is submitted when it drains.  The
window runs whole batches until ``seconds`` have passed; it spans from
the first batch's submission to the last one's drain, and every query in
it finished inside it.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional

from chipbench import generate, program


class Spans:
    """Harness spans around the calls into each layer: ``(name, start,
    end)`` on ``perf_counter``, mirrored into the profiler's trace as
    ``chipbench.<name>`` while one is recorded."""

    def __init__(self):
        self.items: List[tuple] = []
        self.lock = threading.Lock()
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                with self.lock:
                    self.items.append((name, t0, time.perf_counter()))

    def total(self, name: str, t0: float, t1: float) -> float:
        return sum(e - s for n, s, e in self.items
                   if n == name and s >= t0 and e <= t1)


class Window:
    """What a driver hands back: the window's bounds, every query due in
    it, and the counters read at both ends."""

    def __init__(self):
        self.t0 = self.t1 = 0.0
        self.queries: List[dict] = []
        self.before: Dict[str, int] = {}
        self.after: Dict[str, int] = {}
        self.compiles = 0
        self.error: Optional[BaseException] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, key: str) -> int:
        return self.after.get(key, 0) - self.before.get(key, 0)


def batch_loop(ctx, seconds: float, seed: int, batches: Optional[int] = None,
               on_start=None) -> Window:
    """Closed loop of whole batches for ``seconds`` (or ``batches``
    batches of the same size, the warm-up's form)."""
    win = Window()
    spans = ctx.spans
    win.before = program.counters(ctx.hosts)
    c0 = ctx.compiles.count()
    win.t0 = time.perf_counter()
    if on_start:
        on_start(win.t0)
    i = 0
    while True:
        tpl, bindings = generate.batch(ctx.mix, seed, i, ctx.tables)
        graph = ctx.graphs[tpl]
        try:
            with spans.span("consolidate"):
                cons = program.consolidate(graph, bindings)
            with spans.span("solve_plan"):
                plan = program.solve_plan(graph, cons, ctx.hw, ctx.workers)
            with spans.span("processor_run"):
                answers = program.run_batch(graph, ctx.models, ctx.tools,
                                            cons, plan, ctx.hosts,
                                            ctx.workers)
        except Exception as e:                  # the batch failed
            win.error = e
            answers = {}
        done = time.perf_counter()
        for q, b in enumerate(bindings):
            win.queries.append({"template": tpl, "binding": b,
                                "answers": answers.get(q, {}),
                                "failed": q not in answers, "done": done})
        i += 1
        if win.error is not None:
            break
        if (batches is not None and i >= batches) or \
                (batches is None and done - win.t0 >= seconds):
            break
    win.t1 = time.perf_counter()
    win.after = program.counters(ctx.hosts)
    win.compiles = ctx.compiles.count() - c0
    return win


DRIVERS = {"batch_loop": batch_loop}
