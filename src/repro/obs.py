"""Spans and compile attribution inside the program, on ``perf_counter_ns``.

The one span system of the serving path.  A span is ``(id, parent, name,
t0_ns, t1_ns, thread, attrs)``, stamped with ``time.perf_counter_ns()``:

* ``span(name, **attrs)`` — a context manager; nested spans on one thread
  take the innermost open one as their parent;
* ``begin(name, parent=None, **attrs)`` / ``end(s)`` — a span that ends
  on another thread (a wave that completes in engine callbacks);
* ``add(name, t0_ns, t1_ns=None, parent=None, **attrs)`` — a span from
  clock reads the caller takes anyway (a request's submit stamp, a task
  record's start and end).

Spans are recorded only while tracing is on: after ``enable()``, or while
a JAX profiler trace is being captured.  Off, ``span()`` and ``begin()``
return the one shared no-op ``NOOP``.  When a capture is first seen, the
recorder writes an anchor into the profiler's trace: a
``repro.obs.anchor#<id>`` annotation entered between two
``perf_counter_ns()`` reads, recorded as the span ``repro.obs.anchor``
with the same id, so a reader can map every span onto the device trace's
clock.

While tracing is on, one ``jax.monitoring`` listener adds each XLA
compile or persistent-cache load, with its seconds, to the innermost open
span of the thread that triggered it (attrs ``compiles``, ``compile_s``),
or to an unattributed total.

Records stay in memory up to ``CAP``; past it they are counted as
dropped.  ``drain()`` hands them to the caller; nothing is written to
disk here.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from repro.debugsync import named_lock

CAP = 200_000
ANCHOR = "repro.obs.anchor"
_BUILDS = ("/jax/core/compile/backend_compile_duration",
           "/jax/compilation_cache/cache_retrieval_time_sec")


class Span:
    """One open span; becomes a record tuple when it ends."""

    __slots__ = ("id", "parent", "name", "t0", "thread", "attrs")

    def __init__(self, name: str, parent: Optional[int], t0: int,
                 attrs: Dict[str, Any]):
        self.id = next(_ids)
        self.parent = parent
        self.name = name
        self.t0 = t0
        self.thread = threading.get_ident()
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Add attributes known only once the span's work has run."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        _stack().append(self)
        return self

    def __exit__(self, *exc) -> bool:
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        end(self)
        return False


class _NoSpan:
    """The span handed out while tracing is off: does nothing."""

    id = None
    t0 = None

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NOOP = _NoSpan()


class Recorder:
    """The process's records, bounded at ``cap``."""

    def __init__(self, cap: int = CAP):
        self.cap = cap
        self._lock = named_lock("Recorder._lock")
        self.records: List[tuple] = []      # guarded-by: self._lock
        self.dropped = 0                    # guarded-by: self._lock
        self.unattributed = [0, 0.0]        # guarded-by: self._lock

    def put(self, rec: tuple) -> None:
        with self._lock:
            if len(self.records) < self.cap:
                self.records.append(rec)
            else:
                self.dropped += 1

    def unattributed_build(self, secs: float) -> None:
        with self._lock:
            self.unattributed[0] += 1
            self.unattributed[1] += secs

    def drain(self) -> Dict[str, Any]:
        with self._lock:
            out = {"records": self.records, "dropped": self.dropped,
                   "unattributed": {"compiles": self.unattributed[0],
                                    "compile_s": self.unattributed[1]}}
            self.records, self.dropped = [], 0
            self.unattributed = [0, 0.0]
        out["threads"] = {t.ident: t.name for t in threading.enumerate()}
        return out


_REC = Recorder()
_ids = itertools.count(1)
_local = threading.local()
_forced = False          # enable() / disable()
_anchored = False        # this capture's anchor is written
_listening = False       # the monitoring listener is registered


def _stack() -> List[Span]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _resolve_capturing() -> bool:
    global _capturing
    from jax.profiler import TraceAnnotation
    _capturing = TraceAnnotation.is_enabled
    return _capturing()


_capturing = _resolve_capturing


def _on() -> bool:
    """Whether spans record now; writes the anchor when a profiler
    capture is first seen."""
    global _anchored
    if _capturing():
        if not _anchored:
            _anchored = True
            _listen()
            _anchor()
        return True
    _anchored = False
    return _forced


def _anchor() -> None:
    from jax.profiler import TraceAnnotation
    s = Span(ANCHOR, None, 0, {})
    ann = TraceAnnotation(f"{ANCHOR}#{s.id}")
    s.t0 = time.perf_counter_ns()
    ann.__enter__()
    t1 = time.perf_counter_ns()
    ann.__exit__(None, None, None)
    _REC.put((s.id, None, ANCHOR, s.t0, t1, s.thread, {}))


def _listen() -> None:
    global _listening
    if _listening:
        return
    _listening = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _on_duration(event: str, secs: float, **_) -> None:
    if event not in _BUILDS or not (_forced or _anchored):
        return
    st = _stack()
    if st:
        a = st[-1].attrs
        a["compiles"] = a.get("compiles", 0) + 1
        a["compile_s"] = a.get("compile_s", 0.0) + secs
    else:
        _REC.unattributed_build(secs)


def enable() -> None:
    """Record spans from now on, with or without a profiler capture."""
    global _forced
    _listen()
    _forced = True


def disable() -> None:
    """Record spans only while a profiler capture runs (the default)."""
    global _forced
    _forced = False


def span(name: str, **attrs):
    """A span around a ``with`` block, parented by the innermost open
    span of this thread; ``NOOP`` while tracing is off."""
    if not _on():
        return NOOP
    st = _stack()
    return Span(name, st[-1].id if st else None, time.perf_counter_ns(),
                attrs)


def begin(name: str, parent=None, t0: Optional[int] = None, **attrs):
    """Open a span that ``end()`` closes, possibly on another thread;
    ``t0`` reuses a clock read the caller already took."""
    if not _on():
        return NOOP
    return Span(name, getattr(parent, "id", None),
                time.perf_counter_ns() if t0 is None else t0, attrs)


def end(s, t1: Optional[int] = None) -> None:
    """Close a span from ``begin()`` (``NOOP`` is ignored)."""
    if s is NOOP:
        return
    _REC.put((s.id, s.parent, s.name, s.t0,
              time.perf_counter_ns() if t1 is None else t1, s.thread,
              s.attrs))


def add(name: str, t0: int, t1: Optional[int] = None, parent=None,
        **attrs) -> None:
    """Record a closed span from the caller's own clock reads (ns)."""
    end(begin(name, parent, t0, **attrs), t1)


def drain() -> Dict[str, Any]:
    """Every record so far, the dropped count, the unattributed compiles
    and the names of live threads by ident; the recorder starts empty."""
    return _REC.drain()
