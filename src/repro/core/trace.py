"""The program's own spans and counters.

``span(name, **meta)`` times one layer boundary; ``count(name, n)`` counts
an event where it happens (a cache hit, a value handed to the device).
Both only add to in-memory totals per name, which :func:`snapshot` reads;
a reader takes the difference of two snapshots to get a window's share.

A span records its count, its wall and its *self* wall: the wall minus
the walls of the spans that ran inside it on the same thread.  Each thread
keeps its own span stack, so the sharded walk's group threads
(``Orchestrator._walk_wave_sharded``) nest their spans under nothing of
the main thread's.  The totals are always kept; there is no switch.

While a ``jax.profiler`` trace is being captured, each span is also a
``jax.profiler.TraceAnnotation`` carrying ``meta`` as its arguments, so the
program's spans land on the profiler's clock beside the device's
operations.  The profiler decides whether those events are written, and
:func:`captured` returns the totals over the newest capture, to set beside
it.  This module never imports jax: it binds the annotation once something
else has loaded jax (``core/`` stays importable without it).

Names are ``<layer>.<what>`` (``serve.wave``, ``device.walk_reduce.call``,
``cache.splice.hit``); docs/serving.md lists them.
"""
from __future__ import annotations

import sys
import threading
import time

__all__ = ["captured", "count", "snapshot", "span"]

_ZERO = (0, 0.0, 0.0)


class _Totals:
    """One thread's span stack and totals.  Only its thread writes them,
    so the hot path takes no lock; each total is one tuple, replaced in a
    single store, so a concurrent :func:`snapshot` reads it whole."""

    __slots__ = ("thread", "stack", "spans", "counters")

    def __init__(self, thread) -> None:
        self.thread = thread
        self.stack: list = []
        self.spans: dict = {}      # name -> (count, wall_s, self_s)
        self.counters: dict = {}

    def add(self, other: "_Totals") -> None:
        for k, v in list(other.spans.items()):
            a = self.spans.get(k, _ZERO)
            self.spans[k] = (a[0] + v[0], a[1] + v[1], a[2] + v[2])
        for k, n in list(other.counters.items()):
            self.counters[k] = self.counters.get(k, 0) + n


_lock = threading.Lock()           # guards _live and _retired
_live: list = []                   # _Totals of threads that may still run
_retired = _Totals(None)           # folded totals of finished threads
_local = threading.local()
_annotation = None                 # jax.profiler.TraceAnnotation, once bound
_capturing = False                 # a span boundary last saw a capture on
_capture: list = []                # [totals at its start, at its end or None]


def _fold_finished() -> None:
    """Fold the totals of finished threads (the sharded walk starts a
    pool per large wave) into ``_retired``; call with ``_lock`` held."""
    keep = []
    for t in _live:
        if t.thread.is_alive():
            keep.append(t)
        else:
            _retired.add(t)
    _live[:] = keep


def _mine() -> _Totals:
    try:
        return _local.totals
    except AttributeError:
        pass
    mine = _Totals(threading.current_thread())
    with _lock:
        _fold_finished()
        _live.append(mine)
    _local.totals = mine
    return mine


def _totals() -> _Totals:
    """Every thread's totals summed; call with ``_lock`` held."""
    out = _Totals(None)
    _fold_finished()
    out.add(_retired)
    for t in _live:
        out.add(t)
    return out


def _mark(on: bool) -> None:
    """A span boundary saw the profiler's capture start (``on``) or end:
    keep the totals at that instant."""
    global _capturing
    with _lock:
        if _capturing == on:
            return
        _capturing = on
        if on:
            _capture[:] = [_totals(), None]
        else:
            _capture[1] = _totals()


def _bind_annotation():
    """The profiler's annotation class once jax (with its profiler) is
    loaded, else None."""
    global _annotation
    prof = getattr(sys.modules.get("jax"), "profiler", None)
    if prof is not None:
        _annotation = prof.TraceAnnotation
    return _annotation


class span:
    """Context manager timing one span; ``wall`` holds its wall seconds
    after exit (the serving loop's phase walls read it)."""

    __slots__ = ("name", "meta", "wall", "_t0", "_child", "_ann", "_me")

    def __init__(self, name: str, **meta) -> None:
        self.name = name
        self.meta = meta
        self.wall = 0.0

    def __enter__(self) -> "span":
        ta = _annotation or _bind_annotation()
        if ta is not None and ta.is_enabled():
            if not _capturing:
                _mark(True)
            self._ann = ta(self.name, **self.meta)
            self._ann.__enter__()
        else:
            if _capturing:
                _mark(False)
            self._ann = None
        self._me = me = _mine()
        me.stack.append(self)
        self._child = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        wall = time.perf_counter() - self._t0
        self.wall = wall
        if self._ann is not None and not self._ann.is_enabled():
            _mark(False)           # the capture ended inside this span
        me = self._me
        st = me.stack
        st.pop()
        if st:
            st[-1]._child += wall
        c, w, s = me.spans.get(self.name, _ZERO)
        me.spans[self.name] = (c + 1, w + wall, s + wall - self._child)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)

    def note(self, **meta) -> None:
        """Add arguments to the span's profiler event (known only once the
        span has run a while); nothing when no profile is captured."""
        if self._ann is not None:
            self._ann.set_metadata(**meta)


def count(name: str, n: int = 1) -> None:
    c = _mine().counters
    c[name] = c.get(name, 0) + n


def snapshot() -> dict:
    """``{"spans": {name: (count, wall_s, self_s)}, "counters": {name: n}}``
    summed over every thread for the life of the process, spans still
    open not included."""
    with _lock:
        out = _totals()
    return {"spans": out.spans, "counters": out.counters}


def captured():
    """The totals of :func:`snapshot`'s form over the newest
    ``jax.profiler`` capture: from the first span boundary that saw it on
    to the first that saw it off (to now while it lasts).  A span counts
    whole where it closes.  None before any capture."""
    if _capturing and not _annotation.is_enabled():
        _mark(False)
    with _lock:
        if not _capture:
            return None
        t0, t1 = _capture
        if t1 is None:
            t1 = _totals()
    return {"spans": {k: tuple(a - b for a, b in
                               zip(v, t0.spans.get(k, _ZERO)))
                      for k, v in t1.spans.items()},
            "counters": {k: n - t0.counters.get(k, 0)
                         for k, n in t1.counters.items()}}
