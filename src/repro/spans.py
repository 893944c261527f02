"""Host spans of the program, on the device trace's clock while profiling.

``with span("train.fetch") as s: ...`` times its body on
``time.perf_counter`` and leaves the seconds in ``s.seconds``, always;
``last(name)`` gives them too, for the latest span of that name to close.
While a ``jax.profiler`` session runs, a span also

* opens a ``jax.profiler.TraceAnnotation`` of its name, so it lies beside
  the device's ops in the trace itself (TensorBoard, Perfetto), and
* appends ``(name, parent, start_ns, end_ns, attrs)`` to a bounded
  in-memory log (``log()``), times on ``time.perf_counter_ns``, ``parent``
  the position in ``log()`` of the innermost recorded span open on this
  thread (``None`` for none); ``s.note(key=value)`` adds to its ``attrs``.

JAX's compile phases of a jit's first call are logged too, as children of
the span open on the compiling thread: ``jax.trace`` (Python to jaxpr),
``jax.lower`` (jaxpr to MLIR) and ``jax.compile`` (backend compile, or load
from the persistent compilation cache), each with the ``fun_name`` JAX
gives it; ``jax.compile`` also says whether that cache served it
(``cache_hit``).  An inner jit is traced inside its outer one, so
``jax.*`` spans overlap: their union, not their sum, is the compile time.

Nothing is logged outside a profiler session, nor for a span opened
before the session started: a span then costs two clock readings, one
check of the profiler and one entry of the ``last`` table.
"""
from __future__ import annotations

import collections
import threading
import time

import jax
from jax.profiler import TraceAnnotation

MAX_RECORDS = 1 << 16
JAX_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
              "/jax/core/compile/backend_compile_duration": "jax.compile"}
CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_appended = 0           # records ever appended; a record's id is its number
_last: dict = {}        # name -> seconds of the latest span of it to close
_listening = False


class _Thread(threading.local):
    def __init__(self):
        self.open = []          # ids of recorded spans open, innermost last
        self.hit = False        # the persistent cache served this compile


_thread = _Thread()


def _append(name, start_ns, end_ns, attrs) -> list:
    """Log a record: [name, parent id, start_ns, end_ns, attrs, id]."""
    global _appended
    stack = _thread.open
    rec = [name, stack[-1] if stack else None, start_ns, end_ns, attrs]
    with _lock:
        rec.append(_appended)
        _appended += 1
        _records.append(rec)
    return rec


def _on_event(event, **kwargs):
    if event == CACHE_HIT and TraceAnnotation.is_enabled():
        _thread.hit = True


def _on_time_span(event, start_time, end_time, **kwargs):
    name = JAX_PHASES.get(event)
    if name is None:
        return
    hit, _thread.hit = _thread.hit, False
    if not TraceAnnotation.is_enabled():
        return
    # JAX times its phases on time.time(); the log is on perf_counter.
    shift = time.perf_counter_ns() - time.time_ns()
    attrs = {"fun_name": kwargs.get("fun_name")}
    if name == "jax.compile":
        attrs["cache_hit"] = hit
    _append(name, round(start_time * 1e9) + shift,
            round(end_time * 1e9) + shift, attrs)


def _listen():
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_time_span)


class span:
    """A timed block; see the module docstring."""

    __slots__ = ("name", "seconds", "_t0", "_ann", "_rec")

    def __init__(self, name: str):
        self.name = name
        self.seconds = None
        self._ann = self._rec = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        if TraceAnnotation.is_enabled():
            _listen()
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
            self._rec = _append(self.name, self._t0, None, {})
            _thread.open.append(self._rec[5])
        return self

    def note(self, **attrs):
        """Add ``attrs`` to the span's logged record; nothing when it is not
        logged."""
        if self._rec is not None:
            self._rec[4].update(attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.seconds = (t1 - self._t0) / 1e9
        _last[self.name] = self.seconds
        if self._rec is not None:
            self._rec[3] = t1
            _thread.open.pop()
            self._ann.__exit__(*exc)
            self._ann = self._rec = None
        return False


def last(name: str):
    """Seconds of the latest span called ``name`` to close, logged or not;
    None before one has."""
    return _last.get(name)


def log() -> list:
    """The records, oldest first: ``(name, parent, start_ns, end_ns,
    attrs)``; ``end_ns`` is None while the span is open, ``parent`` None
    where the parent has left the bounded log too."""
    with _lock:
        first = _appended - len(_records)
        return [(n, None if p is None or p < first else p - first, s, e,
                 dict(a)) for n, p, s, e, a, _ in _records]


def clear():
    """Empty the log.  A span still open is not logged when it closes, and
    the spans opened inside it get no parent."""
    with _lock:
        _records.clear()
