"""Span tracer — nestable wall-clock spans over the HOST-side control
plane, with pluggable sinks.

The runtime stack (engine -> dispatch planner -> arena -> sweep service)
is judged by the same kind of signals the paper applies to its clients:
per-phase latency, queue depths, silent regressions.  This module gives
every hot layer one instrument::

    from repro.obs import trace
    with trace.span("arena.dispatch", chunk=3, k_pad=8):
        outs = fn(*args)

Design constraints (the observability contract, pinned by
``tests/test_obs.py``):

* **No-op without a sink.**  ``span(...)`` returns a shared singleton
  no-op context manager when no sink is installed — no allocation, no
  clock read, no attribute dict.  Instrumented code pays a dict lookup
  and a truth test, nothing more, so the tracer can live on hot paths
  permanently.
* **Never inside a jit.**  Spans time Python-side orchestration (plan,
  compile, dispatch-call, host reduce).  Nothing here is traceable and
  nothing is ever called from inside a traced function — jax dispatch
  being async, a span around an executable call measures *dispatch*
  latency unless the caller blocks (the arena's reduce spans wrap the
  blocking ``np.asarray``, which is the honest device-time proxy).
* **Structured records.**  A completed span emits one flat dict:
  ``{"name", "ts", "dur", "id", "parent", "depth", "attrs"}`` with
  ``ts``/``dur`` in seconds relative to the module epoch.  Sinks receive
  the dict AFTER the span closes (children before parents, Chrome-trace
  style).
* **Pluggable sinks.**  :class:`MemorySink` (bounded ring),
  :class:`JsonlSink` (one JSON object per line, append-only — the
  ``runlogs/`` flight-recorder format ``tools/obs_report.py`` renders),
  or anything with an ``emit(record) -> None``.  ``installed()``
  context-manages a sink's lifetime for tests and benches.
* **jax.profiler bridge.**  ``profiler_bridge(True)`` additionally
  enters a ``jax.profiler.TraceAnnotation`` per span, its scalar
  attributes as the event's stats, so a captured device profile
  (``jax.profiler.trace``) shows the same taxonomy and counts (the
  ``arena.dispatch`` span's ``sgd_steps`` / ``rows_trained``, the
  ``arena.reduce`` span's ``tier_rounds_run`` / ``tier_rounds_skipped``);
  off by default because annotations cost even when no profile is
  active.

Span taxonomy (see docs/architecture.md "Observability"): dotted
``layer.phase`` names — ``arena.plan`` / ``arena.probe`` /
``arena.compile`` / ``arena.upload`` / ``arena.dispatch`` /
``arena.reduce`` / ``arena.eval`` / ``arena.run`` / ``arena.warmup`` /
``service.batch`` / ``service.reduce`` / ``store.save`` /
``store.load`` / ``engine.round`` / ``trainer.round``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

__all__ = ["span", "event", "install_sink", "remove_sink", "clear_sinks",
           "installed", "profiler_bridge", "MemorySink", "JsonlSink",
           "to_chrome_trace", "export_chrome_trace", "load_jsonl"]

# module epoch: every record's ts is relative to this, so one run's
# records are mutually comparable and small enough for exact float math
_EPOCH = time.perf_counter()

_SINKS: List[Any] = []
_PROFILER_BRIDGE = False

# span ids are process-global and monotonically increasing; the active
# span stack is thread-local so concurrent host threads nest correctly
_LOCK = threading.Lock()
_NEXT_ID = [0]
_TLS = threading.local()


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _emit(record: Dict[str, Any]) -> None:
    for sink in list(_SINKS):
        sink.emit(record)


class _NoopSpan:
    """The shared do-nothing span — returned whenever no sink is
    installed, so un-observed runs pay (almost) nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NoopSpan":
        return self


_NOOP = _NoopSpan()

# attribute types the profiler bridge passes on as the annotation's stats
_SCALARS = (bool, int, float, str)


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "depth", "t0",
                 "_annotation")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._annotation = None

    def set(self, **attrs) -> "_Span":
        """Attach attributes discovered mid-span (e.g. how many
        executables a plan produced); the scalar ones also go on the
        live profiler annotation."""
        self.attrs.update(attrs)
        if self._annotation is not None:  # pragma: no cover
            scalars = {k: v for k, v in attrs.items()
                       if isinstance(v, _SCALARS)}
            if scalars:
                self._annotation.set_metadata(**scalars)
        return self

    def __enter__(self) -> "_Span":
        st = _stack()
        with _LOCK:
            self.id = _NEXT_ID[0]
            _NEXT_ID[0] += 1
        self.parent = st[-1].id if st else None
        self.depth = len(st)
        st.append(self)
        if _PROFILER_BRIDGE:        # pragma: no cover - needs profiler
            try:
                from jax.profiler import TraceAnnotation
                self._annotation = TraceAnnotation(
                    self.name, **{k: v for k, v in self.attrs.items()
                                  if isinstance(v, _SCALARS)})
                self._annotation.__enter__()
            except Exception:
                self._annotation = None
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._annotation is not None:  # pragma: no cover
            self._annotation.__exit__(*exc)
            self._annotation = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _emit({"name": self.name, "ts": self.t0 - _EPOCH,
               "dur": t1 - self.t0, "id": self.id, "parent": self.parent,
               "depth": self.depth, "attrs": self.attrs})
        return False


def span(name: str, **attrs) -> Any:
    """A context manager timing one named phase.  Returns the shared
    no-op singleton when no sink is installed — the zero-overhead
    contract — otherwise a live :class:`_Span` recording wall time,
    ``attrs``, and its position in the active span tree."""
    if not _SINKS:
        return _NOOP
    return _Span(name, attrs)


def event(name: str, **attrs) -> None:
    """An instantaneous structured record (``dur`` 0, no stack entry) —
    the watchdog's warning channel.  No-op without a sink."""
    if not _SINKS:
        return
    st = _stack()
    with _LOCK:
        eid = _NEXT_ID[0]
        _NEXT_ID[0] += 1
    _emit({"name": name, "ts": time.perf_counter() - _EPOCH, "dur": 0.0,
           "id": eid, "parent": st[-1].id if st else None,
           "depth": len(st), "attrs": attrs})


# -- sinks -------------------------------------------------------------------


class MemorySink:
    """Bounded in-memory ring of completed span records (newest kept)."""

    def __init__(self, capacity: int = 4096):
        self.records: deque = deque(maxlen=capacity)

    def emit(self, record: Dict[str, Any]) -> None:
        self.records.append(record)

    def by_name(self, name: str) -> List[Dict[str, Any]]:
        return [r for r in self.records if r["name"] == name]


class JsonlSink:
    """Appends one JSON object per completed span to ``path`` — the
    flight-recorder file format (``runlogs/<run>.jsonl``) that
    ``tools/obs_report.py`` renders and :func:`load_jsonl` reads back.
    Values in ``attrs`` must be JSON-serialisable; numpy scalars are
    coerced via their ``item()``."""

    def __init__(self, path: str, flush_every: int = 64):
        directory = os.path.dirname(path)
        if directory:
            os.makedirs(directory, exist_ok=True)
        self.path = path
        self._fh = open(path, "a")
        self._since_flush = 0
        self._flush_every = max(1, int(flush_every))

    @staticmethod
    def _jsonable(value: Any) -> Any:
        if hasattr(value, "item") and not isinstance(value, (str, bytes)):
            try:
                return value.item()
            except Exception:
                return repr(value)
        if isinstance(value, (list, tuple)):
            return [JsonlSink._jsonable(v) for v in value]
        if isinstance(value, dict):
            return {str(k): JsonlSink._jsonable(v)
                    for k, v in value.items()}
        if isinstance(value, (str, int, float, bool)) or value is None:
            return value
        return repr(value)

    def emit(self, record: Dict[str, Any]) -> None:
        rec = dict(record)
        rec["attrs"] = self._jsonable(record.get("attrs", {}))
        self._fh.write(json.dumps(rec) + "\n")
        self._since_flush += 1
        if self._since_flush >= self._flush_every:
            self._fh.flush()
            self._since_flush = 0

    def close(self) -> None:
        if not self._fh.closed:
            self._fh.flush()
            self._fh.close()

    def __enter__(self) -> "JsonlSink":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def install_sink(sink: Any) -> Any:
    """Register ``sink`` (anything with ``emit(record)``); returns it."""
    _SINKS.append(sink)
    return sink


def remove_sink(sink: Any) -> None:
    if sink in _SINKS:
        _SINKS.remove(sink)


def clear_sinks() -> None:
    del _SINKS[:]


@contextmanager
def installed(sink: Any):
    """``with trace.installed(MemorySink()) as sink: ...`` — sink bound
    for the block, removed (and JsonlSinks closed) on exit."""
    install_sink(sink)
    try:
        yield sink
    finally:
        remove_sink(sink)
        if hasattr(sink, "close"):
            sink.close()


def profiler_bridge(enabled: bool) -> None:
    """Mirror every live span as a ``jax.profiler.TraceAnnotation`` so a
    captured device profile (Perfetto / TensorBoard) carries the same
    span taxonomy, with the span's scalar attributes (those entered
    with it and those ``set`` while it is open) as the event's stats.  Off
    by default — annotations are not free even without an active
    profile, and the no-sink fast path must stay untouched (the bridge
    only fires on spans a sink already made live)."""
    global _PROFILER_BRIDGE
    _PROFILER_BRIDGE = bool(enabled)


# -- chrome trace export -----------------------------------------------------


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    """Read a :class:`JsonlSink` file back into span records (blank
    lines skipped — a crashed writer's torn last line raises, matching
    the flight-recorder expectation that the log is append-only and
    line-atomic)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def to_chrome_trace(records: List[Dict[str, Any]],
                    process_name: str = "repro") -> Dict[str, Any]:
    """Span records -> Chrome Trace Event JSON (the ``chrome://tracing``
    / Perfetto ``traceEvents`` array of complete ``"X"`` events, ts/dur
    in microseconds).  Instant records (``dur == 0``) become ``"i"``
    events."""
    events: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
        "args": {"name": process_name}}]
    for r in records:
        common = {"name": r["name"], "pid": 0, "tid": 0,
                  "ts": round(float(r["ts"]) * 1e6, 3),
                  "args": dict(r.get("attrs", {}))}
        if r.get("dur", 0.0) > 0.0:
            events.append({**common, "ph": "X",
                           "dur": round(float(r["dur"]) * 1e6, 3)})
        else:
            events.append({**common, "ph": "i", "s": "t"})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def export_chrome_trace(records: List[Dict[str, Any]], path: str,
                        process_name: str = "repro") -> str:
    directory = os.path.dirname(path)
    if directory:
        os.makedirs(directory, exist_ok=True)
    with open(path, "w") as f:
        json.dump(to_chrome_trace(records, process_name), f)
    return path
