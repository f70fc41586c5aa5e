"""Device seconds per program scope, and the program's own work counts,
from a ``jax.profiler`` trace (``.xplane.pb``).

The program names its device layers with ``jax.named_scope``
(``repro.obs.scopes``).  Each compiled op carries its scope path as the
``tf_op`` stat of its entry in the device plane's event-metadata map,
e.g. ``jit(batched)/vmap(fl.local_sgd)/while/body/vmap(fl.sgd.grad)/
transpose(jvp(conv))/conv_general_dilated:``.  ``jax.profiler.ProfileData``
shows an op event's own stats only, so the small protobuf reader below
(standard library only) takes the paths from the file.  The op events
and their times are ``bench.xtrace.Trace``'s (device clock shifted onto
the host's); both list a plane's ``XLA Ops`` events in the file's order,
so the n-th op there is the n-th op here, and their HLO texts are
checked equal.

Time goes to scopes thus: each busy instant of the window belongs to
the innermost op event that covers it (the covering event that started
last, so a loop's body ops take their instants from the loop), and that
op's time counts for every scope on its path.  An instant whose op lies
in none of the seven layer scopes is ``unscoped`` (the outer rounds loop
between its body ops, copies).  The layers never nest inside one
another, so the layer totals and ``unscoped`` make up the busy time;
``fl.tier.<rows>`` and ``fl.sgd.*`` split a layer's time and are never
added to it.

The program's ``arena.dispatch`` spans, mirrored into the trace by
``repro.obs.trace.profiler_bridge``, carry ``sgd_steps`` and
``rows_trained`` as stats: the sequential local-SGD steps and the rows
trained (padded slots and tiers included) of what they dispatched.
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import re
import struct
import sys
from collections import defaultdict
from itertools import count
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

#: the program's layer scopes (``repro.obs.scopes.LAYERS``; the
#: benchmark imports nothing of the program, a test holds them equal)
LAYERS = ("fl.decide", "fl.select", "fl.gather", "fl.local_sgd",
          "fl.aggregate", "fl.queues", "fl.eval")
#: every fixed scope name (``repro.obs.scopes.SCOPES``)
SCOPES = LAYERS + ("fl.sgd.shuffle", "fl.sgd.batch", "fl.sgd.grad",
                   "fl.sgd.update")
#: ``fl.tier.<rows>``, one per tier of the bucket ladder
TIER_PREFIX = "fl.tier."
UNSCOPED = "unscoped"
#: the host span whose stats count the program's work
DISPATCH = "arena.dispatch"
COUNTS = ("sgd_steps", "rows_trained")

_TIER = re.compile(re.escape(TIER_PREFIX) + r"\d+")
_WRAPPED = re.compile(r"[A-Za-z_]\w*\((.*)\)")
_DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
_OPS_LINE = "XLA Ops"


# -- the protobuf wire format -------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    a varint, ``(start, end)`` for a length-delimited field, the raw
    bytes for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span, stat_names: Dict[int, str]):
    """(name, value) of one ``XStat``."""
    name, value = None, None
    for f, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f in (5, 6):
            value = _text(buf, v)
        elif f == 7:                       # a string kept in the metadata
            value = stat_names.get(v)
    return name, value


def _map(buf: bytes, span) -> Tuple[int, Tuple[int, int]]:
    """(key, value span) of one entry of a map<int64, message>."""
    key, value = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


@dataclasses.dataclass
class XSpace:
    """What the scope reduction needs of one ``.xplane.pb``."""
    #: device plane -> its ``XLA Ops`` events in file order:
    #: (HLO text, tf_op path or "")
    ops: Dict[str, List[Tuple[str, str]]]
    #: host events named ``DISPATCH``: (start ns, {stat: value})
    dispatches: List[Tuple[float, Dict[str, object]]]


def read_xspace(path: str) -> XSpace:
    with open(path, "rb") as f:
        buf = f.read()
    ops: Dict[str, List[Tuple[str, str]]] = {}
    dispatches: List[Tuple[float, Dict[str, object]]] = []
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:                                  # XSpace.planes
            continue
        name, lines, metadata, stat_spans = "", [], [], []
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                metadata.append(v)
            elif f == 5:
                stat_spans.append(v)
        device = bool(_DEVICE_PLANE.fullmatch(name))
        if not device and not name.startswith("/host:"):
            continue
        stat_names = {}
        for span in stat_spans:
            key, value = _map(buf, span)
            stat_names[key] = next((_text(buf, v) for f, v in
                                    _fields(buf, *value) if f == 2), "")
        events = {}
        for span in metadata:
            key, value = _map(buf, span)
            ev_name, stats = "", {}
            for f, v in _fields(buf, *value):
                if f == 2:
                    ev_name = _text(buf, v)
                elif f == 5 and device:
                    k, val = _stat(buf, v, stat_names)
                    stats[k] = val
            events[key] = (ev_name, stats.get("tf_op") or "")
        if device:
            ops[name] = _device_ops(buf, lines, events)
        else:
            wanted = {k for k, (n, _) in events.items() if n == DISPATCH}
            if wanted:
                dispatches += _host_events(buf, lines, wanted, stat_names)
    return XSpace(ops, dispatches)


def _line(buf: bytes, span):
    """(name, timestamp ns, event spans) of one ``XLine``."""
    name, ts, events = "", 0, []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            ts = v
        elif f == 4:
            events.append(v)
    return name, ts, events


def _device_ops(buf, lines, events) -> List[Tuple[str, str]]:
    out = []
    for span in lines:
        name, _, evs = _line(buf, span)
        if name != _OPS_LINE:
            continue
        for ev in evs:
            mid = next((v for f, v in _fields(buf, *ev) if f == 1), 0)
            out.append(events.get(mid, ("", "")))
    return out


def _host_events(buf, lines, wanted, stat_names):
    out = []
    for span in lines:
        _, ts, evs = _line(buf, span)
        for ev in evs:
            mid, offset_ps, stats = 0, 0, {}
            for f, v in _fields(buf, *ev):
                if f == 1:
                    mid = v
                    if mid not in wanted:
                        break
                elif f == 2:
                    offset_ps = v
                elif f == 4:
                    k, val = _stat(buf, v, stat_names)
                    stats[k] = val
            if mid in wanted:
                out.append((ts + offset_ps * 1e-3, stats))
    return out


# -- scope paths --------------------------------------------------------------

def components(tf_op: str) -> List[str]:
    """The path's components with transform wrappers peeled off:
    ``jit(f)/vmap(fl.local_sgd)/transpose(jvp(fl.sgd.grad))/dot:`` ->
    ``[f, fl.local_sgd, fl.sgd.grad, dot]``.  A trailing ``:<op type>``
    (the profiler's ``tf_op`` form) is dropped."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    out = []
    for part in path.split("/"):
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        out.append(part)
    return out


def scopes_of(tf_op: str) -> Tuple[str, ...]:
    """The scope names on the op's path, each once."""
    found = []
    for part in components(tf_op):
        if (part in SCOPES or _TIER.fullmatch(part)) and part not in found:
            found.append(part)
    return tuple(found)


def innermost(events: Sequence[Tuple[float, float, object]], lo: float,
              hi: float) -> Dict[object, float]:
    """``{key: time}``: each instant of ``[lo, hi)`` that some
    ``(start, end, key)`` event covers, given to the covering event that
    started last (of two that start together, the one that ends first)."""
    out: Dict[object, float] = defaultdict(float)
    heap: list = []
    order = count()
    t = lo
    live = sorted(((s, e, k) for s, e, k in events
                   if e > s and e > lo and s < hi),
                  key=lambda ev: (ev[0], ev[1]))
    for s, e, k in live + [(hi, hi, None)]:
        while heap and t < s:
            _, end, _, key = heap[0]
            if end <= t:
                heapq.heappop(heap)
                continue
            stop = min(end, s)
            out[key] += stop - t
            t = stop
        t = max(t, s)
        heapq.heappush(heap, (-s, e, next(order), k))
    return dict(out)


def join(trace_ops, paths: List[Tuple[str, str]]) -> List[str]:
    """The ``tf_op`` of each of ``trace_ops`` (``(start, end, HLO text)``
    in ``xtrace`` order): by position where the two lists pair up, else
    by HLO text, where a text that two programs share with different
    paths is left unscoped."""
    if len(trace_ops) == len(paths) and all(
            n == text for (_, _, n), (text, _) in zip(trace_ops, paths)):
        return [p for _, p in paths]
    by_text: Dict[str, str] = {}
    for text, p in paths:
        by_text[text] = p if by_text.get(text, p) == p else ""
    return [by_text.get(n, "") for _, _, n in trace_ops]


# -- the reduction ------------------------------------------------------------

@dataclasses.dataclass
class ScopeReading:
    """One traced window, reduced."""
    busy_s: float                  # device busy seconds (mean over devices)
    seconds: Dict[str, float]      # scope (and ``unscoped``) -> seconds
    scoped: bool                   # whether any op carried a scope
    sgd_steps: int                 # summed over the window's dispatches
    rows_trained: int
    dispatches: int

    def layer_s(self, *names: str) -> float:
        return sum(self.seconds.get(n, 0.0) for n in names)

    def breakdown(self) -> List[List[object]]:
        """[scope, device s]: the layers and ``unscoped`` first, then the
        sub-scopes, each group by time."""
        def group(keys):
            return sorted(([k, self.seconds[k]] for k in keys),
                          key=lambda kv: -kv[1])
        first = [k for k in self.seconds if k in LAYERS or k == UNSCOPED]
        rest = [k for k in self.seconds if k not in first]
        return group(first) + group(rest)


def reduce(trace, space: XSpace, lo: float, hi: float) -> ScopeReading:
    """Scope seconds of ``trace`` (an ``xtrace.Trace``) in ``[lo, hi)``,
    with the paths and dispatch counts of ``space`` (the same file)."""
    totals: Dict[str, float] = defaultdict(float)
    busy, scoped = 0.0, False
    for plane, ops in trace.devices.items():
        paths = join(ops, space.ops.get(plane, []))
        per_op = innermost([(s, e, p) for (s, e, _), p in zip(ops, paths)],
                           lo, hi)
        for path, ns in per_op.items():
            busy += ns
            names = scopes_of(path)
            scoped = scoped or bool(names)
            for name in names:
                totals[name] += ns
            if not any(n in LAYERS for n in names):
                totals[UNSCOPED] += ns
    n_dev = max(len(trace.devices), 1)
    seconds = {k: v * 1e-9 / n_dev for k, v in totals.items()}
    inside = [stats for t, stats in space.dispatches if lo <= t < hi]
    sums = {c: sum(int(s.get(c) or 0) for s in inside) for c in COUNTS}
    return ScopeReading(busy * 1e-9 / n_dev, seconds, scoped,
                        sums["sgd_steps"], sums["rows_trained"], len(inside))


def trace_path(ctx) -> Optional[str]:
    """The ``.xplane.pb`` that ``ctx.trace`` was loaded from.
    ``bench/run.py``'s ``Context`` does not keep it; the frame of the
    caller that built ``ctx`` holds it as ``path``."""
    path = getattr(ctx, "trace_path", None)
    frame = sys._getframe(1)
    while path is None and frame is not None:
        loc = frame.f_locals
        if loc.get("ctx") is ctx and str(loc.get("path", "")).endswith(
                ".xplane.pb"):
            path = loc["path"]
        frame = frame.f_back
    return path


def reading(ctx, log=sys.stderr) -> Optional[ScopeReading]:
    """The window's scope reading, reduced once per context (kept on it)
    and its ``device_scopes`` breakdown written to ``log``; None where
    the trace file cannot be found."""
    cached = getattr(ctx, "scope_reading", None)
    if cached is not None:
        return cached
    path = trace_path(ctx)
    if path is None:
        return None
    r = reduce(ctx.trace, read_xspace(path), ctx.lo, ctx.hi)
    ctx.scope_reading = r
    print("device_scopes: " + json.dumps(r.breakdown()), file=log)
    print(f"scope counts: sgd_steps {r.sgd_steps}, rows_trained "
          f"{r.rows_trained} over {r.dispatches} dispatch(es)", file=log)
    return r


# -- what the readers in bench/metrics/ share ---------------------------------

def ms_per_unit(ctx, *names: str) -> Optional[float]:
    """Device milliseconds of the scopes ``names`` per unit of work
    (scenario-rounds or rounds) of the window; None where the program
    names no scope."""
    r = reading(ctx)
    if r is None or not r.scoped or not ctx.run.units:
        return None
    return r.layer_s(*names) * 1e3 / ctx.run.units


def ms_per_sgd_step(ctx, name: str) -> Optional[float]:
    """Device milliseconds of scope ``name`` per sequential local-SGD
    step dispatched in the window."""
    r = reading(ctx)
    if r is None or not r.scoped or r.sgd_steps <= 0:
        return None
    return r.layer_s(name) * 1e3 / r.sgd_steps


def useful_rows_share(ctx) -> Optional[float]:
    """Percent of the rows trained that are true rows of the selected
    clients (E x floor(n/B) x B each, the count ``useful_flops`` makes)."""
    from bench.flops import train_flops
    r = reading(ctx)
    if r is None or r.rows_trained <= 0:
        return None
    run = ctx.run
    rows = run.useful_flops(run.selected_rounds, 0) / train_flops(run.cfg)
    return 100.0 * rows / r.rows_trained
