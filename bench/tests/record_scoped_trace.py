"""Record the small scoped trace that ``test_perfbench_scopes.py`` reads.

Run on a TPU host from the checkout root:

    python bench/tests/record_scoped_trace.py [--raw PATH] [--out PATH]

It sets up the tiny arena cell (``tiny.py``), cut further so that the
trace stays small: 150 training rows (a ladder of two tiers, 16 and 32
rows) and rollouts of 2 rounds in one segment.  Then it traces one
rollout under ``jax.profiler`` inside a ``bench.window`` annotation,
with the program's spans mirrored in (a ``MemorySink`` and
``profiler_bridge``), as ``bench/run.py --trace 1`` does.  The trace as
recorded goes to ``--raw``; the pruned trace to ``--out``
(``bench/tests/data/tiny_tpu_scoped.xplane.pb``), and beside it a
``.json`` of what the readers need of the run: the configuration, the
units of work, the rows evaluated, the clients' sizes and the
selections.

    python bench/tests/record_scoped_trace.py --prune RAW [--out PATH]

prunes a recorded trace again, without a TPU.  Pruning keeps what
``bench/xtrace.py`` and ``bench/xscope.py`` read: the device planes'
``XLA Ops`` and ``XLA Modules`` events without their own stats, each
op's metadata with its name cut to the HLO op's name (``%fusion.12``)
and its ``tf_op`` stat, and the host planes' spans and launches.
"""

from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
OUT = Path(__file__).resolve().parent / "data" / "tiny_tpu_scoped.xplane.pb"
CELL = "cifar10_cnn.arena_zoo"
SEED = 2**33 + 5
DEVICE_LINES = ("XLA Ops", "XLA Modules")


def _raw_fields(buf: bytes, lo: int, hi: int):
    """(field number, start of the field, start of its value, end) of
    each field of the message in ``buf[lo:hi]``."""
    from bench.xscope import _varint
    i = lo
    while i < hi:
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        value = i
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 2:
            n, value = _varint(buf, i)
            i = value + n
        else:
            i += 8 if wire == 1 else 4
        yield key >> 3, start, value, i


def _varint_bytes(v: int) -> bytes:
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | 0x80 if v else b)
        if not v:
            return bytes(out)


def _message(field: int, payload: bytes) -> bytes:
    head = _varint_bytes(field << 3 | 2) + _varint_bytes(len(payload))
    return head + payload


def _varint_field(field: int, value: int) -> bytes:
    return _varint_bytes(field << 3) + _varint_bytes(value)


def prune(buf: bytes) -> bytes:
    """The trace ``buf`` with only what the readers read (module doc)."""
    return b"".join(_prune_plane(buf, lo, hi) for field, _, lo, hi in
                    _raw_fields(buf, 0, len(buf)) if field == 1)


def _prune_plane(buf: bytes, lo: int, hi: int) -> bytes:
    from bench import xscope, xtrace
    parts = list(_raw_fields(buf, lo, hi))
    name = next(xscope._text(buf, (v, e)) for f, _, v, e in parts if f == 2)
    device = bool(xscope._DEVICE_PLANE.fullmatch(name))
    if name == "/host:metadata" or not (device or name.startswith("/host:")):
        return b""
    # event (4) and stat (5) metadata: id -> (name, span of the message)
    meta = {4: {}, 5: {}}
    for f, _, v, e in parts:
        if f in meta:
            key, span = xscope._map(buf, (v, e))
            meta[f][key] = (next((xscope._text(buf, s) for g, s in
                                  xscope._fields(buf, *span) if g == 2), ""),
                            span)
    stat_names = {k: n for k, (n, _) in meta[5].items()}
    plane, used = bytearray(), set()
    for f, start, v, e in parts:
        if f == 3:
            plane += _prune_line(buf, v, e, meta[4], device, used)
        elif f not in meta:
            plane += buf[start:e]
    if not device:
        for f, start, v, e in parts:
            if f == 5 or (f == 4 and xscope._map(buf, (v, e))[0] in used):
                plane += buf[start:e]
        return _message(1, bytes(plane))
    # device metadata: the HLO op's name and its tf_op, the paths kept
    # once each as stat metadata and referenced
    tf_op = next(k for k, n in stat_names.items() if n == "tf_op")
    paths = {}
    for key in sorted(used):
        text, span = meta[4][key]
        path = next((val for g, s in xscope._fields(buf, *span) if g == 5
                     for k, val in [xscope._stat(buf, s, stat_names)]
                     if k == "tf_op"), None)
        body = _varint_field(1, key) + _message(2, text.split(" = ")[0]
                                                .encode())
        if path:
            ref = paths.setdefault(path, max(stat_names) + 1 + len(paths))
            body += _message(5, _varint_field(1, tf_op) +
                             _varint_field(7, ref))
        plane += _message(4, _varint_field(1, key) + _message(2, body))
    names = {**stat_names, **{ref: p for p, ref in paths.items()}}
    for key, text in names.items():
        body = _varint_field(1, key) + _message(2, text.encode())
        plane += _message(5, _varint_field(1, key) + _message(2, body))
    return _message(1, bytes(plane))


def _prune_line(buf, lo, hi, event_meta, device, used) -> bytes:
    """One ``XLine``: on a device, the readers' lines with their events'
    own stats dropped; on the host, the span and launch events."""
    from bench import xscope, xtrace
    lname, _, _ = xscope._line(buf, (lo, hi))
    if device and lname not in DEVICE_LINES:
        return b""
    line, kept = bytearray(), 0
    for g, gs, gv, ge in _raw_fields(buf, lo, hi):
        if g != 4:
            line += buf[gs:ge]
            continue
        mid = next(x for h, x in xscope._fields(buf, gv, ge) if h == 1)
        ev_name = event_meta.get(mid, ("",))[0]
        if not device and not (ev_name.startswith(xtrace.SPAN_PREFIXES)
                               or ev_name == xtrace.LAUNCH):
            continue
        used.add(mid)
        kept += 1
        line += _message(4, b"".join(
            buf[hs:he] for h, hs, _, he in _raw_fields(buf, gv, ge)
            if not (device and h == 4)))
    return _message(3, bytes(line)) if kept else b""


def record(raw: Path, out: Path) -> int:
    import jax
    import numpy as np
    from bench.loops import LOOPS
    from bench.tests.tiny import tiny_cell
    from repro.obs import trace as obs_trace

    if jax.devices()[0].platform != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    c = tiny_cell(CELL)
    cfg, cell = c["cfg"], c["cell"]
    cfg["data"]["examples"] = 150
    cfg["rounds"] = cell["chunk_size"] = cell["eval_every"] = 2
    run = LOOPS["arena"](cfg, cell, SEED)
    run.setup()
    with tempfile.TemporaryDirectory() as d:
        sink = obs_trace.install_sink(obs_trace.MemorySink())
        obs_trace.profiler_bridge(True)
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            run.window(1e9, max_units=1)
        jax.profiler.stop_trace()
        obs_trace.profiler_bridge(False)
        obs_trace.remove_sink(sink)
        raw.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0], raw)
    side = {"cfg": cfg, "cell": cell, "seed": SEED, "units": run.units,
            "eval_rows": run.eval_rows, "sizes": [int(n) for n in run.sizes],
            "selected_rounds": [np.asarray(s).tolist()
                                for s in run.selected_rounds]}
    out.with_suffix(".json").write_text(json.dumps(side) + "\n")
    return 0


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=OUT)
    ap.add_argument("--raw", type=Path, default=Path(
        tempfile.gettempdir()) / "scoped_raw.xplane.pb")
    ap.add_argument("--prune", type=Path, help="prune this recorded trace")
    args = ap.parse_args()
    raw = args.prune or args.raw
    if args.prune is None:
        code = record(raw, args.out)
        if code:
            return code
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_bytes(prune(raw.read_bytes()))

    from bench import xscope, xtrace
    from bench.run import Context
    side = json.loads(args.out.with_suffix(".json").read_text())
    ctx = Context(xtrace.Trace.load(str(args.out)), None, side["cfg"], {}, 1)
    ctx.trace_path = str(args.out)
    r = xscope.reading(ctx, log=sys.stdout)
    print("busy_s", r.busy_s, "window_s", ctx.window_s)
    print("bytes", raw.stat().st_size, "->", args.out.stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main())
