"""repro.obs — the flight recorder: dependency-free observability for
the engine/arena/service stack.

Three instruments, one contract (no-ops unless enabled, never inside a
jit), and one list of names:

* :mod:`repro.obs.trace` — nestable host-side spans (``arena.plan`` /
  ``arena.compile`` / ``arena.dispatch`` / ``arena.reduce`` /
  ``service.*`` / ``store.*``) with pluggable sinks: an in-memory ring,
  an append-only JSONL flight-recorder file, and a Chrome-trace
  (Perfetto) exporter, plus an optional ``jax.profiler`` annotation
  bridge.
* :mod:`repro.obs.metrics` — one named counter/gauge/histogram registry
  per arena absorbing the formerly scattered tallies (``Arena.traces``,
  cache hit/miss counters, ``SweepService.stats``, chunk-store
  save/load counts), all of which remain as views over it.
* :mod:`repro.obs.watchdog` — the retrace/compile sentinel: armed by
  ``Arena.warmup``, it turns any post-warmup scan-body retrace or cold
  compile into a structured event (or a raise, in strict mode) with the
  offending executable-cache-key diff.
* :mod:`repro.obs.scopes` — the ``jax.named_scope`` names of the device
  program's layers (``fl.decide`` ... ``fl.eval``), which a device
  profile shows on every compiled op.

``tools/obs_report.py`` renders a JSONL flight-recorder file into the
per-phase time breakdown and health summary.
"""

from repro.obs import scopes, trace
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import (JsonlSink, MemorySink, export_chrome_trace,
                             install_sink, installed, load_jsonl,
                             remove_sink, span, to_chrome_trace)
from repro.obs.watchdog import RetraceError, Watchdog

__all__ = ["scopes", "trace", "span", "MemorySink", "JsonlSink",
           "installed", "install_sink", "remove_sink", "load_jsonl",
           "to_chrome_trace", "export_chrome_trace", "Counter", "Gauge",
           "Histogram", "MetricsRegistry", "RetraceError", "Watchdog"]
