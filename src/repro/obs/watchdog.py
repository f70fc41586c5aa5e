"""Retrace/compile watchdog — the silent-failure sentinel for the warmed
arena path.

The whole PR-5/6/7 performance story rests on one invariant: after
``Arena.warmup``, same-shape runs never trace or compile again.  A
violated invariant does not crash — it silently multiplies latency
(a scan-body retrace at production shapes costs seconds to minutes) and
is invisible unless someone happens to diff ``Arena.traces``.  The
watchdog turns that diff into an automatic contract:

* :meth:`Watchdog.arm` (called by ``Arena.warmup`` when a watchdog is
  attached) snapshots the trace counter and the executable-cache keys.
* After every subsequent ``Arena.run``, the arena reports back
  (:meth:`observe_run`).  Any new scan-body trace or executable-cache
  key is a violation: the watchdog emits a structured
  ``watchdog.retrace`` event carrying the offending cache-key diff
  (which (bank layout, K_max, shards, eval, dropout) tuples appeared),
  records it in :attr:`violations`, and — in ``strict`` mode — raises
  :class:`RetraceError`.  Non-strict mode warns via ``warnings`` so
  un-observed deployments still surface the regression once.
* The baseline then advances, so one regression is reported once, not
  on every later run.

The streaming path's stall view is the ``arena.dispatch`` /
``arena.reduce`` spans: ``tools/obs_report.py``'s health summary reduces
them to p99 / p50 stall ratios over a flight log.
"""

from __future__ import annotations

import warnings
from typing import Any, Dict, List, Optional

from repro.obs import trace

__all__ = ["RetraceError", "Watchdog"]


class RetraceError(RuntimeError):
    """A strict watchdog saw a post-warmup scan-body retrace or cold
    compile."""


class Watchdog:
    """Arms on warmup, checks every run.  ``strict=True`` raises on a
    violation; otherwise a structured event + one Python warning.

    Attach with :meth:`attach` (or pass ``watchdog=`` to the arena's
    constructor-site code): the arena calls ``arm``/``observe_run`` at
    the right moments itself, so instrumented call sites need nothing.
    """

    def __init__(self, strict: bool = False):
        self.strict = strict
        self.armed = False
        self._traces = 0
        self._fn_keys: set = set()
        #: structured violation records (newest last): ``{"retraces",
        #: "new_executables", "run_meta"}``
        self.violations: List[Dict[str, Any]] = []

    def attach(self, arena) -> "Watchdog":
        """Bind to ``arena`` (one watchdog per arena); returns self."""
        arena.watchdog = self
        return self

    # -- the contract --------------------------------------------------------

    def arm(self, arena) -> None:
        """Snapshot the warmed state: any trace/compile beyond THIS
        point is unexpected."""
        self.armed = True
        self._traces = int(arena.traces)
        self._fn_keys = set(arena._fns)

    def observe_run(self, arena, run_meta: Optional[Dict[str, Any]] = None
                    ) -> Optional[Dict[str, Any]]:
        """Called by the arena after each ``run``.  Returns the
        violation record if one fired, else None."""
        if not self.armed:
            return None
        new_traces = int(arena.traces) - self._traces
        new_keys = sorted(set(arena._fns) - self._fn_keys, key=repr)
        if new_traces <= 0 and not new_keys:
            return None
        violation = {
            "retraces": int(new_traces),
            "new_executables": [repr(k) for k in new_keys],
            "run_meta": {k: run_meta[k] for k in
                         ("k_mode", "k_max", "dispatches",
                          "executables_built")
                         if run_meta and k in run_meta},
        }
        self.violations.append(violation)
        trace.event("watchdog.retrace", **violation)
        # advance the baseline: one regression = one report
        self._traces = int(arena.traces)
        self._fn_keys = set(arena._fns)
        if self.strict:
            raise RetraceError(
                f"post-warmup retrace: {new_traces} new scan-body "
                f"trace(s), {len(new_keys)} new executable cache "
                f"key(s) {violation['new_executables']} — the warmed "
                f"zero-retrace contract is broken (shape or eval "
                f"config drifted from the warmup call)")
        warnings.warn(
            f"obs.Watchdog: post-warmup retrace ({new_traces} new "
            f"trace(s), new cache keys {violation['new_executables']})",
            RuntimeWarning, stacklevel=2)
        return violation
