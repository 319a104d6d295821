"""Named phase timers for tracing/profiling.

TPU-native equivalent of the reference's ``Common::Timer global_timer`` +
RAII ``FunctionTimer`` (reference: include/LightGBM/utils/common.h:931,995),
which accumulates per-phase wall time and prints a report at exit when built
with USE_TIMETAG. Here the report is available programmatically and printed
when ``LIGHTGBM_TPU_TIMETAG=1``.

The package's own phase timers live in ``obs.telemetry`` (``obs.trace_phase``
feeds them, ``obs.report_timers`` prints the ``LIGHTGBM_TPU_TIMETAG``
report); nothing in the package feeds ``global_timer``. The class stays for
scripts that time their own phases.

Note: JAX dispatch is async — timers around jitted calls measure dispatch
unless the caller block_until_ready()s. Use ``timed_sync`` for device phases.
"""
from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Dict, Iterator


class Timer:
    def __init__(self) -> None:
        self._acc: Dict[str, float] = defaultdict(float)
        self._cnt: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def timed(self, name: str) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            self._acc[name] += time.perf_counter() - start
            self._cnt[name] += 1

    def add(self, name: str, seconds: float) -> None:
        self._acc[name] += seconds
        self._cnt[name] += 1

    def maybe_report(self) -> None:
        """Log the accumulated report when profiling is requested
        (LIGHTGBM_TPU_TIMETAG=1 — the reference's USE_TIMETAG analog) or at
        debug verbosity."""
        import os as _os
        from .log import Log as _Log
        if _os.environ.get("LIGHTGBM_TPU_TIMETAG") == "1":
            for line in self.report().splitlines():
                _Log.info("%s", line)
        else:
            for line in self.report().splitlines():
                _Log.debug("%s", line)

    def report(self) -> str:
        lines = ["LightGBM-TPU phase timers:"]
        for name in sorted(self._acc, key=self._acc.get, reverse=True):
            lines.append(
                "  %-40s %10.4f s  (%d calls)" % (name, self._acc[name], self._cnt[name])
            )
        return "\n".join(lines)

    def reset(self) -> None:
        self._acc.clear()
        self._cnt.clear()

    @property
    def times(self) -> Dict[str, float]:
        return dict(self._acc)


global_timer = Timer()


def maybe_print_report() -> None:
    if os.environ.get("LIGHTGBM_TPU_TIMETAG", "0") not in ("0", ""):
        print(global_timer.report())
