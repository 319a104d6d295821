"""naked-timer: PERF.md measurement discipline."""
from __future__ import annotations

import ast
from typing import Iterator

from ..astutil import canonical_call, dotted, import_aliases_cached
from ..core import Finding, Rule, SourceFile, register

_TIMER_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
                "time.process_time", "time.perf_counter_ns",
                "time.monotonic_ns"}

#: the two modules that IMPLEMENT the trusted-timing discipline
_TIMER_IMPL = {"lightgbm_tpu/obs.py", "lightgbm_tpu/utils/timer.py"}


@register
class NakedTimerRule(Rule):
    """PERF.md measurement discipline: wall clocks must come from
    ``lightgbm_tpu.obs`` (``wall``/``timed_sync`` end in a forced
    1-element transfer; a bare ``perf_counter`` pair around an async
    dispatch times the enqueue, not the device)."""

    id = "naked-timer"
    description = ("raw time.time()/perf_counter() wall outside obs.py/"
                   "utils/timer.py; use obs.wall/obs.timed_sync/obs.sync")

    def check_file(self, f: SourceFile) -> Iterator[Finding]:
        if f.rel in _TIMER_IMPL:
            return
        aliases = import_aliases_cached(f)
        for node in f.walk_nodes():
            if isinstance(node, ast.Call) \
                    and canonical_call(node, aliases) in _TIMER_CALLS:
                yield f.finding(node, self.id,
                                "naked wall-clock timer %s(); use "
                                "lightgbm_tpu.obs (wall/timed_sync/sync)"
                                % dotted(node.func))
