"""From a profiler trace (.xplane.pb) and the compiled module's text to device
seconds per scope. Kept with the benchmark so every PR reduces the same way.

What a v5e trace looks like (chip run, PR 23): plane ``/device:TPU:0``, line
``XLA Ops``; an event's name is the whole HLO instruction (``%fusion.260 =
f32[..] fusion(..)``) and carries no scope, so the scope comes from the
compiled module's ``op_name`` metadata, keyed by instruction name.
``%while`` / ``%conditional`` events ENCLOSE the events of their bodies, so
only leaf events are summed. The program's host annotations (``lgbtpu/*``)
are on plane ``/host:CPU``, on the same clock, and so is the benchmark's own
``bench/train_block`` around the traced call: the traced period. Line ``XLA
Modules`` has one event for each run of a compiled program."""
import re

import numpy as np

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')
_SCOPE = re.compile(r"(lgbtpu/[\w\-]+)")


def scope_of(op_name):
    """Outermost ``lgbtpu/<phase>`` component of an op_name, or ''."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else ""


def scope_map(hlo_text):
    """instruction name -> outermost lgbtpu scope ('' when it has none)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def instruction_name(event_name):
    return event_name.split(" = ", 1)[0].lstrip("%")


def leaf_mask(start, end):
    """Events sorted by (start, longer first): an event is a leaf when the
    next event does not start inside it."""
    leaf = np.ones(len(start), dtype=bool)
    leaf[:-1] = start[1:] >= end[:-1]
    return leaf


def union_seconds(start, end):
    """(busy seconds, gaps as (gap start, gap end) arrays) of intervals
    sorted by start, in ns."""
    reach = np.maximum.accumulate(end)
    gap = start[1:] > reach[:-1]
    busy = (reach[-1] - start[0]) - (start[1:][gap] - reach[:-1][gap]).sum()
    return busy / 1e9, reach[:-1][gap], start[1:][gap]


GAPS_ATTRIBUTED = 512   # the longest idle gaps get a name, the rest are summed


def reduce_events(codes, vocab, start_ns, dur_ns, scopes, host_spans=(),
                  window=None):
    """-> dict: busy_s, window_s, by_scope {scope: s}, by_op
    {scope/instruction: s}, idle_gaps {annotation: s}. Event i is instruction
    ``vocab[codes[i]]``; ``scopes`` maps instruction names to scopes;
    ``host_spans`` are (name, start_ns, end_ns) of host annotations.
    ``window`` is (start_ns, end_ns) on the trace's clock: events outside it
    are dropped and its ends count as idle; without it the window runs from
    the first device event to the last. The longest idle gaps are given to
    the innermost annotation that holds their midpoint."""
    start = np.asarray(start_ns, dtype=np.float64)
    dur = np.asarray(dur_ns, dtype=np.float64)
    codes = np.asarray(codes)
    events = len(start)
    if window is not None:
        inside = (start >= window[0]) & (start + dur <= window[1])
        start, dur, codes = start[inside], dur[inside], codes[inside]
    order = np.lexsort((-dur, start))
    start, dur, codes = start[order], dur[order], codes[order]
    end = start + dur
    w0, w1 = window if window is not None else (start[0], end.max())
    leaf = leaf_mask(start, end)
    per_op = np.bincount(codes[leaf], weights=dur[leaf], minlength=len(vocab)) / 1e9
    by_scope, by_op = {}, {}
    for name, seconds in zip(vocab, per_op):
        if seconds:
            scope = scopes.get(name, "")
            by_scope[scope] = by_scope.get(scope, 0.0) + float(seconds)
            by_op[(scope + "/" if scope else "") + name] = float(seconds)
    busy, g0, g1 = union_seconds(start[leaf], end[leaf])
    g0 = np.concatenate([[w0], g0, [end.max()]])   # the window's idle ends
    g1 = np.concatenate([[start[0]], g1, [w1]])
    longest = np.argsort(g0 - g1)[:GAPS_ATTRIBUTED]
    gaps = {}
    if len(g0) > len(longest):
        gaps["(gaps too short to attribute)"] = float(
            (g1 - g0).sum() - (g1 - g0)[longest].sum()) / 1e9
    s = np.array([sp[1] for sp in host_spans], dtype=np.float64)
    e = np.array([sp[2] for sp in host_spans], dtype=np.float64)
    for a, b in zip(g0[longest], g1[longest]):
        mid, who = (a + b) / 2, "(no lgbtpu annotation)"
        inside = np.flatnonzero((s <= mid) & (mid <= e))
        if inside.size:
            who = host_spans[inside[np.argmin((e - s)[inside])]][0]
        gaps[who] = gaps.get(who, 0.0) + float(b - a) / 1e9
    return {"busy_s": float(busy), "window_s": float(w1 - w0) / 1e9,
            "by_scope": by_scope, "by_op": by_op, "idle_gaps": gaps,
            "events": int(events), "events_in_window": int(len(start)),
            "leaf_events": int(leaf.sum())}


def read_xplane(path, device_plane="/device:TPU:0", line="XLA Ops",
                module_line="XLA Modules", host_plane="/host:CPU",
                host_prefixes=("lgbtpu/", "bench/")):
    """-> (codes, vocab, start_ns, dur_ns, host_spans, modules) of one trace:
    device event i is instruction ``vocab[codes[i]]``; ``modules`` are (name,
    start_ns, end_ns) of the programs the device ran."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    codes, start, dur, spans, modules, code_of, vocab = [], [], [], [], [], {}, []
    for plane in data.planes:
        if plane.name == device_plane:
            for ln in plane.lines:
                if ln.name == module_line:
                    modules += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                                for ev in ln.events]
                if ln.name != line:
                    continue
                for ev in ln.events:
                    full = ev.name
                    code = code_of.get(full)
                    if code is None:
                        code = code_of[full] = len(vocab)
                        vocab.append(instruction_name(full))
                    codes.append(code)
                    start.append(ev.start_ns)
                    dur.append(ev.duration_ns)
        elif plane.name == host_plane:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(host_prefixes):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return codes, vocab, start, dur, spans, modules
