"""Profiler capture and the reduction from a trace to numbers.

Busy time is the union of the intervals in which a device operation ran,
clipped to the window (the benchmark's own ``pb.window`` host span), and
averaged over the devices that ran any; the idle share is 1 minus busy
over the window.  Each idle gap is labelled by the benchmark's host span
(``pb.generate``, ``pb.put``, ``pb.get``, ``pb.fetch``) that overlaps it
most, so the gaps say what the host was doing while the device waited.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re
import shutil
import tempfile
import types

WINDOW_SPAN = "pb.window"
SPAN_PREFIX = "pb."
OP_LINE = "XLA Ops"             # the device line that holds the ops
TOP = 10


@contextlib.contextmanager
def capture(on: bool):
    """Trace the body when ``on``, into a new directory under ``TMPDIR``;
    afterwards the yielded object's ``path`` names the trace file
    (``reduce_file`` reads it and removes the directory)."""
    c = types.SimpleNamespace(path=None, dir=None)
    if not on:
        yield c
        return
    import jax
    c.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
    jax.profiler.start_trace(c.dir)
    try:
        yield c
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(c.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        c.path = found[0] if found else None


def union(intervals):
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi):
    """The complement of disjoint sorted ``busy`` within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gap(gap, spans):
    """The name of the host span that overlaps ``gap`` most (``idle``
    where none does)."""
    best, name = 0.0, "no span"
    for n, s, e in spans:
        o = min(e, gap[1]) - max(s, gap[0])
        if o > best:
            best, name = o, n
    return name


def is_kernel(hlo: str) -> bool:
    """A Pallas kernel: XLA calls it as a ``tpu_custom_call``."""
    return 'custom_call_target="tpu_custom_call"' in hlo


_OPCODE = re.compile(r" ([a-z][\w.-]*)\(")


def label(hlo: str) -> str:
    """A short name for a device op from its HLO text: the instruction's
    name, its opcode and the start of its shape."""
    head, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(" " + rest)
    if not m:
        return head[:60]
    return f"{head} {m.group(1)} {rest[:max(m.start() - 1, 0)][:40]}".strip()


def self_times(events):
    """Each event's time less that of the events nested in it (a device
    line nests a loop's body ops inside the loop's own event)."""
    out, stack = [], []           # stack: [index, end]
    for i, (_, s, e, _) in sorted(enumerate(events),
                                  key=lambda x: (x[1][1], -x[1][2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([i, e - s])
        if stack:
            out[stack[-1][0]][1] -= e - s
        stack.append([len(out) - 1, e])
    return [(events[i], t) for i, t in out]


def reduce(devices: dict, spans: list) -> dict:
    """``devices``: {device: [(label, start_ns, end_ns, is_kernel)]};
    ``spans``: [(name, start_ns, end_ns)] host spans.  Times in seconds.
    ``device_ops`` ranks ops by self time, so a loop and its body are
    not counted twice."""
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = window[0]
    host = [(n, s, e) for n, s, e in spans
            if n != WINDOW_SPAN and e > lo and s < hi]
    busy_ns, kernel_ns, used = 0.0, 0.0, 0
    by_op = collections.Counter()
    by_gap = collections.Counter()
    for events in devices.values():
        inside = [(n, max(s, lo), min(e, hi), k) for n, s, e, k in events
                  if e > lo and s < hi]
        if not inside:
            continue
        used += 1
        busy = union([(s, e) for _, s, e, _ in inside])
        busy_ns += sum(e - s for s, e in busy)
        for (name, s, e, kernel), t in self_times(inside):
            by_op[name] += t
            if kernel:
                kernel_ns += e - s
        for g in gaps(busy, lo, hi):
            by_gap[label_gap(g, host)] += g[1] - g[0]
    if not used:
        raise ValueError("no device operation ran in the traced window")
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / used / 1e9,
        "kernel_s": kernel_ns / used / 1e9,
        "devices": used,
        "device_ops": [[n, v / used / 1e9]
                       for n, v in by_op.most_common(TOP)],
        "idle_gaps": [[n, v / used / 1e9]
                      for n, v in by_gap.most_common(TOP)],
    }


def read(path: str) -> tuple[dict, list]:
    """Device op events and the benchmark's host spans from an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    names = {}                    # HLO text -> (label, is_kernel)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            events = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    hlo = ev.name
                    if hlo not in names:
                        names[hlo] = (label(hlo), is_kernel(hlo))
                    name, kernel = names[hlo]
                    s = ev.start_ns
                    events.append((name, s, s + ev.duration_ns, kernel))
            if events:
                devices[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return devices, spans


def reduce_file(captured) -> dict:
    """Reduce a capture's trace, then remove it."""
    try:
        if captured.path is None:
            raise ValueError("the profiler wrote no trace")
        return reduce(*read(captured.path))
    finally:
        shutil.rmtree(captured.dir, ignore_errors=True)
