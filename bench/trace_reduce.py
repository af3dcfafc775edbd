"""Reduce a JAX profiler trace of one window to the numbers the per-layer
metrics read.

Input: the planes of an ``.xplane.pb`` as ``jax.profiler.ProfileData``
gives them (or any objects with the same ``name``/``lines``/``events``/
``start_ns``/``duration_ns`` shape, as the tests build by hand).

* Devices: planes named ``/device:TPU:<n>``.  A chip is busy while an
  event of its module-level line (``XLA Modules``, else ``XLA Ops``)
  runs; busy time is the union of those intervals inside the window.
  Device time per operation comes from ``XLA Ops``, or from the modules
  where a program has no op-level trace points.
* Host: the Python tracer's events (names ``$<file>:<line> <function>``)
  on the host plane, one line per thread.
* Window: the Python event of the function that ran the timed window
  (the cell's driver module names it).
"""

from __future__ import annotations

import re

_DEVICE = re.compile(r"/device:TPU:\d+")
_PY = re.compile(r"\$(?P<file>[^:\s]*)(?::\d+)? (?P<func>.+)")


def union(intervals) -> list:
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def parse_py(name: str):
    """``(file, function)`` of a Python tracer event name, else None."""
    m = _PY.fullmatch(name)
    return (m["file"], m["func"]) if m else None


class Trace:
    """One traced window: device busy intervals per chip, the top device
    operations, and the host's Python events."""

    def __init__(self, planes, window_fn: str):
        self.py_lines = []              # per thread: [(start, end, name)]
        chips = []                      # per chip: (busy line, ops line)
        for plane in planes:
            lines = {ln.name: ln for ln in plane.lines}
            if _DEVICE.fullmatch(plane.name):
                busy = lines.get("XLA Modules") or lines.get("XLA Ops")
                if busy is not None:
                    chips.append((busy, lines.get("XLA Ops") or busy))
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    evs = [sp for sp in _spans(ln) if sp[2].startswith("$")]
                    if evs:
                        self.py_lines.append(evs)
        win = [(s, e, i) for i, evs in enumerate(self.py_lines)
               for s, e, n in evs
               if (parse_py(n) or ("", ""))[1] == window_fn]
        if not win:
            raise ValueError(f"no Python event of {window_fn!r} in the "
                             f"trace: was the Python tracer on?")
        s, e, self.main_line = max(win, key=lambda w: w[1] - w[0])
        self.lo, self.hi = s, e
        self.window_s = (e - s) * 1e-9
        self.busy = [union(clip([(a, b) for a, b, _ in _spans(busy)], s, e))
                     for busy, _ in chips]
        # device seconds per op inside the window, summed over the chips;
        # an op's name is its HLO instruction, without the text.  A
        # program compiled without op-level trace points leaves the ops
        # line empty: its modules stand in for its ops.
        self.op_s: dict = {}
        for busy, ops in chips:
            if not self._add_ops(ops, s, e):
                self._add_ops(busy, s, e)

    def _add_ops(self, line, lo, hi) -> bool:
        seen = False
        for ev in line.events:
            seen = True
            a = max(ev.start_ns, lo)
            b = min(ev.start_ns + ev.duration_ns, hi)
            if b > a:
                name = ev.name.split(" = ")[0]
                self.op_s[name] = self.op_s.get(name, 0.0) + (b - a) * 1e-9
        return seen

    @property
    def chips(self) -> int:
        return len(self.busy)

    def busy_total_s(self) -> float:
        """Busy seconds summed over the chips."""
        return sum(length(b) for b in self.busy) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds, mean over the chips."""
        return self.busy_total_s() / max(self.chips, 1)

    def top_device_ops(self, n: int) -> list:
        """``[name, seconds]`` of the operations that took most device
        time, mean over the chips."""
        k = max(self.chips, 1)
        return [[name, t / k] for name, t in
                sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self, chip: int = 0) -> list:
        """Idle ``(start, end)`` intervals of one chip in the window."""
        out, t = [], self.lo
        for s, e in self.busy[chip] if self.busy else []:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.busy and t < self.hi:
            out.append((t, self.hi))
        return out

    def idle_gaps(self, n: int) -> list:
        """``[label, seconds]`` of the longest idle gaps of chip 0, each
        labelled by the innermost Python function that the thread which
        ran the window was in across the gap's middle."""
        evs = self.py_lines[self.main_line]
        out = []
        for s, e in sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) / 2
            inside = [ev for ev in evs if ev[0] <= mid <= ev[1]]
            label = (min(inside, key=lambda ev: ev[1] - ev[0])[2][1:]
                     if inside else "(no Python frame)")
            out.append([label, (e - s) * 1e-9])
        return out

    def host_seconds(self, functions) -> float | None:
        """Seconds the host spent in any of ``functions`` (pairs of file
        and function name), the union per thread summed over threads;
        ``None`` if none of them ran in the window."""
        want = set(map(tuple, functions))
        total, seen = 0.0, False
        for evs in self.py_lines:
            hits = [(max(s, self.lo), min(e, self.hi)) for s, e, name in evs
                    if parse_py(name) in want and e > self.lo
                    and s < self.hi]
            seen = seen or bool(hits)
            total += length(union(hits))
        return total * 1e-9 if seen else None


def _spans(line) -> list:
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
             ev.name) for ev in line.events]


def load(path: str, window_fn: str) -> Trace:
    import jax

    return Trace(jax.profiler.ProfileData.from_file(path).planes, window_fn)
