"""From a profiler trace to device busy time, op time and idle gaps.

`Profile` records JAX's profiler trace around the measured window and reads
back two kinds of event: the operations that ran on each TPU (the device
planes' ``XLA Ops`` line, each tagged with the program it belongs to, from
the ``XLA Modules`` line), and the host spans that label what the host was
doing (``dispatch/bucket{i}`` from the server's profiler annotations and
the benchmark's own ``kgbench/*`` spans). `reduce` turns those events into
the numbers the benchmark reports. Events are plain tuples, so a recorded
sample can be checked without the profiler (see testdata/).
"""
from __future__ import annotations

import bisect
import glob
import os
import shutil
import tempfile

WINDOW = "kgbench/window"
HOST_PREFIXES = ("kgbench/", "dispatch/")


class Profile:
    """Context manager: profile the block into a temporary directory, then
    read its events (and remove the directory)."""

    def __init__(self):
        self.dir = None
        self._events = None

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="kgbench-trace-")
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def events(self) -> dict:
        """The trace's device ops and host spans (read once)."""
        if self._events is None:
            path = sorted(glob.glob(os.path.join(
                self.dir, "**", "*.xplane.pb"), recursive=True))[-1]
            self._events = read_xplane(path)
            shutil.rmtree(self.dir, ignore_errors=True)
        return self._events


def read_xplane(path: str) -> dict:
    """{"device": [[chip, program, op, start_ns, dur_ns], ...],
    "host": [[name, start_ns, dur_ns], ...]} from an .xplane.pb file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            chip = int(name[12:])
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for e in lines.get("XLA Ops", []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                prog = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                    else "?"
                op = e.name.split(" = ", 1)[0]   # "%fusion.12 = ..." -> name
                device.append([chip, prog, op, int(e.start_ns),
                               int(e.duration_ns)])
        elif name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class _Spans:
    """Host spans, sorted by start, for finding those over an interval."""

    def __init__(self, spans):
        self.spans = sorted((s, d, n) for n, s, d in spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((d for _, d, _ in self.spans), default=0)

    def label(self, a: int, b: int) -> str:
        """The span that best covers [a, b): of those overlapping it at
        least half as much as the best one, the shortest (innermost)."""
        lo = bisect.bisect_left(self.starts, a - self.longest)
        hi = bisect.bisect_left(self.starts, b)
        over = [(min(b, s + d) - max(a, s), d, n)
                for s, d, n in self.spans[lo:hi] if s + d > a]
        if not over:
            return "no host span"
        top = max(o for o, _, _ in over)
        return min((d, n) for o, d, n in over if o >= top / 2)[1]


def reduce(events: dict, executed: int | None = None) -> dict:
    """Device busy seconds (union of op intervals, averaged over chips),
    the window's seconds, seconds per program:op (summed over chips,
    largest first), and the idle gaps of the first chip, longest first,
    each with the host span that covers it. The window is the benchmark's
    ``kgbench/window`` span where the trace has it, else the span of the
    device ops."""
    host = [h for h in events["host"] if h[0] != WINDOW]
    win = [h for h in events["host"] if h[0] == WINDOW]
    dev = events["device"]
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
    elif dev:
        w0 = min(d[3] for d in dev)
        w1 = max(d[3] + d[4] for d in dev)
    else:
        w0 = w1 = 0
    chips = sorted({d[0] for d in dev})
    per_op: dict[str, float] = {}
    busy = []
    gaps = []
    for k, chip in enumerate(chips):
        ivs = []
        for c, prog, op, s, d in dev:
            if c != chip:
                continue
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                ivs.append((a, b))
                key = f"{prog}:{op}"
                per_op[key] = per_op.get(key, 0.0) + (b - a) / 1e9
        u = _union(ivs)
        busy.append(sum(b - a for a, b in u))
        if k == 0:
            edges = [w0] + [x for iv in u for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = _Spans(host)
    labels = [spans.label(a, b) for a, b in gaps]
    idle: dict[str, float] = {}
    for (a, b), lab in zip(gaps, labels):
        idle[lab] = idle.get(lab, 0.0) + (b - a) / 1e9
    busy_s = (sum(busy) / len(busy) / 1e9) if busy else 0.0
    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "executed": executed,
            "ops": sorted(([k, v] for k, v in per_op.items()),
                          key=lambda kv: -kv[1]),
            "gaps": [[lab, (b - a) / 1e9]
                     for (a, b), lab in zip(gaps, labels)],
            "idle_by_label": sorted(([k, v] for k, v in idle.items()),
                                    key=lambda kv: -kv[1])}

