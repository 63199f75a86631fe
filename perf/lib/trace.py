"""From a profiler trace to numbers: device busy time, operation time by
name, collective time exposed, idle gaps by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded trace
(tests/perf): ``from_xplane`` turns the profiler's file into a plain dict
(``{"devices": {id: {"ops": [[name, start_ns, dur_ns], ...], "modules":
[...]}}, "host": [[name, start_ns, dur_ns], ...]}``), and ``Trace`` reduces
that dict. All times inside are nanoseconds on the trace's clock.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the profiler's lines on a device plane that are not single operations
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
           "Framework Name Scope", "Source code", "Sparse Core")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?(\.\d+)?$")
SPAN_PREFIX = "perf."


def find_xplane(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def from_xplane(path):
    """Read the profiler's file with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        if m:
            dev = out["devices"].setdefault(
                m.group(1), {"ops": [], "modules": [], "async": []})
            named = {line.name: line for line in lines}
            op_lines = [named["XLA Ops"]] if "XLA Ops" in named else [
                line for line in lines if line.name not in NOT_OPS]
            for line in op_lines:
                for ev in line.events:
                    dev["ops"].append(
                        [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)])
            if "Async XLA Ops" in named:
                for ev in named["Async XLA Ops"].events:
                    dev["async"].append(
                        [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)])
            if "XLA Modules" in named:
                for ev in named["XLA Modules"].events:
                    dev["modules"].append(
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out["host"].append(
                            [ev.name, int(ev.start_ns), int(ev.duration_ns)])
    for dev in out["devices"].values():
        for line in dev.values():
            line.sort(key=lambda e: e[1])
    out["host"].sort(key=lambda e: e[1])
    return out


def op_name(text):
    """The profiler names a device operation by its whole HLO line
    ('fusion.3 = f32[8]{0} fusion(...)'): keep what stands before ' = '."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_family(name):
    """'fusion.123' -> 'fusion'; '%copy.4' -> 'copy'."""
    return re.sub(r"(\.\d+)+$", "", name.lstrip("%"))


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by the merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


class Trace:
    """One traced window, reduced on demand. The window is the host span
    ``perf.window`` where the trace holds one, else the extent of the device
    operations."""

    def __init__(self, data, window_span="perf.window"):
        self.data = data
        self.devices = sorted(data["devices"], key=int)
        spans = [e for e in data["host"] if e[0] == window_span]
        if spans:
            self.lo = spans[0][1]
            self.hi = spans[-1][1] + spans[-1][2]
        else:
            starts = [d["ops"][0][1] for d in data["devices"].values()
                      if d["ops"]]
            ends = [max(e[1] + e[2] for e in d["ops"])
                    for d in data["devices"].values() if d["ops"]]
            self.lo, self.hi = (min(starts), max(ends)) if starts else (0, 0)
        self._busy = {}

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9

    def ops(self, dev):
        """Operations of one device that start inside the window."""
        return [e for e in self.data["devices"][dev]["ops"]
                if self.lo <= e[1] < self.hi]

    def busy_intervals(self, dev):
        if dev not in self._busy:
            self._busy[dev] = clip(union(
                [e[1], e[1] + e[2]] for e in self.data["devices"][dev]["ops"]
            ), self.lo, self.hi)
        return self._busy[dev]

    def busy_s(self, dev):
        return total(self.busy_intervals(dev)) / 1e9

    def mean_busy_s(self):
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self, dev):
        return 1.0 - self.busy_s(dev) / self.window_s

    def worst_idle_share(self):
        return max(self.idle_share(d) for d in self.devices)

    def op_seconds(self, pattern, dev=None):
        """Summed durations of the operations whose name matches, on one
        device (the first by default). -> (seconds, events)."""
        dev = self.devices[0] if dev is None else dev
        rx = re.compile(pattern)
        hits = [e[2] for e in self.ops(dev) if rx.search(e[0])]
        return sum(hits) / 1e9, len(hits)

    def module_seconds(self, pattern, dev=None):
        """Busy time of the device inside the programs (XLA modules) whose
        name matches. -> (seconds, programs run)."""
        dev = self.devices[0] if dev is None else dev
        rx = re.compile(pattern)
        mods = [[e[1], e[1] + e[2]]
                for e in self.data["devices"][dev]["modules"]
                if rx.search(e[0]) and self.lo <= e[1] < self.hi]
        inside = union(mods)
        busy = self.busy_intervals(dev)
        covered = total(busy) - total(subtract(busy, inside))
        return covered / 1e9, len(mods)

    def module_names(self, dev=None):
        dev = self.devices[0] if dev is None else dev
        return sorted({op_family(e[0])
                       for e in self.data["devices"][dev]["modules"]})

    def top_ops(self, n=10, dev=None):
        dev = self.devices[0] if dev is None else dev
        by = {}
        for name, _, dur in self.ops(dev):
            fam = op_family(name)
            by[fam] = by.get(fam, 0) + dur
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def collectives(self, dev):
        """Collective operations of one device inside the window, from the
        operations' line and from the line of asynchronous ones."""
        d = self.data["devices"][dev]
        return [e for e in d["ops"] + d.get("async", [])
                if self.lo <= e[1] < self.hi and COLLECTIVE.match(e[0])]

    def exposed_collective_s(self, dev):
        """Time in which a collective runs on the device and nothing else
        does."""
        coll = union([e[1], e[1] + e[2]] for e in self.collectives(dev))
        other = union([e[1], e[1] + e[2]] for e in self.ops(dev)
                      if not COLLECTIVE.match(e[0]))
        return total(subtract(coll, other)) / 1e9

    def idle_gaps(self, n=10, dev=None):
        """The device's idle time inside the window, by the host span that
        covers most of each gap ('(no span)' where none does)."""
        dev = self.devices[0] if dev is None else dev
        gaps = subtract([[self.lo, self.hi]], self.busy_intervals(dev))
        spans = [e for e in self.data["host"] if e[0] != "perf.window"]
        starts = [e[1] for e in spans]
        by = {}
        for s, e in gaps:
            best, best_ov = "(no span)", 0
            i = bisect.bisect_right(starts, e)
            for name, ss, dur in spans[max(0, i - 64):i]:
                ov = min(e, ss + dur) - max(s, ss)
                if ov > best_ov:
                    best, best_ov = name, ov
            by[best] = by.get(best, 0) + (e - s)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]
