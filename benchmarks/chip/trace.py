"""Reduction of a profiler trace of the window to device times.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
``jax.profiler.ProfileData`` (nothing but JAX). Device planes are
``/device:TPU:<n>``: their ``XLA Ops`` line holds one event per operation
run, named by its HLO text (a loop's event spans the events of its body,
and only the innermost are kept), their ``XLA Modules`` line one event per
program run, named ``jit_<function>(<id>)``. Host planes hold the
harness's ``TraceAnnotation`` spans, one per task. Times are nanoseconds;
a device's clock runs about a millisecond off the host's, so its times are
first shifted onto the host's (``clock_offset``).

The window is the first task span's start to the last one's end. Busy time
is the union of operation intervals in the window, per device, averaged
over devices; idle gaps are the holes in that union, each named by what the
host was doing (in a task or between tasks) and the program that ran next.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

import numpy as np

TASK = "bench.task"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """'jit_decode(12)' -> 'jit_decode'."""
    return _SUFFIX.sub("", name)


def clock_offset(spans, modules) -> float:
    """Nanoseconds to add to a device's times to put them on the host's
    clock: the device and host clocks of a trace differ by a millisecond or
    so. Of the shifts that start one of the first programs at the start of
    one of the first tasks, the one that puts the most program runs inside
    a task span, and of those the smallest in size."""
    if not spans or not modules:
        return 0.0
    starts = [a for a, _ in spans]
    sample = modules[:500]

    def inside(d):
        n = 0
        for s, e, _ in sample:
            k = bisect.bisect_right(starts, s + d) - 1
            n += k >= 0 and e + d <= spans[k][1]
        return n

    cands = {a - m[0] for a in starts[:3] for m in modules[:100]}
    return max(sorted(cands), key=lambda d: (inside(d), -abs(d)))


class Trace:
    def __init__(self, devices: dict, spans: list):
        # devices: {plane: {"ops": [(start, end, name, program)],
        #                   "modules": [(start, end, name)]}}
        self.spans = sorted(spans)
        if not self.spans:
            raise ValueError(f"no {TASK!r} spans in the trace")
        self.devices = {}
        for plane, d in devices.items():
            mods = sorted(d["modules"])
            off = clock_offset(self.spans, mods)
            self.devices[plane] = {
                "ops": [(s + off, e + off, *rest) for s, e, *rest in d["ops"]],
                "modules": [(s + off, e + off, n) for s, e, n in mods]}
        self.t0 = self.spans[0][0]
        self.t1 = max(end for _, end in self.spans)

    # -- window ------------------------------------------------------------
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _ops(self, plane):
        return [o for o in self.devices[plane]["ops"]
                if o[1] > self.t0 and o[0] < self.t1]

    def _busy(self, plane) -> list:
        """Merged busy intervals of one device, clipped to the window."""
        merged = []
        for s, e, *_ in sorted(self._ops(plane)):
            s, e = max(s, self.t0), min(e, self.t1)
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        per = [sum(e - s for s, e in self._busy(p)) for p in self.devices]
        return float(np.mean(per)) * 1e-9 if per else 0.0

    # -- operations and programs ---------------------------------------------
    def op_seconds(self, pred) -> float:
        """Summed device seconds of operations for which ``pred(name,
        program)`` holds, averaged over devices."""
        per = [sum(o[1] - o[0] for o in self._ops(p) if pred(*o[2:]))
               for p in self.devices]
        return float(np.mean(per)) * 1e-9 if per else 0.0

    def module_runs(self, name: str) -> tuple[float, int]:
        """(summed seconds, runs) of program ``name`` in the window, on the
        first device."""
        plane = sorted(self.devices)[0]
        runs = [e - s for s, e, n in self.devices[plane]["modules"]
                if module_name(n) == name and e > self.t0 and s < self.t1]
        return sum(runs) * 1e-9, len(runs)

    # -- idle gaps -----------------------------------------------------------
    def gaps(self) -> list[tuple[str, float]]:
        """Idle gaps of the first device, longest first, each named by the
        host's state at its middle and the program that ran after it."""
        plane = sorted(self.devices)[0]
        busy = self._busy(plane)
        mods = sorted(self.devices[plane]["modules"])
        starts = [m[0] for m in mods]
        span_starts = [a for a, _ in self.spans]
        out = []
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            k = bisect.bisect_right(span_starts, mid) - 1
            host = ("in a task" if k >= 0 and mid < self.spans[k][1]
                    else "between tasks")
            nxt = "window end"
            if e < self.t1:  # the program running at e, else the next one
                i = int(np.searchsorted(starts, e, side="right")) - 1
                if i < 0 or mods[i][1] <= e:
                    i += 1
                if i < len(mods):
                    nxt = module_name(mods[i][2])
            out.append((f"{host}, before {nxt}", (e - s) * 1e-9))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self) -> dict:
        """The 10 operations with most device time and the 10 longest idle
        gaps, as [name, seconds]."""
        plane = sorted(self.devices)[0]
        by = defaultdict(int)
        for s, e, name, module in self._ops(plane):
            by[f"{module}/{name}"] += e - s
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps()[:10]]}

    def describe(self) -> str:
        parts = [f"window {self.window_s():.3f} s, {len(self.spans)} task "
                 f"spans, busy {self.busy_s():.3f} s"]
        for p, d in sorted(self.devices.items()):
            parts.append(f"{p}: {len(d['ops'])} ops, {len(d['modules'])} "
                         f"program runs")
        return "; ".join(parts)


def instruction(text: str) -> str:
    """'%fusion.12 = bf16[8]{0} fusion(...)' -> 'fusion.12'."""
    return text.split(" = ", 1)[0].lstrip("%")


def _leaves(ops):
    """The operations that enclose no other: a ``while`` or ``call`` event
    spans the events of its body, which are listed too."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[0] < o[1] and nxt[1] <= o[1])]


def _with_modules(ops, modules):
    """Each operation with the name of the program run that encloses it."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    out = []
    for s, e, name in ops:
        k = bisect.bisect_right(starts, s) - 1
        inside = k >= 0 and s < modules[k][1]
        out.append((s, e, name, module_name(modules[k][2]) if inside else ""))
    return out


def from_profile(profile) -> Trace:
    devices, spans = {}, []
    for plane in profile.planes:
        if _DEVICE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                instruction(ev.name)) for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name) for ev in line.events)
            devices[plane.name] = {
                "ops": _with_modules(_leaves(ops), modules),
                "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in line.events if ev.name == TASK)
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    return Trace(devices, spans)


def load(path: str) -> Trace:
    """A trace from an ``.xplane.pb`` file or the directory the profiler
    wrote it under."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"{len(found)} .xplane.pb files under {path}")
        path = found[0]
    return from_profile(ProfileData.from_file(path))
