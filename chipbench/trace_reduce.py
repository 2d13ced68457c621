"""From a profiler trace to device time, idle share and a breakdown.

What it relies on in a TPU trace (``*.xplane.pb`` as read by
``jax.profiler.ProfileData``):

* device planes named ``/device:TPU:<n>``, one per chip;
* on each, the line ``XLA Modules`` (one event per execution of a
  compiled program, named after the jitted function, e.g.
  ``jit_train_step(14735237950879021107)``) and the line ``XLA Ops``
  (one event per HLO instruction executed, named by the instruction's
  text, e.g. ``%fusion.26 = f32[...] fusion(...), kind=kCustom,
  calls=%fused_computation.26``; the instructions of a ``while`` body
  appear as events inside the ``while`` event's interval);
* host planes (``/host:CPU``) whose lines hold the runtime's and the
  Python tracer's events, on the same clock as the device events.

``reduce`` keeps the executions of the step program (the module whose
name contains ``module``), their device operations, and the host
events; ``Device.top`` are the operations not nested in another, which
``category_s`` sums by a predicate; ``breakdown`` lists the longest
top-level operations and the longest idle gaps with what the host was
doing during each.

A trimmed trace can be written as JSON (``dump``) and read back
(``load_json``), which is how the tests hold a recorded TPU trace.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def short(self) -> str:
        """``while.53`` of ``%while.53 = ... while(...)``."""
        return self.name.split(" ", 1)[0].lstrip("%")


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for p in ProfileData.from_file(path).planes:
        lines = []
        for ln in p.lines:
            lines.append(Line(ln.name, [
                Event(e.name, float(e.start_ns), float(e.duration_ns),
                      {k: v for k, v in e.stats})
                for e in ln.events]))
        planes.append(Plane(p.name, lines))
    return planes


def dump(planes: list, path: str, t0: float = float("-inf"),
         t1: float = float("inf"), min_host_ns: float = 0.0) -> None:
    """Write the events that start in [t0, t1) as gzipped JSON, leaving
    out host events shorter than ``min_host_ns``."""
    out = []
    for p in planes:
        lines = []
        short = min_host_ns if p.name.startswith("/host:") else 0.0
        for ln in p.lines:
            evs = [[e.name, e.start_ns, e.dur_ns,
                    {k: (v if isinstance(v, (int, float)) else str(v))
                     for k, v in e.stats.items()}]
                   for e in ln.events
                   if t0 <= e.start_ns < t1 and e.dur_ns >= short]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        out.append({"name": p.name, "lines": lines})
    with gzip.open(path, "wt") as f:
        json.dump(out, f)


def load_json(path: str) -> list:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return [Plane(p["name"], [Line(ln["name"], [Event(*e) for e in ln["events"]])
                              for ln in p["lines"]]) for p in data]


def _line(plane: Plane, name: str) -> list:
    for ln in plane.lines:
        if ln.name == name:
            return ln.events
    return []


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Device:
    index: int
    modules: list       # executions of the step program
    ops: list           # its operations
    busy: list          # union of the op intervals
    start_ns: float
    end_ns: float

    @property
    def top(self) -> list:
        """Operations not nested inside another (a while's body ops are)."""
        out, end = [], float("-inf")
        for o in sorted(self.ops, key=lambda o: (o.start_ns, -o.dur_ns)):
            if o.start_ns >= end:
                out.append(o)
                end = o.end_ns
        return out

    @property
    def window_ns(self) -> float:
        return self.end_ns - self.start_ns

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)


@dataclass
class Reduced:
    devices: list
    host: list          # host events (all host planes)

    @property
    def chips(self) -> int:
        return len(self.devices)

    @property
    def window_s(self) -> float:
        return sum(d.window_ns for d in self.devices) / self.chips / 1e9

    @property
    def busy_s(self) -> float:
        return sum(d.busy_ns for d in self.devices) / self.chips / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def category_s(self, keep) -> float:
        """Device seconds per chip of the top-level ops ``keep`` accepts."""
        return sum(o.dur_ns for d in self.devices for o in d.top
                   if keep(o)) / self.chips / 1e9


def reduce(planes: list, module: str = "train_step") -> Reduced:
    """The step program's executions on every chip, and the host."""
    devices, host = [], []
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if m is None:
            if p.name.startswith("/host:"):
                host.extend(e for ln in p.lines for e in ln.events)
            continue
        mods = _line(p, MODULES_LINE)
        named = [e for e in mods if module in e.name]
        mods = named or mods
        if not mods:
            continue
        spans = [(e.start_ns, e.end_ns) for e in mods]
        ops = [o for o in _line(p, OPS_LINE)
               if any(s <= o.start_ns < e for s, e in spans)]
        devices.append(Device(
            index=int(m.group(1)), modules=mods, ops=ops,
            busy=_union([(o.start_ns, o.end_ns) for o in ops]),
            start_ns=min(s for s, _ in spans), end_ns=max(e for _, e in spans),
        ))
    if not devices:
        raise ValueError("the trace holds no device plane with executions")
    devices.sort(key=lambda d: d.index)
    return Reduced(devices=devices, host=host)


def breakdown(red: Reduced, label=lambda op: op.short, top: int = 10) -> dict:
    """Longest top-level device ops (seconds per chip, summed over the
    executions, named by ``label``) and the longest idle gaps of chip 0,
    each named after the innermost host event running at its midpoint."""
    tot: dict = {}
    for d in red.devices:
        for o in d.top:
            k = label(o)
            tot[k] = tot.get(k, 0.0) + o.dur_ns
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    d0 = red.devices[0]
    gaps = [(s1, e0) for (_, s1), (e0, _) in zip(d0.busy[:-1], d0.busy[1:])
            if e0 > s1]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        covering = [h for h in red.host if h.start_ns <= mid < h.end_ns]
        label = min(covering, key=lambda h: h.dur_ns).name if covering else "no host event"
        named.append([label, (e - s) / 1e9])
    return {"device_ops": [[k, v / red.chips / 1e9] for k, v in ops],
            "idle_gaps": named}
