"""Reduction of a JAX profiler trace (an XSpace, as in ``.xplane.pb``) to
the numbers the per-layer metrics read.

The trace is first flattened into plain ``Event`` tuples (plane, line,
name, start, duration in ns); everything after that works on those, so a
test can feed a recorded trace or a handful of events.

- Device planes are ``/device:<kind>:<n>`` planes.  Their ``XLA Ops`` line
  holds the operations that ran; their ``XLA Modules`` line the programs
  (``jit_bench_decode(...)`` -> ``bench_decode``).
- The traced window is the host span named ``window``; every device time
  is clipped to it.
- Busy time is the union of the operations' intervals, per device, then
  averaged over the devices.
- A program's time is the sum of its module events; its collective time
  the sum of the collective operations that start inside it.
- The operations ranked by time leave out those that hold others (a
  scan's ``while``), whose time their body's operations already show.
- Idle gaps (the window less the busy union) are labelled by the innermost
  harness span open at the gap's midpoint, or ``none``.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import (Dict, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast|"
                        r"psum|ppermute|send|recv", re.IGNORECASE)
PROGRAM = re.compile(r"^(?:jit_)?([A-Za-z_][A-Za-z0-9_]*)")
#: operations that hold others (a scan's loop): in the busy union, but not
#: ranked among the operations that took the most time
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


class Session:
    """A profiler session whose trace stays in memory: ``stop()`` returns
    the serialized XSpace.  ``jax.profiler.stop_trace`` also writes it out
    and converts it for the trace viewer: for a 24 s decode window on a
    TPU v5e that stop took 77 s and 6.9 GB of the host's memory, this one
    36 s and 1.9 GB."""

    def __init__(self, options):
        import jax
        from jax._src.lib import _profiler
        # the backends first, as ``jax.profiler.start_trace`` does, or the
        # TPU's tracer is not set up and the trace holds no device plane
        jax.devices()
        self._session = _profiler.ProfilerSession(options)

    def stop(self) -> bytes:
        return self._session.stop()


def events_from_profile(pd, span_names: Optional[Sequence[str]] = None
                        ) -> List[Event]:
    """The events of a ``jax.profiler.ProfileData``.  With ``span_names``,
    only those ``reduce`` reads: the device planes' operations and
    modules, each operation by its HLO name (``op_name``), and the host
    events of those names.  The reduction of what is kept equals that of
    the whole trace; a 24 s decode window has 2.3 million operations,
    each named by its whole instruction."""
    out = []
    names: Dict[str, str] = {}      # one string for each short name
    for plane in pd.planes:
        pname = plane.name
        device = bool(DEVICE_PLANE.match(pname))
        for line in plane.lines:
            lname = line.name
            if span_names is None:
                out.extend(Event(pname, lname, e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events)
            elif not device:
                out.extend(Event(pname, lname, e.name, float(e.start_ns),
                                 float(e.duration_ns))
                           for e in line.events if e.name in span_names)
            elif lname == OPS_LINE:
                for e in line.events:
                    n = op_name(e.name)
                    out.append(Event(pname, lname, names.setdefault(n, n),
                                     float(e.start_ns),
                                     float(e.duration_ns)))
            elif lname == MODULES_LINE:
                out.extend(Event(pname, lname, e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in line.events)
    return out


def events_from_file(path: str,
                     span_names: Optional[Sequence[str]] = None
                     ) -> List[Event]:
    from jax.profiler import ProfileData
    return events_from_profile(ProfileData.from_file(path), span_names)


def events_from_xspace(xspace: bytes,
                       span_names: Optional[Sequence[str]] = None
                       ) -> List[Event]:
    from jax.profiler import ProfileData
    return events_from_profile(ProfileData.from_serialized_xspace(xspace),
                               span_names)


def op_name(event: str) -> str:
    """An operation's HLO name: TPU traces name an op by its whole
    instruction (``%fusion.13 = bf16[256,512]... fusion(...)``)."""
    return event.split(" = ", 1)[0].lstrip("%")


def program_name(module_event: str) -> str:
    m = PROGRAM.match(module_event)
    return m.group(1) if m else module_event


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over devices
    n_devices: int
    programs: Dict[str, dict]           # name -> calls, s, collective_s
    ops: List[Tuple[str, float]]        # (program/op, s), per device
    idle_by_span: List[Tuple[str, float]]   # (span, s), per device

    def program(self, name: str) -> dict:
        return self.programs.get(name, {"calls": 0, "s": 0.0,
                                        "collective_s": 0.0})

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:top]],
                "idle_gaps": [[n, s] for n, s in self.idle_by_span[:top]]}


def reduce(events: Sequence[Event], span_names: Sequence[str]) -> Reduced:
    spans = [e for e in events if e.name in span_names
             and not DEVICE_PLANE.match(e.plane)]
    windows = [e for e in spans if e.name == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    w = max(windows, key=lambda e: e.dur_ns)
    w0, w1 = w.start_ns, w.start_ns + w.dur_ns
    inner = sorted((e for e in spans if e.name != "window"
                    and e.start_ns < w1 and e.start_ns + e.dur_ns > w0),
                   key=lambda e: e.start_ns)
    inner_starts = [e.start_ns for e in inner]

    ops_by_dev: Dict[str, List[Event]] = defaultdict(list)
    mods_by_dev: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if not DEVICE_PLANE.match(e.plane):
            continue
        if not (e.start_ns < w1 and e.start_ns + e.dur_ns > w0):
            continue
        if e.line == OPS_LINE:
            ops_by_dev[e.plane].append(e)
        elif e.line == MODULES_LINE:
            mods_by_dev[e.plane].append(e)
    devices = sorted(set(ops_by_dev) | set(mods_by_dev))
    n = max(len(devices), 1)

    busy = 0.0
    programs: Dict[str, dict] = defaultdict(
        lambda: {"calls": 0, "s": 0.0, "collective_s": 0.0})
    op_time: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for dev in devices:
        ops = ops_by_dev[dev]
        merged = union((max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1))
                       for e in ops)
        busy += sum(e - s for s, e in merged)
        mods = sorted(mods_by_dev[dev], key=lambda e: e.start_ns)
        starts = [m.start_ns for m in mods]
        for m in mods:
            p = programs[program_name(m.name)]
            p["calls"] += 1
            p["s"] += m.dur_ns * 1e-9
        for e in ops:
            prog = _containing(mods, starts, e.start_ns)
            name = op_name(e.name)
            if not CONTAINER.match(name):
                op_time[f"{prog}/{name}"] += e.dur_ns * 1e-9
            if prog != "?" and COLLECTIVE.search(name):
                programs[prog]["collective_s"] += e.dur_ns * 1e-9
        gap_s = w0
        for s, e in merged + [[w1, w1]]:
            if s > gap_s:
                idle[_label(inner, inner_starts, (gap_s + s) / 2)] += \
                    (s - gap_s) * 1e-9
            gap_s = max(gap_s, e)
    for p in programs.values():
        p["calls"] /= n
        p["s"] /= n
        p["collective_s"] /= n
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n,
        n_devices=len(devices), programs=dict(programs),
        ops=sorted(((k, v / n) for k, v in op_time.items()),
                   key=lambda kv: -kv[1]),
        idle_by_span=sorted(((k, v / n) for k, v in idle.items()),
                            key=lambda kv: -kv[1]))


def _containing(mods: List[Event], starts: List[float], t: float) -> str:
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i].start_ns + mods[i].dur_ns:
        return program_name(mods[i].name)
    return "?"


def _label(spans: List[Event], starts: List[float], t: float) -> str:
    """The innermost (latest started) span open at ``t``.  The harness's
    spans nest at most a few deep, so a short look back finds it."""
    import bisect
    i = bisect.bisect_right(starts, t) - 1
    for e in spans[max(i - 63, 0):i + 1][::-1]:
        if t < e.start_ns + e.dur_ns:
            return e.name
    return "none"
