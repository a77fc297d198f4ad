"""Reduction of a profiler trace to the numbers the per-layer readers use.

The JAX profiler writes an ``.xplane.pb``; ``load_events`` flattens it to
``Event`` records, and ``reduce`` works on those alone, so a test can feed it
a small synthetic trace.  On a TPU, each chip is a plane named
``/device:TPU:<n>`` with an ``XLA Modules`` line (one event per program
run, named like ``jit_serve_step(12)``) and an ``XLA Ops`` line (one event
per operation).  The harness's own host spans are events named ``bench.*``
on the host plane, on the same clock.

- busy: the union of the device's operation intervals inside the traced
  window (its ``XLA Modules`` intervals where a plane has no ops line),
  averaged over the chips;
- programs: per program name (``jit_`` and the ``(n)`` suffix dropped), the
  number of runs and their summed device time;
- gaps: the stretches of the window in which the device ran nothing, each
  named by the innermost ``bench.*`` host span around its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Reduced:
    window_ns: tuple[float, float]
    n_devices: int
    busy_ns: float                       # per chip, averaged
    programs: dict[str, list[float]] = field(default_factory=dict)
    ops: dict[str, float] = field(default_factory=dict)
    gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def program(self, name: str) -> tuple[int, float]:
        """(runs, device seconds) of the program named ``name``."""
        runs, ns = self.programs.get(name, (0, 0.0))
        return int(runs), ns * 1e-9


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [Event(pl.name, ln.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for pl in data.planes for ln in pl.lines for ev in ln.events]


def program_name(event_name: str) -> str:
    name = _PROGRAM_SUFFIX.sub("", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(events: list[Event]) -> Reduced:
    host = [e for e in events if e.name.startswith("bench.")]
    win = [e for e in host if e.name == WINDOW_SPAN]
    dev = [e for e in events if _DEVICE_PLANE.match(e.plane)]
    if win:
        lo, hi = win[0].start_ns, win[0].end_ns
    elif dev:
        lo, hi = min(e.start_ns for e in dev), max(e.end_ns for e in dev)
    else:
        raise ValueError("trace holds no device events and no window span")
    planes = sorted({e.plane for e in dev})
    busy_total = 0.0
    busy_first: list[tuple[float, float]] = []
    programs: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    for p in planes:
        mods = [e for e in dev if e.plane == p and e.line == "XLA Modules"
                and e.end_ns > lo and e.start_ns < hi]
        opev = [e for e in dev if e.plane == p and e.line == "XLA Ops"
                and e.end_ns > lo and e.start_ns < hi]
        for e in mods:
            acc = programs.setdefault(program_name(e.name), [0, 0.0])
            acc[0] += 1
            acc[1] += e.dur_ns
        mods.sort(key=lambda e: e.start_ns)
        starts = [e.start_ns for e in mods]
        for e in opev:                    # "program/op", by the run around it
            k = bisect.bisect_right(starts, e.start_ns) - 1
            prog = (program_name(mods[k].name)
                    if k >= 0 and e.start_ns < mods[k].end_ns else "?")
            key = f"{prog}/{op_name(e.name)}"
            ops[key] = ops.get(key, 0.0) + e.dur_ns
        busy = union(_clip([(e.start_ns, e.end_ns) for e in (opev or mods)],
                           lo, hi))
        busy_total += sum(b - a for a, b in busy)
        if not busy_first:
            busy_first = busy
    gaps: list[tuple[str, float]] = []
    edges = [lo] + [x for iv in busy_first for x in iv] + [hi]
    spans = sorted(host, key=lambda e: e.dur_ns)        # innermost first
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        name = next((s.name for s in spans if s.name != WINDOW_SPAN
                     and s.start_ns <= mid <= s.end_ns), "untraced")
        gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced((lo, hi), len(planes),
                   busy_total / len(planes) if planes else 0.0,
                   programs, ops, gaps)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle
    gaps by what the host was doing, as the result line carries them."""
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])[:top]
    if not ops:
        ops = sorted(((k, v[1]) for k, v in red.programs.items()),
                     key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:top]]}
