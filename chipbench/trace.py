"""Reduction of a profiler trace to the benchmark's numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with
nothing but jax's own reader, and keeps three things:

- per TPU device, the intervals of its ``XLA Ops`` line (operations as they
  ran) and of its ``XLA Modules`` line (whole compiled programs, named
  ``jit_<function>(<fingerprint>)``);
- the benchmark's own host spans (``host-in``, ``solve``, ``mates-out``),
  written by ``jax.profiler.TraceAnnotation`` on the same clock.

Everything after that is arithmetic on intervals in nanoseconds: the union
of operations (busy time), the module time of a phase, the time of
collective operations, the self time of each operation, and the idle gaps
of a device, each named by the host span that was open while the device
waited.
"""
from __future__ import annotations

import dataclasses
import re

#: The benchmark's host spans, in the order one solve opens them.
SPANS = ("host-in", "solve", "mates-out")

_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
#: HLO operations that move data between chips.
_COLLECTIVE = re.compile(
    r"(all-gather|all-to-all|all-reduce|reduce-scatter|collective-permute"
    r"|collective-broadcast)")


@dataclasses.dataclass
class Device:
    ordinal: int
    ops: list  # [(start_ns, end_ns, op name)] from the 'XLA Ops' line
    modules: list  # [(start_ns, end_ns, module name)] from 'XLA Modules'


@dataclasses.dataclass
class Trace:
    devices: dict  # ordinal -> Device
    spans: list  # [(start_ns, end_ns, span name)], sorted by start

    @property
    def window(self) -> tuple[float, float]:
        """From the first span's start to the last span's end."""
        if not self.spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        return self.spans[0][0], max(s[1] for s in self.spans)

    def steps(self) -> list[tuple[float, float]]:
        """One interval per solve: from its ``host-in`` span's start to the
        end of the last span before the next ``host-in``."""
        out = []
        for start, end, name in self.spans:
            if name == SPANS[0]:
                out.append([start, end])
            elif out:
                out[-1][1] = max(out[-1][1], end)
        return [tuple(s) for s in out]


def op_name(hlo_text: str) -> str:
    """'%fusion.131 = s32[65537]{...} fusion(...)' -> 'fusion.131'."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """'jit_mcm(8654646013829495375)' -> 'jit_mcm'."""
    return name.split("(", 1)[0]


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.fullmatch(plane.name)
        if m:
            dev = Device(int(m.group(1)), [], [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.start_ns, e.end_ns, op_name(e.name))
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(e.start_ns, e.end_ns, module_name(e.name))
                                   for e in line.events]
            devices[dev.ordinal] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name in SPANS]
    spans.sort()
    return Trace(devices, spans)


def union(intervals) -> list[tuple[float, float]]:
    """Merge [(start, end, ...)] into disjoint sorted (start, end)."""
    merged = []
    for start, end, *_ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def covered(merged, lo: float, hi: float) -> float:
    """Length of the disjoint ``merged`` intervals inside [lo, hi]."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one operation ran on ``dev``."""
    return covered(union(dev.ops), lo, hi)


def module_ns(dev: Device, names, lo: float, hi: float) -> float | None:
    """Device time of the modules named ``names`` inside [lo, hi]; None
    when none of them ran there, so that a renamed program reads nothing
    rather than 0."""
    hits = [(s, e) for s, e, name in dev.modules
            if name in names and e > lo and s < hi]
    if not hits:
        return None
    return sum(min(e, hi) - max(s, lo) for s, e in hits)


def collective_ns(dev: Device, lo: float, hi: float) -> float | None:
    """Time in [lo, hi] in which a collective operation ran on ``dev``;
    None when the device ran none."""
    coll = [op for op in dev.ops if _COLLECTIVE.match(op[2])]
    if not coll:
        return None
    return covered(union(coll), lo, hi)


def self_times(dev: Device, lo: float, hi: float) -> dict[str, float]:
    """Self time of each operation inside [lo, hi] (its duration less the
    operations nested in it, as a while loop holds its body), summed by
    '<module>/<op>'."""
    ops = sorted((op for op in dev.ops if op[1] > lo and op[0] < hi),
                 key=lambda op: (op[0], -op[1]))
    mods = sorted(dev.modules)
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, key, self]

    def close(entry):
        out[entry[1]] = out.get(entry[1], 0.0) + entry[2]

    mi = 0
    for start, end, name in ops:
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        while mi + 1 < len(mods) and mods[mi + 1][0] <= start:
            mi += 1
        mod = mods[mi][2] if mods and mods[mi][0] <= start < mods[mi][1] \
            else "?"
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, f"{mod}/{name}", end - start])
    while stack:
        close(stack.pop())
    return out


def idle_gaps(dev: Device, spans, lo: float, hi: float) -> list:
    """Gaps in [lo, hi] with no operation on ``dev``, longest first, as
    [(span name, ns)]: the innermost benchmark span open at the gap's
    middle, or 'between-spans'."""
    merged = union(dev.ops)
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        mid = (a + b) / 2
        open_spans = [s for s in spans if s[0] <= mid < s[1]]
        name = min(open_spans, key=lambda s: s[1] - s[0])[2] \
            if open_spans else "between-spans"
        gaps.append((name, b - a))
    gaps.sort(key=lambda g: -g[1])
    return gaps


def per_solve_ms(total_ns: float | None, tr: Trace) -> float | None:
    """``total_ns`` over the traced solves, in milliseconds per solve."""
    if total_ns is None or not tr.steps():
        return None
    return total_ns / len(tr.steps()) / 1e6


def phase_ms(tr: Trace | None, names, ordinal: int = 0) -> float | None:
    """Per solve: device time on ``ordinal`` of the modules ``names``; None
    when none of them ran, so that a renamed program is not counted under
    another phase."""
    if tr is None or ordinal not in tr.devices:
        return None
    lo, hi = tr.window
    return per_solve_ms(module_ns(tr.devices[ordinal], names, lo, hi), tr)


def host_ms(tr: Trace, ordinal: int = 0) -> float | None:
    """Per solve: its wall time less the time ``ordinal`` was busy in it."""
    steps = tr.steps()
    if not steps or ordinal not in tr.devices:
        return None
    dev = tr.devices[ordinal]
    host = sum((e - s) - busy_ns(dev, s, e) for s, e in steps)
    return host / len(steps) / 1e6


def idle_pct(tr: Trace, ordinal: int = 0) -> float | None:
    """Share of the traced window in which ``ordinal`` ran no operation."""
    if ordinal not in tr.devices or not tr.devices[ordinal].ops:
        return None
    lo, hi = tr.window
    return 100.0 * (1.0 - busy_ns(tr.devices[ordinal], lo, hi) / (hi - lo))
