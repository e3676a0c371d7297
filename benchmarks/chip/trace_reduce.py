"""From a `jax.profiler` trace to device busy time, time per XLA module,
and a breakdown of device operations and idle gaps.

The traced window is the host annotation that the benchmark opens around
it (`WINDOW`). Inside it:
  busy_s      the union of the intervals in which an operation ran on a
              device (the "XLA Ops" line; "XLA Modules" where a device has
              no op line), averaged over the devices that ran anything;
  modules     device seconds and executions per XLA module, by name with
              the `jit_` prefix and any `(...)` suffix taken off;
  device_ops  device seconds per operation, the ten largest, each named by
              the first characters of its HLO text; ops nest (a while
              loop holds its body's ops), so these overlap;
  collective_s  device seconds of collective operations (all-gather,
              all-reduce, reduce-scatter, collective-permute, all-to-all,
              and their -start and -done halves), found by the opcode in
              an op's HLO text: the union of their intervals on each
              device, summed over the devices, so at most busy_s times
              devices;
  idle_gaps   the gaps between busy intervals on the first busy device,
              by what the host was doing: for each of the benchmark's own
              host annotations (`bench.*`), the idle seconds it overlaps,
              and the idle seconds that no annotation overlaps; the ten
              largest. Annotations on different threads can overlap the
              same gap, so these need not add up to the idle time.
Events are clipped to the window. The device's clock in a TPU trace runs
about a millisecond apart from the host's (a module was seen to start
0.9 ms before the host call that launched it), so idle gaps much shorter
than that are not attributed reliably.
"""

from __future__ import annotations

import glob
import heapq
import os
import re

ANNOTATION = "bench."
WINDOW = ANNOTATION + "window"
NO_HOST_EVENT = "no bench annotation"
OP_NAME_CHARS = 120
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(.*\)$")
# an op's HLO text: `%name = <shape> <opcode>(<operands>), <attributes>`;
# a shape's layout holds no lower-case word right before a `(`
_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9-]*)\(")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def module_name(raw: str) -> str:
    name = _SUFFIX.sub("", raw).strip()
    return name[4:] if name.startswith("jit_") else name


def load(trace_dir: str) -> list[dict]:
    """Planes of the newest `.xplane.pb` under `trace_dir` as plain data:
    [{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def _clip(events, lo: float, hi: float):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _union(spans) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def is_collective(op_name: str) -> bool:
    """Whether the HLO opcode of a device op, named by its HLO text, is a
    collective or one half of an asynchronous one."""
    m = _OPCODE.search(op_name)
    return m is not None and any(
        m[1] in (c, c + "-start", c + "-done") for c in COLLECTIVES)


def _line(plane: dict, name: str):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return None


def _name_gaps(gaps, host_events) -> dict[str, float]:
    """Idle nanoseconds per host annotation: each annotation gets the part
    of every gap it overlaps, and NO_HOST_EVENT the part that none does
    (a sweep over both, sorted by start)."""
    events = sorted(host_events, key=lambda ev: ev[1])
    active: list = []                                # heap of (end, i)
    named: dict[str, float] = {}
    j = 0
    for gs, ge in gaps:                              # gaps are sorted
        while j < len(events) and events[j][1] < ge:
            heapq.heappush(active, (events[j][2], j))
            j += 1
        while active and active[0][0] <= gs:
            heapq.heappop(active)
        spans = []
        for end, i in active:
            name, s, e = events[i]
            lo, hi = max(s, gs), min(e, ge)
            if hi > lo:
                named[name] = named.get(name, 0.0) + (hi - lo)
                spans.append((lo, hi))
        covered = sum(e - s for s, e in _union(spans))
        if ge - gs > covered:
            named[NO_HOST_EVENT] = named.get(NO_HOST_EVENT, 0.0) + (
                ge - gs - covered)
    return named


def reduce(planes: list[dict], top: int = 10) -> dict | None:
    """The reduction of one trace, or None where it has no traced window
    or no device plane (a trace taken off the chip)."""
    host = [ev for p in planes if not _DEVICE.match(p["name"])
            for ln in p["lines"] for ev in ln["events"]]
    marks = [(s, s + d) for name, s, d in host if name == WINDOW]
    devices = [p for p in planes if _DEVICE.match(p["name"])]
    if not marks or not devices:
        return None
    lo, hi = marks[0]
    busy, modules, ops, gaps_of = [], {}, {}, None
    collective = 0.0
    for dev in devices:
        mods = _line(dev, "XLA Modules") or []
        op_line = _line(dev, "XLA Ops")
        spans = [(s, e) for _, s, e in _clip(op_line if op_line is not None
                                             else mods, lo, hi)]
        if not spans:
            continue
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in _clip(mods, lo, hi):
            m = modules.setdefault(module_name(name),
                                   {"seconds": 0.0, "count": 0})
            m["seconds"] += (e - s) / 1e9
            m["count"] += 1
        for name, s, e in _clip(op_line or [], lo, hi):
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
        collective += sum(e - s for s, e in _union(
            (s, e) for name, s, e in _clip(op_line or [], lo, hi)
            if is_collective(name)))
        if gaps_of is None:
            edges = [lo] + [x for span in merged for x in span] + [hi]
            gaps_of = [(edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]
    if not busy:
        return None
    named = _name_gaps(gaps_of or [],
                       [(n, s, s + d) for n, s, d in host
                        if n.startswith(ANNOTATION) and n != WINDOW
                        and d > 0])
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    # an op's name is its HLO text; its head names it well enough
    short = lambda name: name[:OP_NAME_CHARS]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "devices": len(busy),
        "modules": modules,
        "device_ops": [[short(n), s] for n, s in rank(ops)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in rank(named)],
        "collective_s": collective / 1e9,
    }
