"""Profiler trace -> device busy time, per-op device time and idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``. On a
TPU its ``/device:TPU:<k>`` planes carry an ``XLA Modules`` line (one event
per program run) and an ``XLA Ops`` line (one event per HLO op run; ops
inside a loop nest in the loop's own event). Host threads sit on the
``/host:CPU`` plane; the harness's spans (``jax.profiler.TraceAnnotation``)
land on the line of the thread that opened them.

Only the planes of the chips the cell uses are read. The traced window is
the harness's ``bench.window`` span. Within it:

* busy time is the union of the device's op intervals;
* an op's time is its self time (its duration less that of the ops nested
  in it), so a loop does not count the work inside it twice;
* each stretch of an idle gap goes to the innermost host event, on the
  window's own thread, that covers it: what the host was doing while the
  device waited.

``reduce_events`` is the pure reduction, and is what the tests drive.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

__all__ = ["Reduced", "reduce_events", "reduce_trace", "trace_events",
           "find_xplane", "short_name", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"


def short_name(name: str) -> str:
    """HLO op text ``%fusion.3 = f32[..] fusion(..)`` -> ``%fusion.3``;
    module ``jit_f(1234)`` -> ``jit_f``."""
    head = name.split(" = ", 1)[0]
    return head.split("(", 1)[0] if not head.startswith("%") else head


@dataclass
class Reduced:
    window_ns: float                      # length of the traced window
    busy_ns: float                        # mean over devices of busy time
    devices: int
    op_self_ns: dict = field(default_factory=dict)    # short name -> ns
    op_total_ns: dict = field(default_factory=dict)   # short name -> ns
    module_ns: dict = field(default_factory=dict)     # module -> ns
    gap_ns: dict = field(default_factory=dict)        # host label -> ns

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def breakdown(self, k: int = 10) -> dict:
        top = sorted(self.op_self_ns.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.gap_ns.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, v * 1e-9] for n, v in top],
                "idle_gaps": [[n, v * 1e-9] for n, v in gaps]}


def _clip(events, lo, hi):
    for name, s, e in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            yield name, s2, e2


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events) -> tuple[dict, dict]:
    """Per short name: (self ns, total ns) of nested op events."""
    self_ns: dict = {}
    total_ns: dict = {}
    stack: list = []                      # [end, name]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        key = short_name(name)
        while stack and stack[-1][0] <= s:
            stack.pop()
        d = e - s
        total_ns[key] = total_ns.get(key, 0.0) + d
        self_ns[key] = self_ns.get(key, 0.0) + d
        if stack:
            parent = stack[-1][1]
            self_ns[parent] -= min(e, stack[-1][0]) - s
        stack.append([e, key])
    return self_ns, total_ns


def _attribute(host, g0, g1, into: dict) -> None:
    """Add each stretch of the idle gap [g0, g1) to the innermost host
    event covering it (the window span where no other does)."""
    spans = [(s, e, name) for name, s, e in host
             if s < g1 and e > g0 and name != WINDOW_SPAN]
    cuts = sorted({g0, g1} | {x for s, e, _ in spans for x in (s, e)
                              if g0 < x < g1})
    for a, b in zip(cuts, cuts[1:]):
        cover = [(e - s, name) for s, e, name in spans if s <= a and e >= b]
        label = min(cover)[1] if cover else WINDOW_SPAN
        into[label] = into.get(label, 0.0) + (b - a)


def reduce_events(devices: list[dict], host: list[tuple]) -> Reduced:
    """devices: per device {"ops": [(name, start_ns, end_ns)], "modules":
    [...]}; host: (name, start_ns, end_ns) events of the window's thread,
    the ``bench.window`` span among them."""
    spans = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    red = Reduced(window_ns=float(hi - lo), busy_ns=0.0,
                  devices=len(devices))
    busy_total = 0.0
    for dev in devices:
        ops = list(_clip(dev["ops"], lo, hi))
        busy = _union((s, e) for _, s, e in ops)
        busy_total += sum(e - s for s, e in busy)
        self_ns, total_ns = _self_times(ops)
        for k, v in self_ns.items():
            red.op_self_ns[k] = red.op_self_ns.get(k, 0.0) + v
        for k, v in total_ns.items():
            red.op_total_ns[k] = red.op_total_ns.get(k, 0.0) + v
        for name, s, e in dev["modules"]:
            if lo <= s < hi:
                k = short_name(name)
                red.module_ns[k] = red.module_ns.get(k, 0.0) + (e - s)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                _attribute(host, g0, g1, red.gap_ns)
    red.busy_ns = busy_total / max(len(devices), 1)
    return red


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def trace_events(planes, device_ids) -> tuple[list, list]:
    """(devices, host) for ``reduce_events`` from the planes of a trace:
    the ``/device:TPU:<k>`` planes whose k is in ``device_ids`` (the chips
    the cell uses, not every chip the host holds), and the host line that
    holds the window span."""
    prefix = "/device:TPU:"
    wanted = {int(k) for k in device_ids}
    devices, host = [], None
    for plane in planes:
        name = plane.name
        if name.startswith(prefix) and name[len(prefix):].isdigit():
            if int(name[len(prefix):]) not in wanted:
                continue
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices.append(dev)
        elif name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(n == WINDOW_SPAN for n, _, _ in evs):
                    host = evs
    if len(devices) != len(wanted):
        raise ValueError(f"the trace holds {len(devices)} of the device "
                         f"planes {sorted(wanted)}")
    if host is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return devices, host


def reduce_trace(log_dir: str, device_ids) -> Reduced:
    """Read the newest trace under log_dir and reduce it over the devices
    numbered ``device_ids``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(log_dir))
    return reduce_events(*trace_events(pd.planes, device_ids))
