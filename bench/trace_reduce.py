"""Profiler trace -> the numbers the per-layer metrics read.

``load_xplane`` turns a JAX profiler dump (``*.xplane.pb``) into a
``TraceView``: per device, the op and module events; on the host, the
TraceMe events (the benchmark's own annotations among them). Everything
after that is plain interval arithmetic on ``TraceView``, so the reduction
can be checked on a synthetic trace without a chip.

Times are nanoseconds on the profiler's common clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

#: the benchmark's host annotation around the traced part of the window
WINDOW_SPAN = "bench.window"

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|psum|pmin|pmax", re.I)


@dataclass
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class TraceView:
    #: device id -> op events (one row of work each)
    ops: dict = field(default_factory=dict)
    #: device id -> module (whole jitted program) events
    modules: dict = field(default_factory=dict)
    #: host TraceMe events, the benchmark's annotations among them
    host: list = field(default_factory=list)

    def window(self) -> tuple[float, float] | None:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if not spans:
            return None
        return min(e.start for e in spans), max(e.end for e in spans)


# --------------------------------------------------------------------------
# loading
# --------------------------------------------------------------------------

def find_xplane(log_dir) -> Path | None:
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    return files[-1] if files else None


def load_xplane(path) -> TraceView:
    """Read a profiler dump: device planes ``/device:<KIND>:<n>`` give op
    events from their ``XLA Ops`` line and module events from their
    ``XLA Modules`` line; the host plane's lines give TraceMe events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    view = TraceView()
    for plane in data.planes:
        m = re.match(r"/device:[A-Z_]+:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = view.ops.setdefault(dev, [])
                elif line.name == "XLA Modules":
                    dest = view.modules.setdefault(dev, [])
                else:
                    continue
                dest.extend(Event(e.name, float(e.start_ns),
                                  float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                view.host.extend(Event(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                 for e in line.events)
    return view


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    merged: list[list[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def total(intervals) -> float:
    return sum(t - s for s, t in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, t in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def busy(view: TraceView, dev, lo: float, hi: float):
    """Merged intervals in which an operation ran on ``dev`` — op events,
    or module events where the device line has no ops."""
    evs = view.ops.get(dev) or view.modules.get(dev) or []
    return union(clip(evs, lo, hi))


def busy_seconds(view: TraceView) -> tuple[float, float] | None:
    """(busy seconds averaged over the devices, window seconds)."""
    win = view.window()
    devs = sorted(set(view.ops) | set(view.modules))
    if win is None or not devs:
        return None
    lo, hi = win
    b = [total(busy(view, d, lo, hi)) for d in devs]
    return sum(b) / len(b) / 1e9, (hi - lo) / 1e9


def idle_pct(view: TraceView) -> float | None:
    bw = busy_seconds(view)
    if bw is None or bw[1] <= 0:
        return None
    return 100.0 * (1.0 - bw[0] / bw[1])


def _stem(name: str) -> str:
    return name.split("(")[0].strip()


def module_seconds(view: TraceView, prefix: str) -> float:
    """Device seconds of the modules whose name starts with ``prefix``,
    summed within the window and averaged over the devices."""
    win = view.window()
    if win is None or not view.modules:
        return 0.0
    per = [total(clip([e for e in evs if _stem(e.name).startswith(prefix)],
                      *win))
           for evs in view.modules.values()]
    return sum(per) / len(per) / 1e9


def module_count(view: TraceView) -> int:
    """Module launches within the window on the first device."""
    win = view.window()
    if win is None or not view.modules:
        return 0
    return sum(1 for e in view.modules[min(view.modules)]
               if win[0] <= e.start < win[1])


def collective_exposed_pct(view: TraceView) -> float | None:
    """Share of the window in which a collective runs on a device with no
    other op beside it, averaged over the devices; None without any
    collective in the trace."""
    win = view.window()
    if win is None or not view.ops:
        return None
    lo, hi = win
    shares = []
    seen = False
    for evs in view.ops.values():
        coll = [e for e in evs if COLLECTIVE.search(e.name)]
        seen |= bool(coll)
        other = [e for e in evs if not COLLECTIVE.search(e.name)]
        exposed = subtract(union(clip(coll, lo, hi)),
                           union(clip(other, lo, hi)))
        shares.append(total(exposed) / (hi - lo))
    if not seen:
        return None
    return 100.0 * sum(shares) / len(shares)


#: ops that only hold other ops (a scan's while loop): left out of the top
#: list, whose entries would otherwise count their bodies twice
_CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]*$")


def op_name(name: str) -> str:
    """An op's short name: the HLO instruction name before `` = ``."""
    return name.split(" = ", 1)[0].strip()


def top_ops(view: TraceView, n: int = 10) -> list:
    """[[op name, device seconds]] of the ops that took most time in the
    window on the first device (containers of other ops left out)."""
    win = view.window()
    if win is None or not view.ops:
        return []
    acc: dict = {}
    for s, t, name in ((max(e.start, win[0]), min(e.end, win[1]),
                        op_name(e.name))
                       for e in view.ops[min(view.ops)]
                       if not _CONTAINERS.match(op_name(e.name))):
        if t > s:
            acc[name] = acc.get(name, 0.0) + (t - s) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(view: TraceView, n: int = 10) -> list:
    """[[what the host was doing, seconds]] for the longest idle gaps of the
    first device in the window. A gap is named after the innermost host
    event that covers its midpoint (the benchmark's annotation when no
    deeper one does)."""
    win = view.window()
    devs = sorted(set(view.ops) | set(view.modules))
    if win is None or not devs:
        return []
    lo, hi = win
    gaps = subtract([(lo, hi)], busy(view, devs[0], lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, t in gaps[:n]:
        mid = 0.5 * (s + t)
        cover = [e for e in view.host if e.start <= mid < e.end]
        name = min(cover, key=lambda e: e.dur).name if cover else "host"
        out.append([name, (t - s) / 1e9])
    return out
