"""Host spans around the calls into each layer, the profiler window, and
the reduction of its trace to device busy time, module times and idle
gaps named by what the host was doing.

Spans are ``jax.profiler.TraceAnnotation`` s, so they land in the
profiler's trace on the device's clock: ``execute``, ``upload`` and
``compile`` around the endpoint calls, ``dispatch`` around the control
plane's dispatch pass, ``feeder`` around each submit."""
from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPANS = ("compile", "upload", "execute", "dispatch", "feeder")


def spanned(fn, name: str):
    import jax

    @functools.wraps(fn)
    def wrapper(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapper


def instrument(endpoints: dict, control) -> None:
    """Wrap each endpoint's compile/upload/execute and the control plane's
    dispatch pass in spans of those names."""
    for ep in endpoints.values():
        for name in ("compile", "upload", "execute"):
            setattr(ep, name, spanned(getattr(ep, name), name))
    control.drain = spanned(control.drain, "dispatch")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # Python calls would swamp the trace
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_trace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{files}")
    return files[0]


Interval = Tuple[float, float]           # seconds from the trace's start


@dataclass
class Trace:
    """What the readers need of one profiler window. Times are seconds
    from its start."""
    window_s: float
    # per device plane: (name, start, end) of each XLA module run
    modules: Dict[str, List[Tuple[str, float, float]]]
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    @property
    def devices(self) -> List[str]:
        return sorted(self.modules)

    def busy(self, dev: str) -> List[Interval]:
        return union([(s, e) for _, s, e in self.modules[dev]])

    @property
    def busy_s(self) -> float:
        """Seconds in which some module ran, averaged over the chips."""
        if not self.modules:
            return 0.0
        return sum(length(self.busy(d)) for d in self.devices) / len(
            self.modules)

    def executes(self) -> List[Interval]:
        """``execute`` spans that began and ended inside the window."""
        return [(s, e) for s, e in self.spans.get("execute", ())
                if s >= 0.0 and e <= self.window_s]

    def module_time(self, part: str, within: List[Interval]) -> float:
        """Device seconds of modules whose name holds ``part`` and whose
        midpoint lies in one of ``within``, over all chips."""
        inside = union(within)
        t = 0.0
        for dev in self.devices:
            for name, s, e in self.modules[dev]:
                if part in name and covers(inside, (s + e) / 2):
                    t += e - s
        return t

    def device_ops(self, top: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for dev in self.devices:
            for name, s, e in self.modules[dev]:
                key = name.split("(")[0]
                tot[key] = tot.get(key, 0.0) + (e - s)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda x: -x[1])
                [:top]]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The longest stretches with no module running on a chip, each
        named by the host span that covers most of it (``no_span``:
        none of them)."""
        gaps = []
        for dev in self.devices:
            edge = 0.0
            for s, e in self.busy(dev) + [(self.window_s, self.window_s)]:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
        out = []
        for g in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            cover = {k: overlap(union(self.spans.get(k, [])), g)
                     for k in SPANS}
            name = max(SPANS, key=lambda k: cover[k])
            out.append([name if cover[name] > 0 else "no_span", g[1] - g[0]])
        return out


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(iv: List[Interval]) -> float:
    return sum(e - s for s, e in iv)


def covers(merged: List[Interval], t: float) -> bool:
    import bisect
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and merged[i][0] <= t <= merged[i][1]


def overlap(merged: List[Interval], g: Interval) -> float:
    return sum(max(0.0, min(e, g[1]) - max(s, g[0])) for s, e in merged)


def reduce(path: str) -> Trace:
    """Read an ``.xplane.pb``: module runs of every device plane's ``XLA
    Modules`` line, and the benchmark's spans from the host plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: Dict[str, List] = {}
    spans: Dict[str, List[Interval]] = {k: [] for k in SPANS}
    window = None
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = (int(st["profile_stop_time"])
                          - int(st["profile_start_time"])) / 1e9
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        (e.name, e.start_ns / 1e9, e.end_ns / 1e9)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append((e.start_ns / 1e9,
                                              e.end_ns / 1e9))
    if window is None:
        raise RuntimeError(f"{path}: no profile start/stop time")
    return Trace(window, modules, spans)
