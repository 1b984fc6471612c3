"""The profiler's trace of a window, reduced to what the metric readers use.

``Trace`` holds, for each TPU device, the operations that ran on it (from
the device plane's "XLA Ops" line: name, the XLA module it belongs to,
start and duration in ns) and the executions of each jitted program (the
"XLA Modules" line), and the host spans the benchmark placed with
``jax.profiler.TraceAnnotation`` (names starting ``bench/``).  All times
are the profiler's, on one clock.  ``to_json``/``from_json`` keep a small
recorded trace as a test fixture.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import re
import shutil
from pathlib import Path

SPAN_PREFIX = "bench/"


@dataclasses.dataclass
class Event:
    name: str
    module: str
    start_ns: float
    dur_ns: float
    container: bool = False

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]        # device plane -> operations
    modules: dict[str, list[Event]]    # device plane -> program runs
    spans: list[Event]                 # host spans (module = "")
    _starts: dict = dataclasses.field(default_factory=dict, repr=False)

    # -- reading -----------------------------------------------------------
    def window(self) -> tuple[float, float]:
        """(start, end) ns of the benchmark's measured window span."""
        for s in self.spans:
            if s.name == SPAN_PREFIX + "window":
                return s.start_ns, s.end_ns
        raise ValueError("the trace holds no bench/window span")

    def module_runs(self, program: str) -> dict[str, list[Event]]:
        """Executions of the jitted program whose XLA module is named
        ``jit_<program>``, per device, inside the window."""
        lo, hi = self.window()
        want = f"jit_{program}"
        return {dev: [e for e in evs if _module_name(e.name) == want
                      and lo <= e.start_ns <= hi]
                for dev, evs in self.modules.items()}

    def dominant_program(self) -> str | None:
        """The jitted program with the most device time in the window, as
        ``module_runs`` takes it (without the ``jit_`` prefix)."""
        lo, hi = self.window()
        time: dict[str, float] = {}
        for evs in self.modules.values():
            for e in evs:
                if lo <= e.start_ns <= hi:
                    name = _module_name(e.name)
                    time[name] = time.get(name, 0.0) + e.dur_ns
        if not time:
            return None
        return max(time, key=time.get).removeprefix("jit_")

    def ops_in(self, run: Event, dev: str) -> list[Event]:
        """Ops on ``dev`` that start inside ``run`` (ops are sorted)."""
        starts = self._starts.get(dev)
        if starts is None:
            starts = self._starts[dev] = [o.start_ns for o in self.ops[dev]]
        lo = bisect.bisect_left(starts, run.start_ns)
        hi = bisect.bisect_left(starts, run.end_ns)
        return self.ops[dev][lo:hi]

    def busy_ns(self, dev: str) -> float:
        """Union of the operation intervals on ``dev`` inside the window."""
        lo, hi = self.window()
        return _union(((max(o.start_ns, lo), min(o.end_ns, hi))
                       for o in self.ops[dev]
                       if o.end_ns > lo and o.start_ns < hi))

    def idle_gaps(self, dev: str) -> list[tuple[float, float]]:
        """Intervals of the window in which no operation ran on ``dev``."""
        lo, hi = self.window()
        gaps, cur = [], lo
        for a, b in merged((o.start_ns, o.end_ns) for o in self.ops[dev]
                            if o.end_ns > lo and o.start_ns < hi):
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if hi > cur:
            gaps.append((cur, hi))
        return gaps

    def host_doing(self, gaps) -> list[str]:
        """For each interval of ``gaps`` (in time order), the innermost
        benchmark span covering its middle: what the host was doing."""
        spans = sorted((s for s in self.spans
                        if s.name != SPAN_PREFIX + "window"),
                       key=lambda s: s.start_ns)
        active, i, out = [], 0, []
        for a, b in gaps:
            mid = (a + b) / 2
            while i < len(spans) and spans[i].start_ns <= mid:
                active.append(spans[i])
                i += 1
            active = [s for s in active if s.end_ns >= mid]
            best = min(active, key=lambda s: s.dur_ns, default=None)
            out.append(best.name if best else "host: outside any bench span")
        return out

    # -- fixture form --------------------------------------------------------
    def to_json(self) -> dict:
        def rows(evs):
            return [[e.name, e.module, e.start_ns, e.dur_ns, e.container]
                    for e in evs]
        return {"ops": {d: rows(v) for d, v in self.ops.items()},
                "modules": {d: rows(v) for d, v in self.modules.items()},
                "spans": rows(self.spans)}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        def evs(rows):
            return sorted((Event(*r) for r in rows),
                          key=lambda e: e.start_ns)
        return cls({d: evs(v) for d, v in data["ops"].items()},
                   {d: evs(v) for d, v in data["modules"].items()},
                   evs(data["spans"]))


_CONTAINER = re.compile(r"[\s)}](while|conditional|call)\(")


def op_label(hlo_text: str) -> str:
    """A short, stable name for an op from its HLO text: the instruction's
    name, and for a Pallas kernel the custom call's target before it."""
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in hlo_text:
        return "tpu_custom_call:" + head
    return head


def is_container(hlo_text: str) -> bool:
    """A while loop, conditional or call: an op whose time holds others."""
    body = hlo_text.split(" = ", 1)[-1].split(", condition=", 1)[0]
    return bool(_CONTAINER.search(body))


def _module_name(name: str) -> str:
    """'jit_step(123)' -> 'jit_step'."""
    return name.split("(", 1)[0]


def merged(intervals) -> list[list[float]]:
    """Sorted, overlapping intervals joined."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _union(intervals) -> float:
    return float(sum(b - a for a, b in merged(intervals)))


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return ""


def load(directory: Path) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one xplane.pb under {directory}, "
                         f"found {len(files)}")
    data = ProfileData.from_file(str(files[0]))
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        Event(op_label(e.name), str(_stat(e, "hlo_module")),
                              e.start_ns, e.duration_ns,
                              is_container(e.name))
                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        Event(e.name, e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, "", e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for evs in list(ops.values()) + list(modules.values()):
        evs.sort(key=lambda e: e.start_ns)
    for dev in ops:
        _attribute(ops[dev], modules.get(dev, []))
    return Trace(ops, modules, spans)


def _attribute(ops: list[Event], runs: list[Event]) -> None:
    """Name each op's program: the module run whose interval holds it."""
    i = 0
    for o in ops:
        while i < len(runs) and runs[i].end_ns < o.start_ns:
            i += 1
        if i < len(runs) and runs[i].start_ns <= o.start_ns:
            o.module = _module_name(runs[i].name)


@contextlib.contextmanager
def recording(directory: Path, enabled: bool):
    """Profile the body into ``directory`` (emptied first) when enabled."""
    if not enabled:
        yield
        return
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True)
    jax.profiler.start_trace(str(directory))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
