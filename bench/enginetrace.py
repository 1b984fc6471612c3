"""The serving engine's own marks in a window's profiler trace.

``devtrace.load`` keeps the benchmark's ``bench/`` spans only.  This reads
the same ``.xplane.pb`` into an ``EngineTrace``: a ``devtrace.Trace`` with
what the program marks itself added.

- ``engine``: the host spans named ``engine/...`` that the serving engine
  places (``repro.serving.engine.span``), each with its arguments
  (``rid``, ``slot``, ``step``, ``tokens``, ...), on the device's clock.
- ``Op.op_name``: each TPU op's scope path, the ``op_name`` of its HLO
  metadata.  On TPU v5e the profiler keeps it as the ``tf_op`` stat
  (``<op_name>:<type>``) of the op's event metadata, which
  ``ProfileData``'s events do not carry, so ``op_names`` reads the
  planes' metadata from the file through a minimal XPlane schema.
  ``Op.scope`` is the innermost of the program's named scopes
  (``SCOPES``) on that path, or "" for an op in none (the compiler's own
  copies, loop control).
- ``host_doing`` charges an idle gap to the innermost covering span of
  either set, so a gap reads ``engine/sample`` where the benchmark's span
  around it reads ``bench/decode``.

A program that places no ``engine/`` spans gives an empty ``engine``, and
readers that need it read nothing.  ``of(reading)`` finds the run's trace
for a metric reader and reads it once for every reader.
"""
from __future__ import annotations

import dataclasses
import functools
from pathlib import Path

import devtrace
import harness

SPAN_PREFIX = "engine/"
OP_NAME_STAT = "tf_op"
SCOPES = ("embed", "qkv", "kv_write", "attention", "attn_out", "mlp", "head")
# where run.py profiles a cell's window: bench_out/trace/<cell>
TRACES = harness.ROOT / "bench_out" / "trace"


@dataclasses.dataclass
class Span(devtrace.Event):
    args: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Op(devtrace.Event):
    op_name: str = ""

    @property
    def scope(self) -> str:
        """The innermost of ``SCOPES`` on the op's scope path, or ""."""
        for part in reversed(self.op_name.split("/")):
            if part in SCOPES:
                return part
        return ""


@dataclasses.dataclass
class EngineTrace(devtrace.Trace):
    engine: list[Span] = dataclasses.field(default_factory=list)

    def named(self, name: str) -> list[Span]:
        """Engine spans ``engine/<name>`` that end inside the window."""
        lo, hi = self.window()
        want = SPAN_PREFIX + name
        return [s for s in self.engine
                if s.name == want and lo <= s.end_ns <= hi]

    def host_doing(self, gaps) -> list[str]:
        both = devtrace.Trace(self.ops, self.modules, self.spans + self.engine)
        return both.host_doing(gaps)

    def to_json(self) -> dict:
        out = super().to_json()
        out["ops"] = {d: [[o.name, o.module, o.start_ns, o.dur_ns,
                           o.container, o.op_name] for o in v]
                      for d, v in self.ops.items()}
        out["engine"] = [[s.name, s.start_ns, s.dur_ns, s.args]
                         for s in self.engine]
        return out

    @classmethod
    def from_json(cls, data: dict) -> "EngineTrace":
        """Also reads a ``devtrace`` fixture (no op names, no engine)."""
        def evs(rows, kind=devtrace.Event):
            return sorted((kind(*r) for r in rows), key=lambda e: e.start_ns)
        return cls({d: evs(v, Op) for d, v in data["ops"].items()},
                   {d: evs(v) for d, v in data["modules"].items()},
                   evs(data["spans"]),
                   engine=evs(([n, "", s, d, False, a]
                               for n, s, d, a in data.get("engine", [])),
                              Span))


def load(directory: Path) -> EngineTrace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``directory``:
    what ``devtrace.load`` reads, with op names and engine spans."""
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one xplane.pb under {directory}, "
                         f"found {len(files)}")
    data = ProfileData.from_file(str(files[0]))
    names = op_names(files[0])
    ops, modules, spans, engine = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [
                        Op(devtrace.op_label(e.name),
                           str(devtrace._stat(e, "hlo_module")), e.start_ns,
                           e.duration_ns, devtrace.is_container(e.name),
                           names.get(plane.name, {}).get(e.name, ""))
                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        devtrace.Event(e.name, e.name, e.start_ns,
                                       e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(devtrace.SPAN_PREFIX):
                        spans.append(devtrace.Event(e.name, "", e.start_ns,
                                                    e.duration_ns))
                    elif e.name.startswith(SPAN_PREFIX):
                        engine.append(Span(e.name, "", e.start_ns,
                                           e.duration_ns,
                                           args={k: v for k, v in e.stats}))
    for evs in list(ops.values()) + list(modules.values()):
        evs.sort(key=lambda e: e.start_ns)
    for dev in ops:
        devtrace._attribute(ops[dev], modules.get(dev, []))
    engine.sort(key=lambda e: e.start_ns)
    return EngineTrace(ops, modules, spans, engine=engine)


@functools.cache
def _xspace():
    """The message class of an XSpace that keeps only the planes' event
    and stat metadata (XPlane, tsl/profiler/protobuf/xplane.proto)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="xmeta.proto",
                                           package="xmeta", syntax="proto3")
    fields = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64), ("str_value", 5,
                  F.TYPE_STRING), ("ref_value", 7, F.TYPE_UINT64)],
        "XEventMetadata": [("id", 1, F.TYPE_INT64), ("name", 2,
                           F.TYPE_STRING), ("stats", 5, "XStat")],
        "XStatMetadata": [("id", 1, F.TYPE_INT64), ("name", 2,
                          F.TYPE_STRING)],
        "EventEntry": [("key", 1, F.TYPE_INT64),
                       ("value", 2, "XEventMetadata")],
        "StatEntry": [("key", 1, F.TYPE_INT64), ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, F.TYPE_STRING), ("event_metadata", 4,
                   "EventEntry"), ("stat_metadata", 5, "StatEntry")],
        "XSpace": [("planes", 1, "XPlane")]}
    for name, spec in fields.items():
        m = f.message_type.add(name=name)
        for field, number, kind in spec:
            if isinstance(kind, str):      # a message: repeated, or a map
                label = (F.LABEL_OPTIONAL if field == "value"     # value
                         else F.LABEL_REPEATED)
                m.field.add(name=field, number=number, label=label,
                            type=F.TYPE_MESSAGE, type_name=".xmeta." + kind)
            else:
                m.field.add(name=field, number=number, type=kind,
                            label=F.LABEL_OPTIONAL)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xmeta.XSpace"))


def op_names(path: Path) -> dict[str, dict[str, str]]:
    """For each TPU plane of the ``.xplane.pb`` at ``path``: an op's name
    (its HLO text, as the XLA Ops events give it) -> its ``op_name``."""
    space = _xspace().FromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        want = {k for k, v in stat.items() if v == OP_NAME_STAT}
        names = out[plane.name] = {}
        for entry in plane.event_metadata:
            m = entry.value
            for st in m.stats:
                if st.metadata_id in want:
                    names[m.name] = (st.str_value or
                                     stat.get(st.ref_value, "")).rstrip(":")
    return out


@functools.lru_cache(maxsize=1)
def _load_once(path: Path, mtime_ns: int) -> EngineTrace:
    return load(path.parent)


def of(reading) -> EngineTrace | None:
    """The trace of the run a metric reader reads, with the engine's marks,
    read once for every reader; None when the cell's trace directory holds
    no trace of this run (its window differs from the reading's)."""
    cells = [w["name"] for w in harness.benchmark()["workloads"]
             if w["config"] == reading.cfg.get("name")
             and w["traffic"] == reading.mix.get("name")]
    if len(cells) != 1:
        return None
    files = sorted((TRACES / cells[0]).rglob("*.xplane.pb"))
    if len(files) != 1:
        return None
    trace = _load_once(files[0], files[0].stat().st_mtime_ns)
    try:
        same = trace.window() == reading.trace.window()
    except ValueError:
        return None
    return trace if same else None
