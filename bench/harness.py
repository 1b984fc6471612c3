"""What every cell shares: files found by name, the table of peaks, the
look for a chip, device facts, the compile counter and percentiles.

Nothing here knows a configuration, a traffic mix or a metric by name: a
cell is found through ``BENCHMARK.json``, its configuration under
``bench/configs/``, its traffic mix under ``bench/traffic/`` (a JSON file
that names its generator, a module beside it), its window loop under
``bench/drivers/`` and each per-layer metric under ``bench/metrics/``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:       # the program under test
    sys.path.insert(0, str(ROOT / "src"))


class BenchError(RuntimeError):
    """A cell cannot run as asked (no chip, unknown device, bad file)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    bench = benchmark()
    entry = find(bench["configs"], name, "configuration")
    cfg = load_json(ROOT / entry["file"])
    cfg["name"] = name
    return cfg


def traffic(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no traffic file {path.relative_to(ROOT)}")
    mix = load_json(path)
    mix["name"] = name
    return mix


def generator(mix: dict):
    """The module ``bench/traffic/<generator>.py`` that the mix names."""
    return plugin("traffic", mix["generator"])


def plugin(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by ``device_kind``.  A device the
    table does not hold is an error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchError(f"no peaks for device_kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def jax_key_seed(seed: int) -> tuple[int, int]:
    """Any whole-number seed as two 32-bit words (low, high)."""
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it (a value that was observed)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = max(math.ceil(q / 100.0 * len(xs)), 1)
    return float(xs[k - 1])


def accelerators(chips: int):
    """The first ``chips`` TPU devices; raises when there are fewer."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"no TPU found: JAX platform is {platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, found "
                         f"{len(devices)}")
    return devices[:chips]


def device_facts(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts traces and XLA backend compiles (and their seconds) as they
    happen, through JAX's monitoring events."""

    def __init__(self) -> None:
        import jax
        from jax._src import dispatch
        self.compiles = 0
        self.compile_s = 0.0
        self.traces = 0
        compile_event = dispatch.BACKEND_COMPILE_EVENT
        trace_event = "/jax/core/compile/jaxpr_trace_duration"

        def listen(event, duration, **_):
            if event == compile_event:
                self.compiles += 1
                self.compile_s += duration
            elif event == trace_event:
                self.traces += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.traces


def enable_caches() -> str:
    """JAX's persistent compilation cache at the program's fixed place in
    the checkout (or ``$JAX_COMPILATION_CACHE_DIR``), every program kept."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Clock:
    """Host clock in seconds since ``t0`` (``perf_counter``; default now)."""

    def __init__(self, t0: float | None = None) -> None:
        self.t0 = time.perf_counter() if t0 is None else t0

    def now(self) -> float:
        return time.perf_counter() - self.t0
