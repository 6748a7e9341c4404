"""The harness's shared pieces: finding a cell's files by name, the chip
check, seeds, spans, compile accounting and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found here by the name that
``BENCHMARK.json`` gives it:

    chipbench/configs/<config>.json     sizes, as the cell runs them
    chipbench/configs/<config>.py       its plain reference
    chipbench/traffic/<traffic>.json    the mix (its "kind" names a driver)
    chipbench/drivers/<kind>.py         set-up, window and check of a kind
    chipbench/layer_metrics/<metric>.py a reader: read(ctx) -> float | None
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"


class HarnessError(RuntimeError):
    """A run that cannot give a result: no chip, an unknown device, a
    missing file.  ``run.py`` exits non-zero with the message."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise HarnessError(f"missing file {path}") from None


def load_module(path: Path, name: str | None = None):
    path = Path(path)
    if not path.is_file():
        raise HarnessError(f"missing file {path}")
    name = name or "chipbench._files." + "_".join(
        path.resolve().relative_to(HERE).with_suffix("").parts
    ).replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    config_file: Path
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def reference_file(self) -> Path:
        return self.config_file.with_suffix(".py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: dict | None = None) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json") if bench is None else bench
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if name not in cells:
        raise HarnessError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    cfile = ROOT / conf["file"]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=conf["name"],
        config=load_json(cfile), config_file=cfile,
        traffic_name=w["traffic"],
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def driver_for(cell: Cell):
    return load_module(HERE / "drivers" / f"{cell.traffic['kind']}.py")


def reader_for(metric: str):
    return load_module(HERE / "layer_metrics" / f"{metric}.py")


def use_program_sources() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def prepare_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    given to the program through the variable it reads; every program is
    cached, however short its compile."""
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    use_program_sources()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()


def chip_devices(chips: int):
    """The devices a cell runs on.  No TPU, too few chips, or a chip
    whose peaks are not in the table is an error: the harness never
    falls back to the CPU."""
    import jax
    from chipbench.peaks import peaks_for, UnknownDevice
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise HarnessError(f"no TPU found: JAX platform is "
                           f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devices)}")
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        raise HarnessError(str(e)) from None
    return devices[:chips]


def seed_key(seed: int):
    """A PRNG key from any whole number, wider than 32 bits included."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def span(name: str):
    """A host span in the profiler's trace (chipbench.<name>)."""
    import jax
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


@contextlib.contextmanager
def maybe_trace(enabled: bool, directory: str | None):
    if not enabled:
        yield
        return
    import jax
    with jax.profiler.trace(directory):
        yield


class CompileMeter:
    """Seconds of XLA backend compilation (or of fetching an executable
    from the persistent cache), the count of programs, and the names of
    those compiled rather than fetched, from ``jax.monitoring``."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.secs, self.compiles, self.cache_hits = 0.0, 0, 0
        self.fresh_names: list[str] = []
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        # a cache hit is announced inside the timed block it ends
        if event == self.BACKEND:
            self.secs += duration
            self.compiles += 1
            if not self._hit:
                self.fresh_names.append(str(fun_name))
            self._hit = False

    def _event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1
            self._hit = True

    @property
    def fresh(self) -> int:
        """Programs compiled, not fetched from the persistent cache."""
        return len(self.fresh_names)

    def __str__(self) -> str:
        return (f"backend compile {self.secs:.1f} s, {self.compiles} "
                f"programs, {self.cache_hits} persistent-cache hits")


def memory_peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


@dataclasses.dataclass
class Check:
    """One number compared with its limit: the run is correct only if
    every value is at or under its limit (and finite)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        import math
        return math.isfinite(self.value) and self.value <= self.limit


def relative_gap(got: float, want: float) -> float:
    import math
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


def norm_gaps(got: dict, want: dict, keep=None) -> dict:
    """Per-leaf gap between two norms, against the reference's norm of
    the leaf or of the median leaf, whichever is larger."""
    import math
    import statistics
    names = [k for k in want if keep is None or k in keep]
    if not names:
        return {}
    med = statistics.median(want[k] for k in names)
    out = {}
    for k in names:
        g = got.get(k, math.nan)
        out[k] = (abs(g - want[k]) / max(want[k], med, 1e-30)
                  if math.isfinite(g) else math.inf)
    return out


def worst(gaps: dict) -> tuple[str, float]:
    if not gaps:
        return "-", 0.0
    k = max(gaps, key=gaps.get)
    return k, gaps[k]


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader sees: the traced run's trace
    summary, the counters ``drivers/<kind>.py`` kept for the traced
    window, the chip's peaks and the cell."""
    summary: object
    counters: dict
    peaks: dict
    cell: Cell


@dataclasses.dataclass
class DriverResult:
    end_to_end: dict
    counters: dict
    checks: list
    attempted: int
    failed: int
    memory_peak_bytes: int


@dataclasses.dataclass
class Context:
    """A run as ``drivers/<kind>.py`` sees it."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    trace_dir: str | None
    devices: list
    peaks: dict | None
    meter: CompileMeter
    process_start: float
