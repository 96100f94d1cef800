"""Shared pieces of the chip benchmark: file lookup by name, the device
check, the compile counter, host spans, the measured window, seeded
weights and the result line.

Nothing here knows a cell: every cell, configuration, traffic mix and
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import math
import re
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


# -- files found by name ------------------------------------------------------

def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config_file(spec: dict, name: str, root: Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no configuration named {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def limits_file(cell: str) -> dict:
    """The limits of the numbers a cell compares for ``correct``, set from
    readings of the program and of the control (see PERF.md)."""
    path = BENCH_DIR / "limits" / f"{cell}.json"
    return {k: v for k, v in load_json(path).items()
            if not k.startswith("_")} if path.exists() else {}


def load_module(path: Path, name: Optional[str] = None):
    spec = importlib.util.spec_from_file_location(
        name or "chip_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_module(name: str):
    return load_module(BENCH_DIR / "drivers" / f"{name}.py")


def metric_reader(name: str) -> Callable:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


_MODEL_TYPE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@functools.cache
def _model_module(kind: str, model_type: str):
    path = BENCH_DIR / kind / f"{model_type}.py"
    if not _MODEL_TYPE.match(model_type) or not path.is_file():
        raise FileNotFoundError(
            f"model_type {model_type!r} needs the file {kind}/{model_type}.py "
            f"in {BENCH_DIR}, which is not there")
    return load_module(path, f"chip_{kind}_{model_type}")


def arch(cfg_file: dict):
    """The module ``archs/<model_type>.py`` of a configuration file: the
    program's configuration, the seeded weights and the counts of its
    architecture (the interface is set out in ``archs/olmo.py``)."""
    return _model_module("archs", cfg_file["model_type"])


def reference(cfg_file: dict):
    """The module ``reference/<model_type>.py``: the plain float32
    implementation that ``correct`` compares with."""
    return _model_module("reference", cfg_file["model_type"])


def cell_metrics(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: end-to-end with tracing off,
    per-layer with it on.  A metric without a ``workloads`` list is
    reported in every cell."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# -- device -------------------------------------------------------------------

class NoChip(RuntimeError):
    """The run found no accelerator it may measure on."""


def check_device(devices, chips: int, peaks: dict):
    """The first device has to be a TPU whose kind the peaks table knows,
    and JAX has to see at least ``chips`` of them."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU, but JAX's first device is on platform "
                     f"{dev.platform!r}")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return peaks[dev.device_kind]


def peaks_table() -> dict:
    return {k: v for k, v in load_json(BENCH_DIR / "peaks.json").items()
            if not k.startswith("_")}


def memory_peak_bytes(devices) -> Optional[int]:
    vals = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


# -- compile counter ----------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts XLA compilations and persistent-cache retrievals: both mean
    a program was not ready in memory when it was called."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if event in _COMPILE_EVENTS:
            self.n += 1


# -- spans --------------------------------------------------------------------

class Spans:
    """Host spans in the profiler's trace, around the benchmark's calls
    into each layer.  With tracing off they cost one attribute test."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def wrap(self, name: str, fn: Callable) -> Callable:
        if not self.on:
            return fn

        def wrapped(*a, **kw):
            with self(name):
                return fn(*a, **kw)
        return wrapped


class Profile:
    """The profiler around the measured window of a ``--trace 1`` run,
    Python tracing off (it would time every Python call)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def xplane(self) -> Optional[str]:
        found = sorted(Path(self.log_dir).rglob("*.xplane.pb"))
        return str(found[-1]) if found else None


# -- the measured window ------------------------------------------------------

class Window:
    """The measured window: opens once, closes at ``seconds`` after it
    opened.  ``t0``/``t1`` are ``time.perf_counter`` readings."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None

    def open(self):
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + self.seconds

    @property
    def is_open(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def due(self) -> bool:
        return time.perf_counter() >= self.deadline

    def close(self):
        self.t1 = time.perf_counter()

    @property
    def length(self) -> float:
        return self.t1 - self.t0


# -- the configuration as the program runs it ---------------------------------

def program_config(cfg_file: dict):
    """The program's ModelConfig for a configuration file, checked against
    the file by its architecture's module."""
    return arch(cfg_file).program_config(cfg_file)


# -- seeds and weights --------------------------------------------------------

def prng_key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size: 31 bits at a time are folded in,
    so seeds past 2**31 stay distinct."""
    import jax
    key = jax.random.PRNGKey(stream)
    s = int(seed)
    while True:
        key = jax.random.fold_in(key, s & 0x7FFFFFFF)
        s >>= 31
        if not s:
            return key


def make_params(abstract_tree, seed: int, scale: float,
                init: Optional[Callable] = None):
    """Weights for every leaf of ``abstract_tree`` (ShapeDtypeStructs),
    drawn N(0, scale) from the seed on the device, in one jitted call, in
    each leaf's own dtype.  Neither the program under test nor its
    initializer makes them, so the reference can start from the same
    numbers.  ``init(path, leaf, key)``, where given, returns a leaf's
    own initial value (float32, from ``key``), or None for the normal
    draw; ``path`` is the leaf's ``jax.tree_util.keystr``."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)

    @jax.jit
    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            x = None if init is None else init(
                jax.tree_util.keystr(path), leaf, k)
            if x is None:
                x = jax.random.normal(k, leaf.shape, jnp.float32) * scale
            out.append(x.astype(leaf.dtype))
        return out

    made = make(prng_key(seed, stream=1))
    return jax.tree_util.tree_unflatten(treedef, made)


# -- statistics ---------------------------------------------------------------

def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


# -- the run ------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, with its limit: the run is
    correct only if ``value <= limit`` for every check."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (self.value is not None and not math.isnan(self.value)
                and self.value <= self.limit)


@dataclasses.dataclass
class RunArgs:
    """What a driver gets: the cell's files, already read, and the run's
    command-line settings."""
    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)
    counter: Any = None
    t_start: float = dataclasses.field(default_factory=time.perf_counter)
    log: Callable[[str], None] = lambda msg: print(msg, file=sys.stderr,
                                                   flush=True)


@dataclasses.dataclass
class RunRecord:
    """What a driver hands back.  ``end_to_end`` holds host-clock
    metrics by name; ``host`` the host-clock series that per-layer readers
    reduce; ``trace_path`` the profiler's file of a traced run."""
    end_to_end: Dict[str, float]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    host: Dict[str, Any]
    window_s: float
    trace_path: Optional[str] = None
    compiles_in_window: int = 0


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: List[Check],
                breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value,
                              "limit": None if math.isnan(c.limit)
                              else c.limit} for c in checks}
    return json.dumps(out)
