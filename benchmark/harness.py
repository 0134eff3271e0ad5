"""What ``run.py`` and the runners share: finding a cell's files by name, the
device check, the compile-cache listener, the traced slice and the small
arithmetic (percentiles) behind the end-to-end metrics.

Nothing here lists cells, configurations, metrics or readers: each is a file
found by the name ``BENCHMARK.json`` or another data file gives it.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import re
import threading
import time
from pathlib import Path
from typing import Any, Callable

ROOT = Path(__file__).resolve().parent          # benchmark/
REPO = ROOT.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


class BenchmarkError(RuntimeError):
    """The run cannot produce a result line (bad name, no chip, no peak row)."""


# ------------------------------------------------------------------ data files

def load(kind: str, name: str, root: Path = ROOT) -> dict:
    """``<root>/<kind>/<name>.json`` — kind is ``workloads``, ``configs`` or
    ``layer_metrics``.  The name comes from the command line or a data file,
    so it is held to the characters a name may have before it becomes a path."""
    if not NAME.fullmatch(name):
        raise BenchmarkError(f"{name!r} is not a name ([A-Za-z0-9_.-], 1-64)")
    path = root / kind / f"{name}.json"
    if not path.is_file():
        have = sorted(p.stem for p in (root / kind).glob("*.json"))
        raise BenchmarkError(f"no {path.relative_to(root.parent)}; {kind} has {have}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``benchmark.<kind>.<name>`` — kind is ``runners`` or ``readers``."""
    if not NAME.fullmatch(name) or "." in name or "-" in name:
        raise BenchmarkError(f"{name!r} is not a module name")
    if not (ROOT / kind / f"{name}.py").is_file():
        raise BenchmarkError(f"no benchmark/{kind}/{name}.py")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def manifest(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def cell_metrics(man: dict, group: str, cell: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` that ``cell`` reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in man[group] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------- device

def require_tpu(n_chips: int):
    """The first thing a run does with JAX: read ``jax.devices()``.  There is
    no CPU branch: without a TPU, or with too few chips, nothing is run."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchmarkError(
            f"needs a TPU; JAX found platform {d.platform!r} "
            f"({d.device_kind!r}, {len(devs)} device(s)). Nothing was run.")
    if len(devs) < n_chips:
        raise BenchmarkError(f"the cell needs {n_chips} chips; JAX found "
                             f"{len(devs)}. Nothing was run.")
    return devs[:n_chips]


def load_peak(device_kind: str, root: Path = ROOT) -> dict:
    peaks = json.loads((root / "peaks.json").read_text())
    if device_kind not in peaks:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(peaks)}). Nothing was run.")
    return peaks[device_kind]


def live_bytes(devices) -> int:
    """``bytes_in_use`` on the fullest of ``devices``; 0 on a backend that
    keeps no memory statistics (the CPU of the tests)."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


class CacheEvents:
    """Counts JAX's own persistent-compilation-cache events."""

    def __init__(self):
        import jax

        self.counts = {"compile_requests_use_cache": 0, "cache_hits": 0,
                       "cache_misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in self.counts:
            self.counts[name] += 1


# ------------------------------------------------------------------ arithmetic

def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def seed32(seed: int) -> int:
    """``--seed`` may exceed 31 bits; JAX keys and NumPy both take 32."""
    return int(seed) % (1 << 32)


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ the trace

class TraceSlice:
    """``jax.profiler`` over seconds ``[start_s, stop_s)`` of the window, from
    a thread of its own so that neither runner knows about tracing.  Traces
    are large and tracing slows the host, so only a slice is traced, and only
    in the ``--trace 1`` run."""

    def __init__(self, out_dir: Path, start_s: float, stop_s: float):
        self.out_dir, self.start_s, self.stop_s = out_dir, start_s, stop_s
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def arm(self, window_t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(window_t0,),
                                        name="benchmark-trace", daemon=True)
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax

        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # device ops are what is read
            time.sleep(max(0.0, t0 + self.start_s - time.perf_counter()))
            jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
            try:
                time.sleep(max(0.0, t0 + self.stop_s - time.perf_counter()))
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:            # surfaced by join()
            self.error = e

    def join(self) -> Path:
        """Wait for the slice to end; the ``.xplane.pb`` it wrote."""
        if self._thread is None:
            raise BenchmarkError("the runner never opened its window")
        self._thread.join()
        if self.error is not None:
            raise self.error
        found = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise BenchmarkError(f"the profiler wrote no trace under {self.out_dir}")
        return found[-1]


# ------------------------------------------------------------ runner interface

@dataclasses.dataclass
class Cell:
    """What a runner is given."""
    workload: dict              # benchmark/workloads/<cell>.json
    config: dict                # benchmark/configs/<config>.json
    seed: int
    seconds: float
    devices: list               # the chips the cell asked for
    process_t0: float           # perf_counter() at process start
    # called by the runner with perf_counter() at the window's first instant
    on_window: Callable[[float], None] = lambda t0: None


@dataclasses.dataclass
class Outcome:
    """What a runner returns: every end-to-end value it can compute, and the
    facts (counts, rates, snapshots) the per-layer readers take theirs from."""
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict[str, float]
    facts: dict[str, Any]


def transformer_config(config: dict):
    """The program's ``TransformerConfig`` from a config file's
    ``transformer_config`` group (dtypes are given by name)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    kw = dict(config["transformer_config"])
    for key in ("dtype", "param_dtype"):
        kw[key] = getattr(jnp, kw[key])
    return TransformerConfig(**kw)


def init_params_on_device(cfg, seed: int):
    """The weights, made on the device from the seed in one jitted call."""
    import jax

    from deeplearning4j_tpu.models.transformer import init_params

    return jax.jit(lambda key: init_params(key, cfg))(
        jax.random.key(seed32(seed)))
