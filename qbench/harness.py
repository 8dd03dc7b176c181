"""What every kind of cell shares: the look for a chip, the compile cache,
the compile counter, the profiler window and the result line."""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from . import manifest

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_DIR_NAME = ".qbench_jax_cache"  # fixed: the path is part of the cache key


def enable_compile_cache() -> str:
    """Persistent compile cache where ``JAX_COMPILATION_CACHE_DIR`` says if
    it is set (nothing is set in code then), else at a fixed path inside the
    checkout. Either way every program is cached, however fast it compiled:
    the eager sampler is some 170 sub-second programs, which JAX's default
    threshold of one second would compile again in every run."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(manifest.ROOT, CACHE_DIR_NAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_chips(chips: int, any_device: bool = False) -> Dict[str, Any]:
    """The device record of the result line. Off a TPU, with an unknown kind
    of TPU or with fewer chips than the cell asks for, the run ends here
    (``any_device`` is for the tests' CPU rehearsal only)."""
    import jax

    devs = jax.devices()
    record = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if any_device:
        return record
    if record["platform"] != "tpu":
        raise SystemExit(f"qbench measures on a TPU only; JAX found {record}")
    manifest.load_peaks(record["kind"])
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX found {record}")
    return record


def memory_peak_bytes(chips: int) -> int:
    import jax

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    return max((int(s["peak_bytes_in_use"]) for s in stats if s), default=0)


class CompileWatch:
    """Counts the programs the process compiles (a persistent-cache hit
    counts too: it is a load inside the window all the same)."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self._programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self._programs += 1

    def mark(self) -> int:
        """Programs compiled since the previous mark."""
        with self._lock:
            n, self._programs = self._programs, 0
        return n

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)


class TraceWindow:
    """The profiler around the measured window of a ``--trace 1`` run. The
    trace goes to a directory under ``TMPDIR`` and is removed once reduced."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[str] = None
        self.t0 = self.t1 = 0.0

    def __enter__(self):
        if self.on:
            import jax

            self.dir = tempfile.mkdtemp(prefix="qbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.on:
            import jax

            jax.profiler.stop_trace()
        return False

    def reduce(self, keep: Optional[str] = None):
        """`reduce.TraceSummary` of the window, or None in an untraced run."""
        if not self.on:
            return None
        from . import reduce

        try:
            path = reduce.find_xplane(self.dir)
            if keep:
                os.makedirs(os.path.dirname(keep), exist_ok=True)
                shutil.copy(path, keep)
            return reduce.summarize(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def end_to_end_metrics(cell: manifest.Cell, values: Dict[str, float]) -> Dict[str, Dict[str, Any]]:
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer_metrics(cell: manifest.Cell, device: Dict[str, Any],
                      ctx: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each of the cell's per-layer metrics through its reader; a reader
    that finds nothing to read returns None and the metric is left out.
    Off a TPU (the tests' rehearsal) there are no peaks to read against."""
    ctx = dict(ctx, peaks=manifest.load_peaks(device["kind"])
               if device["platform"] == "tpu" else None)
    out = {}
    for m in cell.per_layer:
        value = manifest.load_reader(m["reader"])(ctx, **m.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                compared: Dict[str, Any], breakdown: Optional[Dict[str, List]] = None,
                extra: Optional[Dict[str, Any]] = None) -> str:
    line: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["compared"] = compared  # comes last: the driver keeps a line's end
    return json.dumps(line)
