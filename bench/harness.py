"""Pieces every driver shares: the device record, the compile counter, the
traced window, peak memory, and freeing the program's state."""

from __future__ import annotations

import gc
import shutil
import threading
from pathlib import Path

import jax
import numpy as np

from bench import spec, trace as trace_mod

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
TRACE_DIR = spec.ROOT / "bench_out" / "trace"


class CompileCounter:
    """Counts lowerings and backend compiles while ``armed``."""

    def __init__(self):
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in COMPILE_EVENTS:
            with self._lock:
                self.count += 1


class Tracer:
    """Profiler trace around a window.  ``max_seconds`` stops it from a
    helper thread after that long, for windows too long to trace whole; the
    host span ``bench_window`` marks the traced stretch either way."""

    def __init__(self, name: str, max_seconds: float | None = None):
        self.dir = TRACE_DIR / name
        self.max_seconds = max_seconds
        self._stop = threading.Event()
        self._thread = None
        self.reduced = None

    def __enter__(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        # the first transfer after the profiler starts stalls the chip for
        # about a second; take it here, outside the traced window
        jax.device_put(np.zeros((1,), np.float32)).block_until_ready()
        self._thread = threading.Thread(target=self._span, daemon=True)
        self._thread.start()
        return self

    def _span(self):
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            self._stop.wait(self.max_seconds)
        jax.profiler.stop_trace()

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        return False

    def reduce(self):
        self.reduced = trace_mod.reduce(trace_mod.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free(tree) -> None:
    """Delete every device array of ``tree`` now, not when GC gets to it."""
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()
    gc.collect()
