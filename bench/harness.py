"""What every run shares: the device check, the compile cache, the run
context that drivers get, the trace of the window, and the result line.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"           # traces, made and removed by each run


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def annotate(name: str, **kw):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set (JAX reads it itself), else ``.jax_cache/`` at the root of
    the checkout, a fixed path.  Every program is kept, however fast it
    compiled, so that a warm set-up compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts tracing and compiling events while ``on``."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0

        def listen(event, *args, **kw):
            if self.on and ("compile" in event or "jaxpr_trace" in event):
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


class Context:
    """One run of one cell, as a driver sees it."""

    def __init__(self, *, name, cell, config, traffic, seed, seconds, trace,
                 control, over, started, compiles):
        self.name, self.cell, self.config, self.traffic = \
            name, cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control, self.over = control, over
        self.started = started
        self.compiles = compiles
        self.setup_s = None
        self.setup_marks = {}     # set-up phase -> seconds since start
        self.memory_peak = None
        self.trace_file = None

    def mark(self, phase: str) -> None:
        self.setup_marks[phase] = time.perf_counter() - self.started

    def window_opens(self) -> None:
        """Set-up ends here: process start to the first timed tick."""
        gc.collect()
        self.setup_s = time.perf_counter() - self.started
        self.compiles.on = True

    def window_closed(self) -> None:
        import jax
        self.compiles.on = False
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None

    @contextlib.contextmanager
    def traced_window(self):
        """The measured window, under the profiler with ``--trace 1``."""
        import jax
        if not self.trace:
            with annotate("bench.window"):
                yield
            return
        out = OUT / self.name
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            with annotate("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(str(out / "plugins/profile/*/*.xplane.pb"))
        self.trace_file = found[0] if found else None


def device_info(count_check: int | None, rehearse: bool):
    """(platform, kind, count) of the local devices; exits without a
    result where there is no TPU (unless rehearsing on the CPU) or fewer
    chips than the cell needs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not rehearse:
        print(f"bench: no TPU (JAX found {d.platform!r}); a cell runs on "
              f"the chip only", file=sys.stderr)
        sys.exit(3)
    if count_check and len(devs) < count_check:
        print(f"bench: the cell needs {count_check} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(3)
    return d.platform, d.device_kind, len(devs)


def load_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read`` function."""
    import importlib.util
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name: str):
    import importlib.util
    path = BENCH / "drivers" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"driver_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
