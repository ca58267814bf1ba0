"""What every family needs around its measured window: the device it
runs on, a quiet process, a count of compilations, and a profiler
session whose host spans sit on the same clock as the device's ops."""

from __future__ import annotations

import gc
import glob
import os
import shutil
import tempfile
import time


class NoAccelerator(Exception):
    """The run found no TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int, rehearse: bool):
    """The cell's devices. On the chip path anything but a TPU with
    enough chips ends the run (exit code 2, no result line): a
    measurement never falls back to the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and platform != "tpu":
        raise NoAccelerator(
            f"benchmarks/run.py: JAX found platform {platform!r}, not a TPU; "
            "a cell is measured only on the chip (use --rehearse for a CPU "
            "rehearsal, which prints no device metric)"
        )
    if len(devices) < chips:
        raise NoAccelerator(
            f"benchmarks/run.py: the cell asks for {chips} chips and JAX "
            f"found {len(devices)}"
        )
    return devices[:chips]


def device_report(devices, program_temp_bytes: int = 0) -> dict:
    """The device as JAX reports it. `memory_peak_bytes` is the fullest
    chip's `peak_bytes_in_use` plus the larger of its
    `peak_bytes_reserved` and the temporaries of the family's largest
    program, where the family gives them: the runtime's counters leave a
    running program's temporaries out."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(
            int(stats.get("peak_bytes_in_use", 0))
            + max(int(stats.get("peak_bytes_reserved", 0)), int(program_temp_bytes))
        )
    return {
        "platform": str(devices[0].platform),
        "kind": str(devices[0].device_kind),
        "count": len(devices),
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }


class CompileCounter:
    """Backend compilations and persistent-cache misses since `reset()`,
    from jax.monitoring. A run with one inside its window is not a
    measurement."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compiles = 0
        self.cache_misses = 0
        self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.compiles = self.cache_misses = self.cache_hits = 0
        self.seconds = 0.0

    def snapshot(self) -> dict:
        return {
            "compiles": self.compiles,
            "cache_misses": self.cache_misses,
            "cache_hits": self.cache_hits,
            "compile_s": self.seconds,
        }


class CompiledHere(Exception):
    """This process had to compile a program: it does not measure."""


def settle(ctx):
    """The last thing before the window. A process that compiled any of
    its programs starts over and fetches them all (`run.py` says why).
    Then collect, and freeze what survives, so that no generation-2
    collection walks the model's Python objects inside the window."""
    if ctx.may_start_over and ctx.compiles.cache_misses:
        raise CompiledHere()
    gc.collect()
    gc.freeze()


def span(name: str):
    """A host span in the profiler's own trace (nothing when no trace is
    running, beyond a TraceMe check)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class TraceSession:
    """One profiler session. `start()` and `stop()` bracket the traced
    part of the window; `path` is then the .xplane.pb. The Python tracer
    is off: it would put an event on every call of the host loop."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None
        self.path = None
        self.started = False
        self.stopped = False
        self.t_start = None
        self.t_stop = None

    def start(self):
        if not self.enabled or self.started:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # TraceAnnotations, not every runtime call
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.started = True
        self.t_start = time.perf_counter()

    def stop(self):
        if not self.started or self.stopped:
            return
        import jax

        if self.t_stop is None:
            self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.stopped = True
        found = sorted(
            glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        )
        self.path = found[-1] if found else None

    def cleanup(self):
        if self.started and not self.stopped:
            self.stop()
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


class Ctx:
    """What `run.py` hands a family: the cell's data, the run's
    arguments, the devices, the compile counter and the trace session."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, rehearse,
                 devices, t_process_start, may_start_over=False):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.devices = devices
        self.t_process_start = t_process_start
        self.may_start_over = may_start_over
        self.compiles = CompileCounter()
        self.tracer = TraceSession(self.trace)
        self.marks = []  # (what was just finished, seconds since process start)

    def mark(self, what: str) -> None:
        """A point on the run's timeline, for the line's `setup_marks`."""
        self.marks.append((what, time.perf_counter() - self.t_process_start))
