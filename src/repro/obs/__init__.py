"""repro.obs — unified telemetry plane.

One nullable handle (``Telemetry``) threads through every subsystem:
the training service, transport, fleet/chaos controllers, worker
pool, deploy publisher, and serving engine all accept
``telemetry=None`` and pay nothing when it is absent (``NULL`` is a
shared no-op whose ``span`` returns a singleton context manager).

A span goes to two sinks:

- the ``jax.profiler`` capture, when one is running: ``span(name,
  **args)`` enters a ``jax.profiler.TraceAnnotation`` with the args as
  its metadata, on the device trace's clock, whether the handle is
  ``NULL`` or live.  A span whose args hold ``step_num`` is a step
  annotation (``StepTraceAnnotation``).  jax is imported on the first
  span, so ``python -m repro.obs`` needs no accelerator.
- the crash-safe JSONL trace (``trace.py``), when ``Telemetry`` was
  given a path.

``complete_span`` (a span whose start was captured earlier, such as
``serve.swap``) and ``instant`` go to the JSONL trace only.  Enabled,
the handle also provides a typed
:class:`~repro.obs.metrics.MetricRegistry` (``.metrics``) with
lock-free hot-path recording, ``sample_metrics()`` (a snapshot of the
registry into the trace as a counter record), and exporters:
Chrome/Perfetto ``trace_event`` JSON (``perfetto.py``) and a summary
CLI (``python -m repro.obs``).

Span/event name vocabulary (``plane.component``).  A name, or an
argument, is here because something reads it: ``python -m repro.obs
summary`` (``summary.py``), the README's Observability section, or a
benchmark metric (``bench/metrics/``).

========================  =============================================
``train.phase``           one shard×phase inner-loop execution
                          (``shard``, ``phase``: summary)
``train.fragment_send``   one fragment slot shipped on the wire
                          (``shard``, ``phase``: summary)
``transport.ship``        mesh transport device round-trip
``transport.retry``       instant: a send attempt failed and backed off
                          (``shard``: summary)
``fleet.epoch``           instant: membership epoch commit
``fleet.chaos``           instant: chaos controller action
``pool.task``             worker-pool task execution
``deploy.cycle``          one publisher publish cycle
``deploy.canary``         canary gate evaluation
``serve.swap``            engine hot-swap window (drain start→install)
``serve.tick``            one continuous-batching engine step
                          (``step_num``: the tick)
``serve.schedule``        swap poll, routing, preemption and the
                          admissions decision
``serve.admit``           one island's admissions (``path``,
                          ``requests``)
``serve.prefill``         one prefill dispatch (``rows``,
                          ``padded_rows``, ``tokens``: real prompt
                          tokens, ``padded_tokens``: the tokens the
                          program computes, ``bucket``)
``serve.fetch``           a device-to-host copy of logits (``bytes``)
``serve.decode``          the tick's decode dispatches (``rows``,
                          ``slots``, ``dense``)
``serve.emit``            greedy tokens, retirement, migration
                          (``rows``, ``finished``)
========================  =============================================

The ``serve.*`` spans inside ``serve.tick`` are read from a profiler
capture by the benchmark's ``idle_in_*_ms``,
``logits_to_host_mb_per_tick`` and ``prefill_useful_share`` metrics.
"""

from __future__ import annotations

import functools
import time

from .metrics import Counter, Gauge, Histogram, MetricRegistry
from .trace import TraceWriter, read_trace, validate_trace

__all__ = [
    "NULL",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "NullTelemetry",
    "Telemetry",
    "TraceWriter",
    "as_telemetry",
    "read_trace",
    "validate_trace",
]


class _NullSpan:
    """Singleton no-op span: zero allocation on the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kv):
        pass


_NULL_SPAN = _NullSpan()


@functools.cache
def _annotation_type():
    """``jax.profiler.TraceAnnotation`` with the span's ``set``."""
    from jax.profiler import TraceAnnotation

    class Annotation(TraceAnnotation):
        __slots__ = ()

        def set(self, **kv):
            self.set_metadata(**kv)

    return Annotation


def _annotation(name, args):
    """The span's profiler sink: an annotation while a capture runs,
    else ``None`` (nothing is allocated)."""
    cls = _annotation_type()
    if not cls.is_enabled():
        return None
    if "step_num" in args:
        return cls(name, _r=1, **args)    # what StepTraceAnnotation passes
    return cls(name, **args)


class _PairedSpan:
    """One span in both sinks: the JSONL trace and the profiler."""

    __slots__ = ("_jsonl", "_profiler")

    def __init__(self, jsonl, profiler):
        self._jsonl, self._profiler = jsonl, profiler

    def __enter__(self):
        self._profiler.__enter__()
        self._jsonl.__enter__()
        return self

    def __exit__(self, *exc):
        self._jsonl.__exit__(*exc)
        self._profiler.__exit__(*exc)
        return False

    def set(self, **kv):
        self._jsonl.set(**kv)
        self._profiler.set(**kv)


class NullTelemetry:
    """Disabled telemetry: every call is a no-op.

    ``metrics`` is ``None`` — subsystems that need a registry even
    without tracing (e.g. the service's comm accounting) create their
    own private :class:`MetricRegistry` when they see ``None``.
    """

    __slots__ = ()

    enabled = False
    metrics = None
    path = None
    trace = None

    def span(self, name, **args):
        ann = _annotation(name, args)
        return _NULL_SPAN if ann is None else ann

    def complete_span(self, name, t0_ns, **args):
        pass

    def instant(self, name, **args):
        pass

    def sample_metrics(self, prefix=""):
        pass

    def flush(self):
        pass

    def close(self):
        pass


NULL = NullTelemetry()


class Telemetry:
    """Live telemetry handle: a trace writer + a metric registry.

    ``path=None`` keeps the registry but drops all trace records —
    metrics-only mode with the same API.
    """

    enabled = True

    def __init__(self, path=None, *, meta=None, registry=None,
                 fresh=False, flush_every=None):
        self.path = None if path is None else str(path)
        self.metrics = registry if registry is not None else MetricRegistry()
        self.trace = (
            TraceWriter(path, meta=meta, fresh=fresh,
                        flush_every=flush_every)
            if path is not None else None
        )

    @property
    def epoch(self):
        return self.trace.epoch if self.trace is not None else 0

    def span(self, name, **args):
        ann = _annotation(name, args)
        if self.trace is None:
            return _NULL_SPAN if ann is None else ann
        jsonl = self.trace.span(name, **args)
        return jsonl if ann is None else _PairedSpan(jsonl, ann)

    def complete_span(self, name, t0_ns, **args):
        """Record a span whose start was captured earlier (e.g. an
        engine swap window opened ticks ago)."""
        if self.trace is not None:
            self.trace.emit_span(name, t0_ns, time.monotonic_ns(), args)

    def instant(self, name, **args):
        if self.trace is not None:
            self.trace.instant(name, **args)

    def sample_metrics(self, prefix=""):
        if self.trace is not None:
            values = self.metrics.flat(prefix)
            if values:
                self.trace.counters(values)

    def flush(self):
        """Drain the trace buffer.  File IO — never call under a
        subsystem lock (LCK301 enforces this)."""
        if self.trace is not None:
            self.trace.flush()

    def close(self):
        if self.trace is not None:
            self.sample_metrics()
            self.trace.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def as_telemetry(telemetry):
    """Normalize a nullable handle: ``None`` → the shared ``NULL``."""
    return NULL if telemetry is None else telemetry
