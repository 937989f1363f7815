"""The program's own tracing: host spans, stage scopes and a compile count.

Three instruments, each read from outside the program:

* ``span(name)`` is a ``jax.profiler.TraceAnnotation`` named
  ``engine.<name>``.  It writes into the profiler's own trace, on the
  clock the device planes are aligned to, and does nothing while no
  profiler session runs (its arguments are formatted only inside one).
* ``stage(name)`` is ``jax.named_scope("fl.<name>")`` for one of
  ``STAGES``.  It names the ops of the compiled grid program in their
  ``op_name`` metadata, so a device trace joined to the compiled HLO
  credits each op's device time to a stage; it adds no op.
* ``compile_counts()`` snapshots a count kept by a ``jax.monitoring``
  listener, registered once when this module is first imported: how many
  times, and for how many host seconds, JAX traced a function to a jaxpr,
  lowered a jaxpr to MLIR and ran the backend compiler (a persistent-cache
  fetch included), over the life of the process.

Capture a sweep with ``jax.profiler.trace(dir)`` around ``run_grid`` (see
docs/performance.md, "Tracing a sweep").
"""
from __future__ import annotations

import threading

import jax

SPAN_PREFIX = "engine."
STAGE_PREFIX = "fl."
STAGES = ("init", "warmup", "geometry", "select", "train", "server", "eval")

# jax.monitoring duration events -> the phase of compilation each times
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``engine.<name>`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


def stage(name: str) -> jax.named_scope:
    """The named scope ``fl.<name>`` of one stage of the grid program."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    return jax.named_scope(STAGE_PREFIX + name)


class _CompileCount:
    """Counts and host seconds per compile phase, fed by jax.monitoring.

    Traces nest (each jitted function inside the grid program is traced
    while the program is), so a phase's seconds are the union of its
    events' time spans, not the sum of their durations.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._count = dict.fromkeys(COMPILE_EVENTS.values(), 0)
        self._seconds = dict.fromkeys(COMPILE_EVENTS.values(), 0.0)
        # per phase, the disjoint spans so far, in order
        self._spans = {phase: [] for phase in COMPILE_EVENTS.values()}

    def __call__(self, event: str, start: float, end: float, **_):
        phase = COMPILE_EVENTS.get(event)
        if phase is None:
            return
        with self._lock:
            self._count[phase] += 1
            spans = self._spans[phase]
            # events arrive as they end: one overlaps only the latest spans
            while spans and spans[-1][1] >= start:
                a, b = spans.pop()
                self._seconds[phase] -= b - a
                start, end = min(start, a), max(end, b)
            spans.append((start, end))
            self._seconds[phase] += end - start

    def snapshot(self) -> dict:
        with self._lock:
            return {phase: {"count": self._count[phase],
                            "seconds": self._seconds[phase]}
                    for phase in self._count}


_COUNT = _CompileCount()
jax.monitoring.register_event_time_span_listener(_COUNT)


def compile_counts() -> dict:
    """{"trace" | "lower" | "compile": {"count", "seconds"}} so far in this
    process (from this module's first import)."""
    return _COUNT.snapshot()
