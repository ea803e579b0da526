"""Engine step phases on the profiler's clock.

``with phase(tracer, clock, PH_DISPATCH, kind="mixed", bucket=64, rows=3):``
always opens a :class:`jax.profiler.TraceAnnotation` of that name, so any
profiler session (``jax.profiler.trace``, ``start_trace``, a TensorBoard
capture) records the phase on the host plane beside the device's
operations, and JAX's own compile annotations (``backend_compile_and_load``
and the lowering before it) nest inside the phase that triggered them.
Outside a session that is one no-op enter and exit (about 1 µs on a CPU
host). With libra-trace armed the same name and arguments are also recorded
as a span on ``TRACK_ENGINE``, timed by ``clock`` (the engine clock), so the
Perfetto export and the device trace speak one vocabulary.

This module imports JAX; :mod:`repro.obs.tracer` stays stdlib only.
"""

from __future__ import annotations

from typing import Any, Callable

from jax.profiler import TraceAnnotation

from .tracer import TRACK_ENGINE, Tracer

# The phases of ServingEngine.step (README.md §Observability). step.* cover
# the step's own stages; cache.* the data-plane copies between the dense
# running cache and the paged pool, nested in the stage that runs them.
PH_SWAPPER = "step.swapper"  # swapper tick and its proactive swaps
PH_ADMIT = "step.admit"  # ranking, lookup, admit, pin, prefix seed
PH_PLAN = "step.plan"  # plan_step, batch assembly, adapter ids
PH_DISPATCH = "step.dispatch"  # the jitted step call; kind, bucket, rows
PH_MERGE = "step.merge"  # decode rows merged into the dense cache
PH_READBACK = "step.readback"  # argmax and the transfer that blocks on it
PH_FINISH = "step.finish"  # a finished request's bookkeeping
PH_GATHER = "cache.gather"  # matched prefix: pool -> dense cache
PH_SCATTER = "cache.scatter"  # finished request's KV: dense cache -> pool
PH_SWAP = "cache.swap"  # one swap op: a swap-out waits for its copy

PHASES = (PH_SWAPPER, PH_ADMIT, PH_PLAN, PH_DISPATCH, PH_MERGE, PH_READBACK,
          PH_FINISH, PH_GATHER, PH_SCATTER, PH_SWAP)


class _Recorded:
    """The annotation plus a libra-trace span of the same name."""

    __slots__ = ("ann", "tracer", "clock", "name", "args", "t0")

    def __init__(self, ann: TraceAnnotation, tracer: Tracer,
                 clock: Callable[[], float], name: str, args: dict):
        self.ann = ann
        self.tracer = tracer
        self.clock = clock
        self.name = name
        self.args = args

    def __enter__(self) -> "_Recorded":
        self.t0 = self.clock()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.ann.__exit__(*exc)
        self.tracer.span(TRACK_ENGINE, self.name, self.t0, self.clock(),
                         **self.args)


def phase(tracer: Tracer, clock: Callable[[], float], name: str,
          **args: Any):
    """A context manager for one phase: the bare annotation while
    libra-trace is off, the annotation plus a ``TRACK_ENGINE`` span while
    it is armed."""
    ann = TraceAnnotation(name, **args)
    if not tracer.enabled:
        return ann
    return _Recorded(ann, tracer, clock, name, args)
