"""Profiler + wall-clock hooks (docs/observability.md §Profiling).

Three layers, on one clock where it matters:

  maybe_trace(dir)  device-level: wraps a region in jax.profiler.trace
                    when `dir` is set, no-op otherwise.  The round code
                    is already annotated with jax.named_scope on the
                    local/mix/scatter/head-gather phases (and the u-step
                    update, `dfedpgp.u_update`), so the trace viewer
                    shows phase-labelled device timelines.
  span(name)        host-level: a `jax.profiler.TraceAnnotation`, so the
                    host's work lands in the same trace as the device's
                    ops, on the same clock.  The program's host spans are
                    all named `dfedpgp.host.<what>`.
  PhaseTimer        host-level: perf_counter phase buckets emitted as
                    plain gauges on the round/tick record, each phase
                    also a `dfedpgp.host.<phase>` span — so a record's
                    `t_<phase>_s` and a `--profile` trace show the same
                    intervals.  Cheap enough to leave on whenever
                    telemetry is on.

compile_count() is the process's running count of programs compiled or
loaded from the persistent compilation cache; a record's `compiles`
field is its difference over one round.

PhaseTimer measures HOST wall-clock.  For the number to mean device
time rather than dispatch, the phase must block on its outputs before
the bucket closes — `phase(name, block=True)` does that for you: assign
the phase's result to the yielded holder's `.out` and
jax.block_until_ready runs inside the bucket.  block=False (default)
keeps the seed behaviour for callers that block themselves or that
deliberately time dispatch.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Optional

import jax

# the program's host spans, as the trace names them
HOST_SPAN = "dfedpgp.host."


@contextmanager
def maybe_trace(profile_dir: Optional[str]):
    """jax.profiler.trace(profile_dir) when set, else a no-op — so
    `--profile <dir>` can gate tracing without duplicating the loop."""
    if not profile_dir:
        yield
        return
    with jax.profiler.trace(profile_dir):
        yield


def span(name: str):
    """A host span on the profiler's timeline: `with span(name): ...`.
    With no trace running it costs about a microsecond."""
    return jax.profiler.TraceAnnotation(name)


# `backend_compile_duration` is recorded once for each program JAX
# compiles or loads from the persistent cache, and not for a call that
# hits the in-memory cache; tracing alone, as eager `vmap` does, is not
# counted.  One listener for the process: JAX's registry is process-wide
# and keeps what it is given.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles: Optional[list] = None          # [count] once registered
_compiles_lock = threading.Lock()


def _on_compile(event, duration, **kwargs):
    if event == _COMPILE_EVENT:
        with _compiles_lock:
            _compiles[0] += 1


def compile_count() -> int:
    """Programs compiled or loaded from the persistent cache in this
    process since the first call (which registers the listener and
    returns 0).  The difference of two calls counts what ran between
    them."""
    global _compiles
    with _compiles_lock:
        if _compiles is None:
            _compiles = [0]
            jax.monitoring.register_event_duration_secs_listener(_on_compile)
        return _compiles[0]


class _PhaseResult:
    """The holder `phase()` yields: set `.out` to the phase's result and
    a block=True phase waits on it before the bucket closes."""
    __slots__ = ("out",)

    def __init__(self):
        self.out: Any = None


class PhaseTimer:
    """Named perf_counter buckets: accumulate seconds per phase, then
    `gauges()` renders them as `t_<phase>_s` record fields.

        pt = PhaseTimer()
        with pt.phase("round", block=True) as ph:
            state, metrics = step(state)
            ph.out = metrics          # block_until_ready before closing
        sink.emit(round_record(step=r, **pt.gauges(), ...))

    The block= form closes the dispatch-vs-device footgun: without it a
    jitted step returns immediately and the bucket times dispatch only.
    Each phase is also the host span `dfedpgp.host.<name>`.
    Re-entering a phase accumulates; `reset()` clears between emits."""

    def __init__(self):
        self._acc: dict = {}

    @contextmanager
    def phase(self, name: str, block: bool = False):
        holder = _PhaseResult()
        with span(HOST_SPAN + name):
            t0 = time.perf_counter()
            try:
                yield holder
            finally:
                if block and holder.out is not None:
                    jax.block_until_ready(holder.out)
                self._acc[name] = (self._acc.get(name, 0.0)
                                   + time.perf_counter() - t0)

    def seconds(self, name: str) -> float:
        return self._acc.get(name, 0.0)

    def gauges(self) -> dict:
        return {f"t_{k}_s": round(v, 6) for k, v in self._acc.items()}

    def reset(self) -> None:
        self._acc.clear()
