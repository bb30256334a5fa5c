"""Compile ledger: which programs JAX compiled, when, and how long each
stage took (docs/metrics.md, docs/tracing.md).

On the compiled path the program has no host code between two steps, so
the one thing it can stall on by itself is a compilation: a shape that
changed, a cache that missed. ``hvd.init()`` installs one
``jax.monitoring`` duration listener (``hvd.shutdown()`` removes it) that
keeps

* ``horovod_compiles_total`` — programs handed to the backend compiler,
  persistent-cache hits included (the event wraps the cache lookup);
* ``horovod_compile_seconds_total{stage}`` — seconds per stage:
  ``trace`` (Python to jaxpr, a program's nested jitted functions counted
  once, inside it), ``lower`` (jaxpr to StableHLO),
  ``backend_compile`` (XLA, or the fetch that stood in for it) and
  ``cache_retrieval`` (the fetch alone, also counted inside
  ``backend_compile``);
* a bounded list of ``CompileEvent``s, :func:`compile_events`, a few for
  each program compiled: what an operator asks when a step stalls —
  *which* program compiled, *when*.

The listener runs only when JAX compiles; a steady training loop never
reaches it. The list outlives ``shutdown()``, so that it can be read
after the job.

:func:`record_exchange_collectives` reads a compiled step's text for the
other thing compilation decides: whether the gradient exchange can run
beside compute (``horovod_exchange_collectives{program}``,
``horovod_exchange_async_collectives{program}``).
"""

from __future__ import annotations

import collections
import re
import threading
import time
from typing import List, NamedTuple

from .registry import registry as _metrics

MAX_EVENTS = 512

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}

_COMPILES = _metrics().counter(
    "horovod_compiles_total",
    "Programs handed to the backend compiler, persistent-cache hits "
    "included")
_COMPILE_SECONDS = _metrics().counter(
    "horovod_compile_seconds_total",
    "Seconds JAX spent per compile stage (cache_retrieval is also inside "
    "backend_compile)", labels=("stage",))
_EXCHANGE_COLLECTIVES = _metrics().gauge(
    "horovod_exchange_collectives",
    "Collectives (all-reduce, reduce-scatter, all-gather) in a compiled "
    "step's scheduled text", labels=("program",))
_EXCHANGE_ASYNC_COLLECTIVES = _metrics().gauge(
    "horovod_exchange_async_collectives",
    "Those of horovod_exchange_collectives that compiled to a form compute "
    "runs beside: a -start/-done pair or an async collective fusion",
    labels=("program",))

# the opcode of a collective instruction in scheduled HLO text; a ``-done``
# closes a ``-start`` and is not counted again
_COLLECTIVE_OPCODE = re.compile(
    r" (?:all-reduce|reduce-scatter|all-gather)(-start)?\(")
_CHAIN_ID = re.compile(r'chain_id="(\d+)"')


class CompileEvent(NamedTuple):
    at: float        # time.perf_counter() when the stage ended
    fun_name: str    # "" where JAX gives none
    stage: str       # trace | lower | backend_compile | cache_retrieval
    seconds: float


class CompileLedger:
    """The listener and what it keeps; ``ledger()`` is the process's.

    Tracing a program traces every jitted function it calls first, each
    with an event of its own that ended inside the program's (hundreds
    for one training step), and every eager ``jnp`` call traces too.
    Trace events wait in ``_traces``: an enclosing one folds away those
    nested in it, the outermost are what the counter takes when the next
    program is lowered, and the list keeps the longest of them — the
    program's own, beside the eager calls before it and what its lowering
    rules trace. That makes a few entries for each program compiled."""

    def __init__(self, maxlen: int = MAX_EVENTS) -> None:
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(maxlen=maxlen)
        self._traces: collections.deque = collections.deque(maxlen=maxlen)
        self._installed = False

    def install(self) -> bool:
        """Register the listener; False if it already was."""
        from jax import monitoring

        with self._lock:
            if self._installed:
                return False
            monitoring.register_event_duration_secs_listener(self._on_event)
            self._installed = True
            return True

    def uninstall(self) -> None:
        """Remove the listener; the events stay readable."""
        from jax import monitoring

        with self._lock:
            if not self._installed:
                return
            monitoring.unregister_event_duration_listener(self._on_event)
            self._installed = False

    def events(self) -> List[CompileEvent]:
        with self._lock:
            return list(self._events) + list(self._traces)

    def _on_event(self, event: str, duration_secs: float, **kwargs) -> None:
        stage = _STAGE_OF_EVENT.get(event)
        if stage is None:
            return
        now = time.perf_counter()
        entry = CompileEvent(now, str(kwargs.get("fun_name", "")), stage,
                             float(duration_secs))
        with self._lock:
            if stage == "trace":
                while self._traces and \
                        self._traces[-1].at >= now - entry.seconds:
                    self._traces.pop()
                self._traces.append(entry)
                return
            settled = list(self._traces)
            self._traces.clear()
            if stage == "backend_compile" and self._events:
                # a cache fetch reports no fun_name of its own in this JAX
                # (jax/_src/compiler.py); it belongs to the program whose
                # backend_compile event closes round it
                last = self._events[-1]
                if last.stage == "cache_retrieval" and not last.fun_name:
                    self._events[-1] = last._replace(fun_name=entry.fun_name)
            if stage == "lower" and settled:
                self._events.append(max(settled, key=lambda e: e.seconds))
            self._events.append(entry)
        for e in (*settled, entry):
            _COMPILE_SECONDS.labels(stage=e.stage).inc(e.seconds)
        if stage == "backend_compile":
            _COMPILES.inc()


_ledger = CompileLedger()


def ledger() -> CompileLedger:
    return _ledger


def compiles_total() -> int:
    """``horovod_compiles_total`` as it stands."""
    return int(_COMPILES.value)


def record_exchange_collectives(program: str, hlo_text: str) -> tuple:
    """``(collectives, asynchronous ones)`` of a compiled step's text
    (``compiled.as_text()``), set on the two gauges under ``program``.

    A synchronous collective holds the core while it runs. Asynchronous,
    and counted as such, is one that compiled to a form in which compute
    runs beside it: a ``-start``/``-done`` pair or, from the TPU compiler,
    an *async collective fusion* — the collective cut into steps, each
    fused with a neighbouring operation, every step's copy of it carrying
    the same ``chain_id`` (counted once). An ``async_collective_name``
    alone is not counted: the v5e runs such an all-reduce as one
    operation with nothing beside it (PERF.md §6, PR 27)."""
    total = asynchronous = 0
    chains = set()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_OPCODE.search(line)
        if m is None:
            continue
        chain = _CHAIN_ID.search(line)
        if chain is not None:
            chains.add(chain.group(1))
            continue
        total += 1
        asynchronous += bool(m.group(1))
    total += len(chains)
    asynchronous += len(chains)
    _EXCHANGE_COLLECTIVES.labels(program=program).set(total)
    _EXCHANGE_ASYNC_COLLECTIVES.labels(program=program).set(asynchronous)
    return total, asynchronous


def compile_events() -> List[CompileEvent]:
    """The newest ``MAX_EVENTS`` compile-stage events of this process,
    oldest first, as ``(at, fun_name, stage, seconds)``; ``at`` is on
    ``time.perf_counter()``'s clock."""
    return _ledger.events()
