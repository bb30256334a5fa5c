"""Observability plane: unified metrics registry + cross-rank aggregation.

The subsystem docs live in docs/metrics.md; the pieces:

* :mod:`.registry` — process-local counters/gauges/mergeable histograms
  plus ``merge_snapshots`` (the pointwise world fold);
* :mod:`.httpd` — the shared stdlib loopback HTTP machinery (server
  thread lifecycle, route table, content-type handling) the metrics
  endpoint and the serving gateway both ride;
* :mod:`.exposition` — Prometheus text + JSON rendering, the loopback
  HTTP server (``HOROVOD_METRICS_PORT``) as a route set on it, and the
  ``parse_prometheus`` format-lint helper;
* :mod:`.bridge` — registry deltas as ``Timeline.counter`` tracks so the
  existing Chrome-tracing tooling keeps working;
* :mod:`.tracing` — the distributed-tracing half (docs/tracing.md):
  NTP-style clock alignment over the control wire and the coordinator's
  straggler attribution folded into :func:`straggler_report`;
* :mod:`.compiles` — the compile ledger: one ``jax.monitoring`` listener
  behind ``horovod_compiles_total`` / ``horovod_compile_seconds_total``
  and :func:`compile_events` (which program compiled, when), and
  :func:`record_exchange_collectives` (whether a compiled step's
  collectives can run beside compute);
* :mod:`.moe` — the expert layer's routing gauges, from the flax
  collection it sows (docs/laguna.md);
* :mod:`.bd` — a block-diffusion step's masked share and mean loss weight,
  from the flax collection ``bd_stats`` that ``models.sdar.SdarMoeLM`` sows.
* :mod:`.kda` — the delta-rule layers' decay and state gauges, likewise
  (docs/kimi-linear.md);
* :func:`metrics_snapshot` — the Python API: this process's families, or
  the world-aggregated view rank 0's coordinator assembled from the
  per-rank pushes riding the HMAC control wire.
"""

from __future__ import annotations

from typing import Dict, Optional

from .registry import (  # noqa: F401 - public surface
    Counter,
    Gauge,
    Histogram,
    Registry,
    merge_snapshots,
    registry,
)
from .bridge import TimelineBridge  # noqa: F401
from . import bd  # noqa: F401 - public surface (docs/sdar.md)
from . import compiles  # noqa: F401
from .compiles import (CompileEvent, compile_events,  # noqa: F401
                       record_exchange_collectives)
from . import exposition  # noqa: F401
from . import flightrec  # noqa: F401 - public surface (docs/blackbox.md)
from . import kda  # noqa: F401 - public surface (docs/kimi-linear.md)
from . import moe  # noqa: F401 - public surface (docs/laguna.md)
from . import tensorwatch  # noqa: F401 - public surface (docs/tensorwatch.md)
from .tensorwatch import tensor_report  # noqa: F401
from .tracing import (  # noqa: F401 - public surface (docs/tracing.md)
    ClockSync,
    build_straggler_report,
    straggler_report,
)


def _pull_world_store(client) -> Dict[int, dict]:
    """Fetch the coordinator's per-rank snapshot store over a transient
    ANONYMOUS control-wire connection — never the engine's cycle client,
    whose request lock a pull would contend with mid-negotiation (the
    "metrics must not perturb the cycle" contract)."""
    from ..runner.network import BasicClient

    pull = None
    try:
        pull = BasicClient(client._addr, secret=client._secret,
                           timeout_s=5.0, attempts=3)
        kind, store = pull.request(
            ("metrics_pull", getattr(client, "_world_id", "")))
        assert kind == "metrics", kind
        return dict(store)
    finally:
        if pull is not None:
            pull.close()


def metrics_snapshot(world: bool = False):
    """Live metrics of this job (docs/metrics.md).

    ``world=False``: this process's registry families, as a plain dict.

    ``world=True``: ``{"world": merged_families, "ranks": {rank:
    families}}`` — the merged view plus the per-rank snapshots it was
    folded from. On the rank hosting the Python controller service the
    per-rank section is the coordinator's live push store; other ranks
    pull that store over a transient control-wire connection. This
    process's own entry is always refreshed from its live registry, so
    local families are exact while remote ones are as fresh as the last
    publisher push (``HOROVOD_METRICS_INTERVAL_S``; publishers run only
    when the plane is opted into — port or interval set — so an
    un-opted-in job's world view carries this rank alone). Size-1 worlds
    and the native (C++) controller — whose fixed binary wire predates
    the metrics RPC — degrade to a world of this rank alone too."""
    local = registry().snapshot()
    if not world:
        return local
    rank = 0
    engine = None
    try:
        from .. import basics
        from ..ops import engine as _engine_mod

        if basics.is_initialized():
            rank = basics.rank()
        engine = _engine_mod._engine
    except Exception:  # noqa: BLE001 - pre-init callers get local-only
        pass
    store: Dict[int, dict] = {}
    if engine is not None and not getattr(engine, "_native_controller",
                                          False):
        service = getattr(engine, "_service", None)
        client = getattr(engine, "_client", None)
        if service is not None and hasattr(service, "metrics_store"):
            store = service.metrics_store()
        elif client is not None and hasattr(client, "_addr"):
            try:
                store = _pull_world_store(client)
            except Exception:  # noqa: BLE001 - degraded view, not a crash
                store = {}
    ranks = dict(store)
    ranks[rank] = local
    return {"world": merge_snapshots(ranks.values()), "ranks": ranks}


def health_report() -> dict:
    """One-shot fold of the live engine/controller state (docs/blackbox.md):
    the SAME snapshots a black-box incident dump embeds — one definition
    — served live, so a slow-but-alive world can be poked without
    killing it. Exposed over HTTP as ``GET /v1/introspect`` on rank 0's
    exposition server and on the serving gateway's co-hosted metrics
    routes (the PR 11 httpd)."""
    report: dict = {
        "initialized": False,
        "engine": None,
        "controller": None,
        "flightrec": flightrec.recorder().stats(),
    }
    engine = None
    try:
        from .. import basics
        from ..ops import engine as _engine_mod

        if basics.is_initialized():
            report.update(initialized=True, rank=basics.rank(),
                          size=basics.size(),
                          epoch=basics.world_epoch())
        engine = _engine_mod._engine
    except Exception:  # noqa: BLE001 - pre-init callers get the shell
        pass
    if engine is not None:
        try:
            report["engine"] = engine.state_snapshot()
        except Exception as exc:  # noqa: BLE001 - live poke, best-effort
            report["engine"] = {"error": str(exc)}
        service = getattr(engine, "_service", None)
        if service is not None and hasattr(service, "state_snapshot"):
            try:
                report["controller"] = service.state_snapshot()
            except Exception as exc:  # noqa: BLE001
                report["controller"] = {"error": str(exc)}
    return report


def world_snapshot_provider():
    """The exposition server's provider (``basics.init`` wires it up)."""
    return metrics_snapshot(world=True)


def metrics_port() -> Optional[int]:
    """Port of the live HTTP exposition server, or None when disabled."""
    return exposition.metrics_port()
