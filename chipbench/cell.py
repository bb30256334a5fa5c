"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration under a traffic mix
on 1 or 4 chips. Everything that belongs to one configuration, one mix,
one per-layer metric or one cell's limits is a file of its own, found by
name under the directories that ``paths`` lists:

* the configuration: the ``file`` of its ``configs`` entry;
* its family (model code, shape functions, plain reference):
  ``<path>/families/<family>.py``, ``family`` being a key of the
  configuration;
* the traffic mix: ``<path>/traffic/<traffic>.json``;
* a per-layer metric's reader: ``<path>/readers/<metric>.py``;
* the limits of ``correct``: ``<path>/limits/<cell>.json``.

A later PR adds files and entries; nothing here names a cell, a
configuration or a metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _load_json(path: str) -> Any:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from None


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot import {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of the benchmark with everything found for it."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple   # metric entries this cell reports
    per_layer: tuple    # metric entries this cell reports
    spec: "Spec"

    @property
    def family(self):
        return self.spec.family(self.config["family"])

    @property
    def per_chip_batch(self) -> int:
        if self.traffic["global_batch"] % self.chips:
            raise SpecError(
                f"{self.name}: global batch {self.traffic['global_batch']} "
                f"does not split over {self.chips} chips")
        return self.traffic["global_batch"] // self.chips

    def limits(self) -> dict:
        return self.spec.limits(self.name)


class Spec:
    """``BENCHMARK.json`` (or a test's copy of its shape) and its files."""

    def __init__(self, path: Optional[str] = None):
        self.path = os.path.abspath(path or os.path.join(ROOT,
                                                         "BENCHMARK.json"))
        self.data = _load_json(self.path)
        for key in ("paths", "configs", "workloads", "end_to_end",
                    "per_layer", "run_seconds"):
            if key not in self.data:
                raise SpecError(f"{self.path} has no {key!r}")
        self._modules: dict = {}

    def _find(self, *parts: str) -> str:
        tried = []
        for base in self.data["paths"]:
            path = os.path.join(ROOT, base, *parts)
            if os.path.isfile(path):
                return path
            tried.append(path)
        raise SpecError(f"none of {tried} exists")

    def _module(self, kind: str, name: str):
        key = (kind, name)
        if key not in self._modules:
            self._modules[key] = _load_module(
                self._find(kind, f"{name}.py"),
                f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_"))
        return self._modules[key]

    def family(self, name: str):
        return self._module("families", name)

    def reader(self, metric: str):
        return self._module("readers", metric)

    def traffic(self, name: str) -> dict:
        return _load_json(self._find("traffic", f"{name}.json"))

    def limits(self, cell: str) -> dict:
        return _load_json(self._find("limits", f"{cell}.json"))

    def config(self, name: str) -> dict:
        for entry in self.data["configs"]:
            if entry["name"] == name:
                return _load_json(os.path.join(ROOT, entry["file"]))
        raise SpecError(f"no configuration {name!r} in {self.path}")

    def cell_names(self) -> list:
        return [w["name"] for w in self.data["workloads"]]

    def _metrics_of(self, section: str, cell: str, reported: set) -> tuple:
        """The entries of ``section`` that ``cell`` reports: those that list
        it under ``workloads``, or have no such key and (per-layer) move an
        end-to-end metric the cell reports."""
        out = []
        for m in self.data[section]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif section == "end_to_end" or m["moves"] in reported:
                out.append(m)
        return tuple(out)

    def cell(self, name: str) -> Cell:
        for w in self.data["workloads"]:
            if w["name"] == name:
                break
        else:
            raise SpecError(f"no workload {name!r} in {self.path}; it has "
                            f"{self.cell_names()}")
        end_to_end = self._metrics_of("end_to_end", name, set())
        per_layer = self._metrics_of(
            "per_layer", name, {m["name"] for m in end_to_end})
        return Cell(name=name, chips=int(w["chips"]),
                    config_name=w["config"], config=self.config(w["config"]),
                    traffic_name=w["traffic"],
                    traffic=self.traffic(w["traffic"]),
                    end_to_end=end_to_end, per_layer=per_layer, spec=self)


def peaks_of(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A kind that the table does
    not list is an error, never a default."""
    table = _load_json(os.path.join(ROOT, "chipbench", "peaks.json"))
    if device_kind not in table:
        raise SpecError(
            f"no peaks on record for device_kind {device_kind!r} (known: "
            f"{sorted(table)}); add it to chipbench/peaks.json with its "
            f"source")
    return table[device_kind]


def seed_keys(seed: int, n: int):
    """``n`` independent JAX keys from ``--seed``, which may be any whole
    number up to a little over 2**31 (more than 32 signed bits hold)."""
    import jax

    if seed < 0:
        raise SpecError(f"--seed must be >= 0, got {seed}")
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.split(key, n)
