#!/usr/bin/env python
"""Render the measured-results markdown table from benchmark result lines.

    python tools/bench_table.py <results_dir>

Reads every ``*.json`` file in the directory (the last JSON line of each
is one benchmark's result line, e.g. ``python bench.py > dir/resnet50.json``)
and prints the docs/benchmarks.md measured table — config,
img|tokens/s/device, achieved TFLOP/s, MFU, and vs-reference ratio — so
landing a result in the docs is one copy-paste, not hand-transcription.
"""

from __future__ import annotations

import glob
import json
import os
import sys

_LABELS = {
    "serving_continuous_batching_speedup":
        "Serving gateway, continuous batching (batch {batch_max}) vs "
        "naive, peak rps at p99<={p99_budget_ms}ms",
    "resnet50": "ResNet-50, bs {batch_size}",
    "resnet101": "ResNet-101, bs {batch_size}",
    "vgg16": "VGG-16, bs {batch_size}",
    "inception3": "Inception V3, bs {batch_size}",
    "transformer_lm": "Transformer LM ({attention}, seq {seq_len}, "
                      "bs {batch_size})",
    "torch": "Torch front-end (hooks → engine → {data_plane} plane), "
             "bs {batch_size}",
}


def _label(rec: dict) -> str:
    model = rec.get("metric", "").split("_synthetic")[0]
    model = model.replace("_train_images_per_sec_per_device", "")
    model = model.replace("_tokens_per_sec_per_device", "")
    tmpl = _LABELS.get(rec.get("metric", ""), _LABELS.get(model,
                                                          model or "?"))
    try:
        return tmpl.format(**rec)
    except KeyError:
        return tmpl


def _render_serving(rec: dict) -> None:
    """The serving_bench.py final-line contract (docs/serving.md): the
    per-mode offered-QPS sweeps rendered as the docs/benchmarks.md
    serving table — p50/p99 latency next to achieved throughput, naive
    and batched side by side per offered level."""
    sweeps = rec["serving"]
    by_offered = {}
    for mode in ("naive", "batched"):
        for row in sweeps.get(mode, []):
            by_offered.setdefault(row["offered_qps"], {})[mode] = row
    print()
    print(f"Serving sweep (batch_max {rec.get('batch_max', '?')}, "
          f"{rec.get('clients', '?')} clients, p99 budget "
          f"{rec.get('p99_budget_ms', '?')} ms) — speedup "
          f"{rec.get('value', '?')}x:")
    print("| Offered QPS | naive rps | naive p50/p99 ms | batched rps |"
          " batched p50/p99 ms |")
    print("|---|---|---|---|---|")

    def _cell(row, key):
        return "—" if row is None or row.get(key) is None else row[key]

    for offered in sorted(by_offered):
        naive = by_offered[offered].get("naive")
        batched = by_offered[offered].get("batched")
        print(f"| {offered:g} "
              f"| {_cell(naive, 'achieved_rps')} "
              f"| {_cell(naive, 'p50_ms')} / {_cell(naive, 'p99_ms')} "
              f"| {_cell(batched, 'achieved_rps')} "
              f"| {_cell(batched, 'p50_ms')} / {_cell(batched, 'p99_ms')} "
              f"|")


def _render_hierarchy(rec: dict) -> None:
    """The controller_bench.py --scaling final-line contract
    (docs/hierarchy.md): simulated-world root-load rows rendered as the
    docs table — flat vs tree root messages and bytes per cycle, with
    the in-process Negotiator cycle rate alongside."""
    rows = rec["hierarchy"].get("rows", [])
    print()
    print(f"Negotiation-tree root load "
          f"({rec['hierarchy'].get('tensors_per_cycle', '?')} "
          f"tensors/cycle, islands = floor(sqrt(ranks))) — "
          f"{rec.get('value', '?')}x fewer root messages at "
          f"{rec.get('ranks', '?')} ranks:")
    print("| Ranks | Islands | flat msgs/cyc | tree msgs/cyc |"
          " flat B/cyc | tree B/cyc | flat cyc/s | tree cyc/s |")
    print("|---|---|---|---|---|---|---|---|")
    for row in rows:
        print(f"| {row.get('ranks', '—')} | {row.get('islands', '—')} "
              f"| {row.get('flat_root_msgs', '—')} "
              f"| {row.get('tree_root_msgs', '—')} "
              f"| {row.get('flat_root_bytes', '—')} "
              f"| {row.get('tree_root_bytes', '—')} "
              f"| {row.get('flat_cycles_per_s', '—')} "
              f"| {row.get('tree_cycles_per_s', '—')} |")


def main() -> None:
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    out_dir = sys.argv[1]
    rows = []
    serving_recs = []
    hier_recs = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        try:
            with open(path) as f:
                lines = [ln for ln in f.read().splitlines()
                         if ln.startswith("{")]
            rec = json.loads(lines[-1])
        except (OSError, ValueError, IndexError):
            continue
        if "metric" not in rec or "value" not in rec:
            continue  # onchip bench etc. have their own tables
        if isinstance(rec.get("serving"), dict):
            serving_recs.append(rec)
        if isinstance(rec.get("hierarchy"), dict):
            # root-load capture, not a per-device-rate row — render its
            # own table and keep it out of the throughput table
            hier_recs.append(rec)
            continue
        rows.append((os.path.basename(path), rec))
    if not rows and not hier_recs:
        print(f"(no parseable captures in {out_dir})", file=sys.stderr)
        sys.exit(1)
    if rows:
        print("| Config | per-device rate | TFLOP/s | MFU | vs reference |")
        print("|---|---|---|---|---|")
    for name, rec in rows:
        unit = rec.get("unit", "")
        tf = rec.get("tflops_per_device")
        mfu = rec.get("mfu_pct")
        vs = rec.get("vs_baseline")
        print(f"| {_label(rec)} | {rec['value']} {unit} | "
              f"{tf if tf is not None else '—'} | "
              f"{str(mfu) + '%' if mfu is not None else '—'} | "
              f"{str(vs) + 'x' if vs is not None else '—'} |")
    for rec in serving_recs:
        _render_serving(rec)
    for rec in hier_recs:
        _render_hierarchy(rec)


if __name__ == "__main__":
    main()
