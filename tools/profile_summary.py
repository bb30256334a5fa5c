#!/usr/bin/env python
"""Summarize a captured device profile into a bottleneck attribution.

Where ResNet-50 bs32's step time goes on a chip is an open question — the
BN/bandwidth-bound hypothesis (docs/benchmarks.md) needs the device profile
(``HOROVOD_BENCH_PROFILE=<dir>`` in bench.py) to confirm or refute it.
This tool turns that captured XPlane into the answer without TensorBoard:

    python tools/profile_summary.py chiprun_out/resnet50_profile \
        [--top 25] [--out chiprun_out/resnet50_profile_summary.md]

It extracts xprof's ``hlo_stats`` table (self-time, bound_by, HBM
bandwidth, FLOP rate per HLO op — populated for TPU traces) with
``framework_op_stats`` as the fallback (host/CPU traces), aggregates
self-time by op category, and prints the top ops. The final line is one
JSON object so captures can be post-processed mechanically.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def _tables(obj):
    """Yield every gviz-style {cols, rows} table in a tool's JSON output
    (some tools return one table, some a list of tables)."""
    if isinstance(obj, dict) and "cols" in obj and "rows" in obj:
        yield obj
    elif isinstance(obj, list):
        for item in obj:
            yield from _tables(item)


def _rows_as_dicts(table):
    ids = [c["id"] for c in table["cols"]]
    for row in table.get("rows", []):
        cells = [c.get("v") if isinstance(c, dict) else None
                 for c in row["c"]]
        yield dict(zip(ids, cells))


def _pick_time_key(row) -> str | None:
    for key in ("total_self_time", "total_self_time_in_us",
                "self_time_us", "total_self_time_us"):
        if key in row:
            return key
    return None


def summarize(profile_dir: str, top: int = 25):
    """Returns (lines, summary_dict). Raises with a clear message when the
    dir holds no parseable profile."""
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(
            f"no *.xplane.pb under {profile_dir!r} — was the profile "
            f"captured (HOROVOD_BENCH_PROFILE)?")
    # jax.profiler writes each capture into its own timestamped
    # plugins/profile/<ts>/ session dir and never clears old ones; a retried
    # bench therefore leaves several sessions under one HOROVOD_BENCH_PROFILE
    # dir. Summarize only the NEWEST session — merging them would
    # double-count every op in the attribution artifact.
    by_session: dict[str, list[str]] = {}
    for p in paths:
        by_session.setdefault(os.path.dirname(p), []).append(p)
    if len(by_session) > 1:
        newest = max(by_session, key=lambda d: max(
            os.path.getmtime(p) for p in by_session[d]))
        skipped = sorted(set(by_session) - {newest})
        print(f"[profile_summary] {len(by_session)} capture sessions under "
              f"{profile_dir!r}; using newest {newest!r}, ignoring "
              f"{skipped}", file=sys.stderr)
        paths = sorted(by_session[newest])
    from xprof.convert import raw_to_tool_data as r2t

    rows = []
    tool_used = None
    for tool in ("hlo_stats", "framework_op_stats"):
        try:
            data, _ = r2t.xspace_to_tool_data(list(paths), tool, {})
        except Exception as exc:  # noqa: BLE001 - try the next tool
            print(f"[profile_summary] {tool} failed: {exc!r}",
                  file=sys.stderr)
            continue
        if isinstance(data, bytes):
            data = data.decode()
        try:
            obj = json.loads(data)
        except ValueError:
            continue
        for table in _tables(obj):
            cand = [row for row in _rows_as_dicts(table)
                    if _pick_time_key(row)]
            # an IDLE-only / all-zero table is no attribution at all —
            # keep looking (and ultimately fall back to raw trace events)
            if cand and any(float(row.get(_pick_time_key(row)) or 0) > 0
                            for row in cand):
                rows = cand
                tool_used = tool
                break
        if rows:
            break
    if not rows:
        # Final fallback: aggregate raw trace events (CPU traces populate
        # neither hlo_stats nor device op stats; TPU captures never reach
        # this branch). Wall duration by event name stands in for self
        # time — good enough to rank the hot ops.
        try:
            data, _ = r2t.xspace_to_tool_data(
                list(paths), "trace_viewer@", {"trace_viewer_options": {}})
            if isinstance(data, bytes):
                data = data.decode()
            events = json.loads(data).get("traceEvents", [])
        except Exception as exc:  # noqa: BLE001
            raise RuntimeError(
                "profile parsed but no op table carried self-time rows "
                f"(and trace_viewer fallback failed: {exc!r})") from exc
        agg: dict[str, dict] = {}
        for ev in events:
            if ev.get("ph") != "X" or not ev.get("dur"):
                continue
            name = str(ev.get("name", "?"))
            slot = agg.setdefault(
                name, {"operation": name, "type": "trace",
                       "total_self_time": 0.0, "occurrences": 0})
            slot["total_self_time"] += float(ev["dur"])
            slot["occurrences"] += 1
        rows = list(agg.values())
        tool_used = "trace_viewer"
    if not rows:
        raise RuntimeError(
            "profile parsed but no op table carried self-time rows "
            "(empty trace? idle-only capture?)")

    tkey = _pick_time_key(rows[0])
    total = sum(float(row.get(tkey) or 0.0) for row in rows)
    by_cat: dict[str, float] = {}
    for row in rows:
        cat = str(row.get("category") or row.get("type") or "?")
        by_cat[cat] = by_cat.get(cat, 0.0) + float(row.get(tkey) or 0.0)

    lines = [f"# profile summary: {profile_dir}",
             f"tool: {tool_used}; ops: {len(rows)}; "
             f"total self time: {total:.0f} us", "",
             "## self-time by category"]
    cats = sorted(by_cat.items(), key=lambda kv: -kv[1])
    for cat, us in cats:
        lines.append(f"  {cat:<32} {us:>12.0f} us  "
                     f"{100.0 * us / total if total else 0.0:5.1f}%")
    lines += ["", f"## top {top} ops by self time"]
    name_key = "hlo_op_name" if "hlo_op_name" in rows[0] else "operation"
    for row in sorted(rows, key=lambda r: -float(r.get(tkey) or 0.0))[:top]:
        extras = []
        for k, fmt in (("bound_by", "{}"), ("hbm_bw", "hbm={:.1f}GB/s"),
                       ("measured_memory_bw", "bw={:.1f}GB/s"),
                       ("model_flop_rate", "flops={:.2f}G/s"),
                       ("occurrences", "x{}")):
            v = row.get(k)
            if v not in (None, "", 0, "0"):
                try:
                    extras.append(fmt.format(float(v) if "{:" in fmt else v))
                except (ValueError, TypeError):
                    extras.append(f"{k}={v}")
        lines.append(
            f"  {float(row.get(tkey) or 0):>10.0f} us "
            f"{100.0 * float(row.get(tkey) or 0) / total if total else 0:5.1f}%"
            f"  {str(row.get('category') or row.get('type') or ''):<16}"
            f" {str(row.get(name_key) or '')[:60]:<60} {' '.join(extras)}")

    summary = {
        "profile_dir": profile_dir,
        "tool": tool_used,
        "total_self_time_us": round(total, 1),
        "by_category_us": {c: round(u, 1) for c, u in cats},
        "top_op": (sorted(rows, key=lambda r: -float(r.get(tkey) or 0.0))[0]
                   .get(name_key) if rows else None),
    }
    return lines, summary


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("profile_dir")
    parser.add_argument("--top", type=int, default=25)
    parser.add_argument("--out", help="also write the report to this file")
    args = parser.parse_args()
    lines, summary = summarize(args.profile_dir, args.top)
    report = "\n".join(lines)
    print(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(report + "\n")
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
