"""tools/profile_summary.py turns a captured XPlane profile into the
bottleneck attribution the benchmarks doc needs (round-3 verdict #3). On
TPU captures it reads xprof's hlo_stats (bound_by / HBM bandwidth per op);
this CPU test exercises the capture->parse->rank pipeline end to end via
the raw-trace fallback."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The e2e tier drives the REAL converter: tools/profile_summary.py's
# summarize() imports xprof.convert to turn the captured xplane.pb into
# tables. Without the xprof package (this image ships the jax profiler
# but not the converter), every capture summarizes to ModuleNotFoundError
# — the parsing contract is still fully covered by the stubbed-xprof
# fixture tier below, so the e2e tier gates loudly instead of failing on
# an environment it cannot run in.
_NEEDS_XPROF = pytest.mark.skipif(
    importlib.util.find_spec("xprof") is None,
    reason="xprof (the profile converter behind tools/profile_summary.py)"
           " is not installed in this image; the capture->parse pipeline "
           "cannot run — parsing itself is pinned by the stubbed-xprof "
           "fixture tier in this file")


@_NEEDS_XPROF
def test_profile_summary_end_to_end(tmp_path):
    prof_dir = str(tmp_path / "prof")
    capture = f"""
import os
os.environ.pop("JAX_PLATFORMS", None)
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
x = jnp.ones((512, 512))
f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
f(x).block_until_ready()
jax.profiler.start_trace({prof_dir!r})
for _ in range(3):
    x = f(x)
x.block_until_ready()
jax.profiler.stop_trace()
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    cap = subprocess.run([sys.executable, "-c", capture], env=env,
                         capture_output=True, text=True, timeout=300)
    assert cap.returncode == 0, cap.stderr

    out_md = str(tmp_path / "summary.md")
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "profile_summary.py"),
         prof_dir, "--top", "10", "--out", out_md],
        env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    lines = result.stdout.strip().splitlines()
    summary = json.loads(lines[-1])
    assert summary["total_self_time_us"] > 0
    # the dominant compute op must surface in the ranking
    assert any("dot" in ln for ln in lines), result.stdout
    with open(out_md) as f:
        assert "top 10 ops by self time" in f.read()


def test_profile_summary_missing_dir(tmp_path):
    env = dict(os.environ)
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "profile_summary.py"),
         str(tmp_path / "nope")],
        env=env, capture_output=True, text=True, timeout=120)
    assert result.returncode != 0
    assert "xplane.pb" in result.stderr


@_NEEDS_XPROF
def test_profile_summary_uses_newest_session_only(tmp_path):
    """A retried bench leaves several timestamped capture sessions under
    one profile dir; merging them would double-count every op in the
    attribution artifact — only the newest session may be summarized."""
    import time

    prof_dir = str(tmp_path / "prof")
    capture = f"""
import os, sys
os.environ.pop("JAX_PLATFORMS", None)
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
x = jnp.ones((256, 256))
f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
f(x).block_until_ready()
jax.profiler.start_trace({prof_dir!r})
for _ in range(int(sys.argv[1])):
    x = f(x)
x.block_until_ready()
jax.profiler.stop_trace()
"""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    for reps in ("2", "3"):
        cap = subprocess.run([sys.executable, "-c", capture, reps], env=env,
                             capture_output=True, text=True, timeout=300)
        assert cap.returncode == 0, cap.stderr
        time.sleep(1.1)  # distinct session timestamps/mtimes

    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "profile_summary.py"),
         prof_dir],
        env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    assert "capture sessions" in result.stderr, result.stderr
    summary = json.loads(result.stdout.strip().splitlines()[-1])
    assert summary["total_self_time_us"] > 0


# -- fixture-table unit tier ---------------------------------------------------
# The end-to-end tests above need a live JAX capture (slow, and the row
# shapes depend on whatever xprof version is installed); the tests below
# pin the PARSING contract itself — gviz table handling, the hlo_stats →
# framework_op_stats fallback, and the final-line-JSON shape — against
# small checked-in fixture tables and a stubbed xprof, so a regression in
# summarize() is attributable without a 300 s capture.


def _load_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_profile_summary_under_test",
        os.path.join(_ROOT, "tools", "profile_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gviz(cols, rows):
    """Minimal gviz-style {cols, rows} table (the xprof tool output
    shape summarize() parses)."""
    return {"cols": [{"id": c} for c in cols],
            "rows": [{"c": [{"v": v} if v is not None else None
                            for v in row]} for row in rows]}


_HLO_TABLE = _gviz(
    ["hlo_op_name", "category", "total_self_time", "bound_by",
     "occurrences"],
    [["fusion.1", "convolution", 700.0, "hbm", 3],
     ["all-reduce.2", "collective", 200.0, None, 1],
     ["copy.3", "data formatting", 100.0, None, 2]])

_FRAMEWORK_TABLE = _gviz(
    ["operation", "type", "total_self_time_in_us", "occurrences"],
    [["Conv2D", "Conv2D", 60.0, 4],
     ["MatMul", "MatMul", 40.0, 2]])


def _fake_xprof(monkeypatch, tool_data):
    """Install a stub xprof.convert.raw_to_tool_data whose
    xspace_to_tool_data serves canned per-tool JSON (or raises when the
    canned value is an exception)."""
    import types

    def xspace_to_tool_data(paths, tool, _params):
        value = tool_data[tool]
        if isinstance(value, Exception):
            raise value
        return json.dumps(value), None

    r2t = types.ModuleType("xprof.convert.raw_to_tool_data")
    r2t.xspace_to_tool_data = xspace_to_tool_data
    convert = types.ModuleType("xprof.convert")
    convert.raw_to_tool_data = r2t
    xprof = types.ModuleType("xprof")
    xprof.convert = convert
    monkeypatch.setitem(sys.modules, "xprof", xprof)
    monkeypatch.setitem(sys.modules, "xprof.convert", convert)
    monkeypatch.setitem(sys.modules, "xprof.convert.raw_to_tool_data", r2t)


def _capture_dir(tmp_path):
    session = tmp_path / "prof" / "plugins" / "profile" / "2026_08_03"
    session.mkdir(parents=True)
    (session / "host.xplane.pb").write_bytes(b"\x00")  # glob target only
    return str(tmp_path / "prof")


def test_gviz_table_helpers():
    tool = _load_tool()
    nested = [{"not": "a table"}, [_HLO_TABLE], _FRAMEWORK_TABLE]
    tables = list(tool._tables(nested))
    assert tables == [_HLO_TABLE, _FRAMEWORK_TABLE]
    rows = list(tool._rows_as_dicts(_HLO_TABLE))
    assert rows[0]["hlo_op_name"] == "fusion.1"
    assert rows[0]["total_self_time"] == 700.0
    assert rows[1]["bound_by"] is None  # null cells survive as None
    assert tool._pick_time_key(rows[0]) == "total_self_time"
    assert tool._pick_time_key({"name": "x"}) is None


def test_summarize_prefers_hlo_stats(tmp_path, monkeypatch):
    tool = _load_tool()
    _fake_xprof(monkeypatch, {"hlo_stats": _HLO_TABLE,
                              "framework_op_stats": _FRAMEWORK_TABLE})
    lines, summary = tool.summarize(_capture_dir(tmp_path), top=2)
    assert summary["tool"] == "hlo_stats"
    assert summary["total_self_time_us"] == 1000.0
    assert summary["by_category_us"] == {
        "convolution": 700.0, "collective": 200.0, "data formatting": 100.0}
    assert summary["top_op"] == "fusion.1"
    text = "\n".join(lines)
    assert "top 2 ops by self time" in text
    assert "fusion.1" in text and "hbm" in text  # bound_by surfaced


def test_summarize_falls_back_to_framework_op_stats(tmp_path, monkeypatch):
    """hlo_stats failing (CPU traces never populate it) or carrying only
    zero self-time rows must fall through to framework_op_stats."""
    tool = _load_tool()
    zero_hlo = _gviz(["hlo_op_name", "category", "total_self_time"],
                     [["idle", "idle", 0.0]])
    for hlo in (RuntimeError("no hlo_stats in this trace"), zero_hlo):
        _fake_xprof(monkeypatch, {"hlo_stats": hlo,
                                  "framework_op_stats": _FRAMEWORK_TABLE})
        _lines, summary = tool.summarize(_capture_dir(tmp_path), top=5)
        assert summary["tool"] == "framework_op_stats"
        assert summary["total_self_time_us"] == 100.0
        assert summary["top_op"] == "Conv2D"
        import shutil

        shutil.rmtree(tmp_path / "prof")


def test_summarize_missing_captures_raises(tmp_path):
    tool = _load_tool()
    with pytest.raises(FileNotFoundError, match="xplane.pb"):
        tool.summarize(str(tmp_path))


def test_main_final_line_json_contract(tmp_path, monkeypatch, capsys):
    """The LAST stdout line is one JSON object — the contract mechanical
    consumers (bench drivers, the docs table) parse; the human report
    precedes it and --out mirrors the report to a file."""
    tool = _load_tool()
    _fake_xprof(monkeypatch, {"hlo_stats": _HLO_TABLE,
                              "framework_op_stats": _FRAMEWORK_TABLE})
    out_md = str(tmp_path / "summary.md")
    monkeypatch.setattr(sys, "argv", [
        "profile_summary.py", _capture_dir(tmp_path), "--top", "1",
        "--out", out_md])
    tool.main()
    lines = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(lines[-1])
    assert summary["tool"] == "hlo_stats"
    assert summary["total_self_time_us"] == 1000.0
    assert summary["top_op"] == "fusion.1"
    assert set(summary) >= {"profile_dir", "tool", "total_self_time_us",
                            "by_category_us", "top_op"}
    with pytest.raises(ValueError):
        json.loads(lines[-2])  # the report body is NOT the JSON line
    with open(out_md) as f:
        assert "top 1 ops by self time" in f.read()


def test_bench_table_renders_captures(tmp_path):
    """tools/bench_table.py turns result lines into the docs table."""
    (tmp_path / "resnet50.json").write_text(json.dumps({
        "metric": "resnet50_synthetic_train_images_per_sec_per_device",
        "value": 1700.0, "unit": "img/s", "vs_baseline": 16.4,
        "live": True, "batch_size": 32, "mfu_pct": 10.8,
        "tflops_per_device": 21.2}) + "\n")
    (tmp_path / "junk.json").write_text("not json\n")
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_table.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    assert "ResNet-50, bs 32" in result.stdout
    assert "10.8%" in result.stdout
    empty = tmp_path / "none"
    empty.mkdir()
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "bench_table.py"),
         str(empty)],
        capture_output=True, text=True, timeout=120)
    assert result.returncode == 1
