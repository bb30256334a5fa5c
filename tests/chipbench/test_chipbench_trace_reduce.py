"""The trace reduction on small synthetic event lists with known busy and
idle time, overlapped and exposed collectives."""

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event

MS = 1e6  # ns


def test_merge_total_subtract():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.total(merged) == 6
    assert tr.subtract([(0, 10)], merged) == [(3, 5), (8, 10)]
    assert tr.subtract(merged, [(0, 10)]) == []
    assert tr.subtract(merged, []) == merged
    assert tr.subtract([(0, 4), (6, 9)], [(2, 7)]) == [(0, 2), (7, 9)]


@pytest.mark.parametrize("name,expected", [
    ("all-reduce.3", True), ("%all-reduce-start.1", True),
    ("all-reduce-done.1", True), ("reduce-scatter.7", True),
    ("collective-permute-start", True), ("fusion.12", False),
    ("all-reduce-fusion", False), ("convolution.4", False)])
def test_is_collective(name, expected):
    assert tr.is_collective(name) is expected


FLASH = ('%flash_attention.24 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, '
         'f32[64,1024,128]{2,1,0:T(8,128)}) custom-call(bf16[64,1024,64]'
         '{2,1,0} %bitcast.1), custom_call_target="tpu_custom_call"')
HEAD = ('%fusion.22 = (f32[1024,50257]{0,1:T(8,128)}, f32[1024,50257]{0,1}) '
        'fusion(f32[1024,50257]{0,1} %p, f32[] %custom-call.30), kind=kOutput')
START = ('%all-reduce-start.1 = f32[1024]{0} all-reduce-start(f32[1024]{0} '
         '%fusion.3), replica_groups={{0,1,2,3}}')


def test_operation_names_are_whole_hlo_text():
    """As a v5e trace names them (my chip run, PR 23)."""
    assert tr.parse_op(FLASH) == (
        "flash_attention.24", "custom-call",
        "(bf16[64,1024,64], f32[64,1024,128])")
    assert tr.is_mosaic(FLASH) and not tr.is_collective(FLASH)
    assert tr.parse_op(HEAD)[:2] == ("fusion.22", "fusion")
    # an operand called custom-call does not make a fusion a kernel
    assert not tr.is_mosaic(HEAD)
    assert tr.group_of(HEAD) == "fusion (f32[1024,50257], f32[1024,50257])"
    assert tr.group_of(FLASH.replace(".24", ".31")) == tr.group_of(FLASH)
    assert tr.parse_op(START)[:2] == ("all-reduce-start.1",
                                      "all-reduce-start")
    assert tr.is_collective(START)
    assert tr.parse_op("fusion.12") == ("fusion.12", "fusion", "")


def test_mosaic_time_and_groups():
    ops = [Event(FLASH, 0, 2 * MS), Event(HEAD, 2 * MS, 3 * MS),
           Event(FLASH.replace(".24", ".25"), 5 * MS, 2 * MS)]
    d = tr.reduce_device(ops, [], [])
    assert d["mosaic_s"] == pytest.approx(0.004)
    assert d["op_seconds"]["flash_attention.25"] == pytest.approx(0.002)
    assert d["group_seconds"][tr.group_of(FLASH)] == pytest.approx(0.004)
    top = tr.breakdown({"devices": [d]})["device_ops"]
    assert top[0] == [tr.group_of(FLASH), pytest.approx(0.004)]


def _two_steps():
    """Two 10 ms steps after a first one that is dropped. Each step: 6 ms
    of compute, a synchronous 2 ms all-reduce with nothing beside it
    (exposed), 1 ms of compute, 1 ms idle."""
    modules, ops = [], []
    for i in range(3):
        t = i * 10 * MS
        modules.append(Event("jit_train_step", t, 9 * MS))
        ops += [Event("fusion.1", t, 6 * MS),
                Event("all-reduce.1", t + 6 * MS, 2 * MS),
                Event("fusion.2", t + 8 * MS, 1 * MS)]
    return modules, ops


def test_busy_idle_and_exposed_synchronous_collective():
    modules, ops = _two_steps()
    spans = [Event("chipbench.read_loss", 18.5 * MS, 2 * MS),
             Event("chipbench.dispatch", 0, 1 * MS),
             Event("somebody.else", 19 * MS, 1 * MS)]
    d = tr.reduce_device(ops, modules, spans)
    assert d["steps"] == 2
    assert d["window_s"] == pytest.approx(0.019)     # 10 ms .. 29 ms
    assert d["busy_s"] == pytest.approx(0.018)
    assert d["compute_s"] == pytest.approx(0.014)
    assert d["collective_s"] == pytest.approx(0.004)
    assert d["exposed_collective_s"] == pytest.approx(0.004)
    assert d["op_seconds"]["fusion.1"] == pytest.approx(0.012)
    assert d["idle_gaps"] == [("host:read_loss", pytest.approx(0.001))]


def test_overlapped_asynchronous_collective_is_not_exposed():
    """all-reduce-start at 2 ms, compute until 8 ms, done waits 8..9 ms:
    in flight 2..9 ms, of which the start's own 0.1 ms and the last
    millisecond are exposed."""
    ops = [Event("fusion.1", 0, 2 * MS),
           Event("all-reduce-start.1", 2 * MS, 0.1 * MS),
           Event("fusion.2", 2.1 * MS, 5.9 * MS),
           Event("all-reduce-done.1", 8 * MS, 1 * MS),
           Event("fusion.3", 9 * MS, 1 * MS)]
    d = tr.reduce_device(ops, [], [])
    assert d["window_s"] == pytest.approx(0.010)
    assert d["busy_s"] == pytest.approx(0.010)
    assert d["collective_s"] == pytest.approx(0.007)
    assert d["exposed_collective_s"] == pytest.approx(0.0011)
    assert d["idle_gaps"] == []


def test_breakdown_has_at_most_ten_of_each():
    ops = [Event(f"fusion.{i}", 2 * i * MS, (1 + i / 100) * MS)
           for i in range(15)]
    reduced = {"devices": [tr.reduce_device(ops, [], [])]}
    b = tr.breakdown(reduced)
    assert len(b["idle_gaps"]) == 10
    assert b["device_ops"] == [["fusion", pytest.approx(
        sum(1 + i / 100 for i in range(15)) * 1e-3)]]
    assert all(gap[0] == "host:outside_any_span" for gap in b["idle_gaps"])


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        tr.steady_window([], [])
