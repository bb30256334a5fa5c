"""Gradient numerics observatory tests (docs/tensorwatch.md).

Named past the 870 s tier-1 truncation point (ROADMAP note); the
``tensorwatch`` marker runs just this battery. Covers: the sampling
gate and its zero-allocation armed-idle path, the stats/SNR math
against NumPy references (numpy and jnp twins pinned equal), the
worst-K label cardinality cap, the evidence gate's block/admit/revert
loop down to the JSONL decision log, the merge_snapshots overflow-
bucket satellite, the report fold + tool contract, the disabled-path
HLO audit, and the 2-proc sampled-world bit-exactness acceptance on
both negotiation cores.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import tracemalloc

import numpy as np
import pytest

from horovod_tpu.obs import tensorwatch as tw

pytestmark = pytest.mark.tensorwatch


@pytest.fixture(autouse=True)
def _fresh_gate():
    tw.reset_for_tests()
    yield
    tw.reset_for_tests()


# -- sampling gate -------------------------------------------------------------


class TestSamplingGate:
    def test_interval_gating(self):
        watch = tw.TensorWatch(3)
        sampled = []
        for _ in range(9):
            watch.begin_batch()
            sampled.append(watch.sampling)
        assert sampled == [False, False, True] * 3
        assert watch.ordinal == 9

    def test_from_config_disabled_is_none(self):
        from horovod_tpu.core.config import Config

        assert tw.from_config(Config()) is None
        cfg = Config(tensorwatch_interval_steps=4)
        watch = tw.from_config(cfg, size=2, rank=1)
        assert watch is not None and watch.interval == 4

    def test_armed_idle_path_allocation_free(self):
        """The flightrec bar: an armed observatory's NON-sampled batches
        are integer arithmetic only — no allocation growth over
        thousands of batches (interval 0 builds no object at all, so
        the disabled path is one `is not None` check)."""
        watch = tw.TensorWatch(1 << 30)
        watch.begin_batch()  # warm the attribute paths
        tracemalloc.start()
        before = tracemalloc.take_snapshot()
        for _ in range(2000):
            watch.begin_batch()
        after = tracemalloc.take_snapshot()
        tracemalloc.stop()
        stats = after.compare_to(before, "filename")
        grown = sum(s.size_diff for s in stats if s.size_diff > 0)
        # tracemalloc bookkeeping itself can show a few hundred bytes
        assert grown < 4096, f"armed-idle begin_batch allocated {grown}B"

    def test_watch_codecs_from_config(self):
        from horovod_tpu.core.config import Config

        assert tw.watch_codecs(Config()) == ()
        assert tw.watch_codecs(Config(compression="int8")) == ("int8",)
        cfg = Config(compression="fp16",
                     autotune_codecs=("int8", "fp8"))
        # cast codecs carry no decode leg; consent candidates do
        assert tw.watch_codecs(cfg) == ("int8", "fp8")


# -- stats / SNR math ----------------------------------------------------------


class TestStatsMath:
    def test_np_stats_reference(self):
        arr = np.array([0.0, 1.0, -2.0, 0.5, 8.0], np.float32)
        st = tw._np_tensor_stats(arr)
        assert st["elems"] == 5
        assert st["nnz"] == 4
        assert st["absmax"] == 8.0
        assert abs(st["norm2"] - float((arr.astype(np.float64) ** 2)
                                       .sum())) < 1e-9
        # log2 exponents: 0, 1, -1, 3 -> bins at offsets 24, 25, 23, 27
        hist = st["log2_hist"]
        assert hist[24] == 1 and hist[25] == 1 and hist[23] == 1 \
            and hist[27] == 1
        assert sum(hist) == 4
        # top-1 entry (8.0) holds 64/69.25 of the energy; k=1 for all
        # three fractions at n=5
        expect = 64.0 / float((arr.astype(np.float64) ** 2).sum())
        for key in ("0.1", "1", "10"):
            assert abs(st["topk"][key] - expect) < 1e-12

    def test_snr_db_definition(self):
        assert tw.snr_db(0.0, 1.0) == 0.0
        assert tw.snr_db(1.0, 0.0) == tw.SNR_CAP_DB
        assert abs(tw.snr_db(100.0, 1.0) - 20.0) < 1e-12
        # the cap also bounds absurdly clean measurements
        assert tw.snr_db(1e300, 1e-300) == tw.SNR_CAP_DB
        # non-finite power (NaN batch, f32 overflow) reports 0 dB —
        # conservative for the gate, never NaN/Infinity in the JSON
        assert tw.snr_db(float("nan"), 1.0) == 0.0
        assert tw.snr_db(1.0, float("nan")) == 0.0
        assert tw.snr_db(float("inf"), 1.0) == 0.0

    def test_nonfinite_sample_skipped_not_leaked(self):
        """The observatory is PRE-sentry by design, so NaN gradients
        reach sampled measurements — the tensor is skipped and counted,
        never a NaN in the table/gauges (the RFC-JSON surfaces)."""
        watch = tw.TensorWatch(1)
        watch.begin_batch()
        bad = np.array([1.0, np.nan, 2.0], np.float32)
        good = np.array([1.0, -2.0, 3.0], np.float32)
        watch.observe_batch(["bad", "good"], [bad, good], [bad, good])
        report = watch.report()
        assert "bad" not in report["tensors"]
        row = report["tensors"]["good"]
        assert math.isfinite(row["norm2"])
        # and the full JSON document stays RFC-parseable
        json.loads(json.dumps(report))

    def test_int8_roundtrip_vs_numpy_reference(self):
        """The codec's roundtrip_error against an INDEPENDENT reference
        implementation of the block math (docs/compression.md)."""
        from horovod_tpu.ops.compression import Compression

        rng = np.random.RandomState(7)
        x = (rng.randn(3000) * np.logspace(-2, 1, 3000)).astype(
            np.float32)
        size = 2
        codec = Compression.int8
        sp, ep = codec.roundtrip_error(x, size)
        # reference: pad to the codec's block geometry, quantize each
        # block with scale = absmax/127 (multiply by the reciprocal,
        # like the wire), round, clip, dequantize
        block, padded = codec.block_layout(x.size, size)
        flat = np.concatenate([x, np.zeros(padded - x.size, np.float32)])
        blocks = flat.reshape(-1, block)
        absmax = np.abs(blocks).max(axis=1)
        scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(
            np.float32)
        q = np.clip(np.round(blocks * (1.0 / scale)[:, None]),
                    -127, 127).astype(np.int8)
        deq = q.astype(np.float32) * scale[:, None]
        ref_sp = float((blocks.astype(np.float64) ** 2).sum())
        ref_ep = float(((deq - blocks).astype(np.float64) ** 2).sum())
        assert abs(sp - ref_sp) < 1e-6 * max(ref_sp, 1)
        assert abs(ep - ref_ep) < 1e-6 * max(ref_ep, 1)
        # and the SNR lands in the plausible int8 regime
        assert 25.0 < tw.snr_db(sp, ep) < 60.0

    def test_jnp_twin_matches_numpy(self):
        """ops.spmd.codec_roundtrip (the compiled probe's body) pinned
        equal to Compression.roundtrip_error — one definition."""
        import jax

        from horovod_tpu.ops.compression import Compression
        from horovod_tpu.ops.spmd import codec_roundtrip

        rng = np.random.RandomState(3)
        x = rng.randn(2000).astype(np.float32)
        for codec in (Compression.int8, Compression.fp8):
            sp_n, ep_n = codec.roundtrip_error(x, 4)
            sp_j, ep_j = jax.jit(
                lambda v, c=codec: codec_roundtrip(v, c, 4))(x)
            snr_n = tw.snr_db(sp_n, ep_n)
            snr_j = tw.snr_db(float(sp_j), float(ep_j))
            assert abs(snr_n - snr_j) < 0.05, (codec.codec_name,
                                               snr_n, snr_j)

    def test_plane_probes_match_numpy(self):
        """XlaDataPlane.tensorwatch_stats / codec_snr (the device-side
        scalar probes) agree with the host measurement."""
        import types

        import jax.numpy as jnp

        from horovod_tpu.ops.xla_plane import XlaDataPlane

        plane = XlaDataPlane(types.SimpleNamespace(rank=0, size=1))
        x = np.random.RandomState(11).randn(1500).astype(np.float32)
        st = plane.tensorwatch_stats(jnp.asarray(x))
        ref = tw._np_tensor_stats(x)
        assert st["elems"] == ref["elems"]
        assert st["nnz"] == ref["nnz"]
        assert st["log2_hist"] == ref["log2_hist"]
        assert abs(st["norm2"] - ref["norm2"]) < 1e-4 * ref["norm2"]
        for key in ("0.1", "1", "10"):
            assert abs(st["topk"][key] - ref["topk"][key]) < 1e-5
        sp, ep = plane.codec_snr(jnp.asarray(x), "int8")
        ref_snr = tw._np_codec_snr(x, "int8", 1)
        assert abs(tw.snr_db(sp, ep) - ref_snr) < 0.05
        # the pre-reduce side's scalar-only probe (never the full stats
        # program twice): one norm², pinned to the numpy twin
        n2 = plane.tensorwatch_norm2(jnp.asarray(x))
        ref_n2 = tw._np_norm2(x)
        assert abs(n2 - ref_n2) < 1e-4 * ref_n2

    def test_quantized_codec_tags_cross_pinned(self):
        from horovod_tpu.ops.compression import Compression

        for tag in tw.QUANTIZED_CODECS:
            assert getattr(Compression.lookup(tag), "quantized", False)
        # and no quantized codec is missing from the copy
        for name in ("none", "fp16", "bf16", "int8", "fp8"):
            codec = Compression.lookup(name)
            if getattr(codec, "quantized", False):
                assert name in tw.QUANTIZED_CODECS


# -- cardinality cap -----------------------------------------------------------


class TestCardinality:
    def test_worst_k_label_cap(self):
        watch = tw.TensorWatch(1, worst_k=3)
        names = [f"tw.cap.{i}" for i in range(40)]
        arrs = [np.full(16, float(i + 1), np.float32)
                for i in range(40)]
        watch.begin_batch()
        assert watch.sampling
        watch.observe_batch(names, arrs, arrs, "none")
        # the full table keeps everything; labels stay bounded
        assert len(watch.report()["tensors"]) == 40
        assert len(watch._labeled) <= 4 * 3
        from horovod_tpu.obs.registry import registry

        fam = registry().snapshot()[tw.FAMILY_TENSOR_NORM2]
        ours = [s for s in fam["samples"]
                if s["labels"].get("tensor", "").startswith("tw.cap.")]
        assert 0 < len(ours) <= 4 * 3

    def test_retired_tensor_pins_to_zero(self):
        watch = tw.TensorWatch(1, worst_k=1)
        watch.begin_batch()
        watch.observe_batch(["tw.ret.a"],
                            [np.full(8, 2.0, np.float32)],
                            [np.full(8, 2.0, np.float32)], "none")
        watch.begin_batch()
        # a bigger tensor takes the single worst slot; 'a' retires to 0
        watch.observe_batch(["tw.ret.b"],
                            [np.full(8, 99.0, np.float32)],
                            [np.full(8, 99.0, np.float32)], "none")
        from horovod_tpu.obs.registry import registry

        fam = registry().snapshot()[tw.FAMILY_TENSOR_NORM2]
        values = {s["labels"]["tensor"]: s["value"]
                  for s in fam["samples"]}
        assert values["tw.ret.a"] == 0
        assert values["tw.ret.b"] > 0


# -- merge_snapshots overflow bucket (the PR satellite) ------------------------


class TestOverflowBucketFold:
    @staticmethod
    def _hist_snap(buckets):
        return {"m": {"type": "histogram", "help": "", "label_names": [],
                      "samples": [{"bounds": [1.0, 2.0],
                                   "buckets": list(buckets),
                                   "sum": float(sum(buckets)),
                                   "count": sum(buckets),
                                   "labels": {}}]}}

    def test_world_fold_preserves_overflow_distinct(self):
        """The +Inf overflow bucket (the slot past the last bound, whose
        quantiles deliberately read None since PR 6) must fold as its
        own slot — never blended into the finite buckets."""
        from horovod_tpu.obs.registry import merge_snapshots

        merged = merge_snapshots([self._hist_snap([1, 2, 7]),
                                  self._hist_snap([3, 4, 11])])
        sample = merged["m"]["samples"][0]
        assert sample["buckets"] == [4, 6, 18]
        assert len(sample["buckets"]) == len(sample["bounds"]) + 1

    def test_truncated_bucket_list_fails_loudly(self):
        """A malformed snapshot whose bucket list lost the overflow slot
        must fail the fold, not let zip() silently drop the counts."""
        from horovod_tpu.obs.registry import merge_snapshots

        with pytest.raises(ValueError, match="overflow"):
            merge_snapshots([self._hist_snap([1, 2, 7]),
                             self._hist_snap([3, 4])])

    def test_live_histogram_overflow_survives_fold(self):
        from horovod_tpu.obs.registry import Registry, merge_snapshots

        regs = [Registry(), Registry()]
        for i, reg in enumerate(regs):
            h = reg.histogram("tw_overflow_probe", "", buckets=(0.5,))
            h.observe(0.1)       # finite bucket
            h.observe(100.0 + i)  # overflow bucket
        merged = merge_snapshots([r.snapshot() for r in regs])
        sample = merged["tw_overflow_probe"]["samples"][0]
        assert sample["buckets"] == [2, 2]  # [<=0.5, +Inf] per-rank sums


# -- evidence gate -------------------------------------------------------------


class TestEvidenceGate:
    def test_certify_needs_full_window(self):
        gate = tw.EvidenceGate(20.0, 3)
        gate.observe("int8", 30.0)
        gate.observe("int8", 30.0)
        assert not gate.allows("int8")
        gate.observe("int8", 30.0)
        assert gate.allows("int8")
        record = gate.evidence_record("int8")
        assert record["certified"] and record["certified_at_sample"] == 3
        assert record["snr_db_window"] == [30.0, 30.0, 30.0]

    def test_floor_miss_resets_certification(self):
        gate = tw.EvidenceGate(20.0, 2)
        gate.observe("int8", 25.0)
        gate.observe("int8", 10.0)  # miss BEFORE any certification
        gate.observe("int8", 25.0)
        assert not gate.allows("int8")  # window holds [10, 25]
        # and a pre-certification dip never latches a collapse
        assert not gate.take_collapse("int8")
        gate.observe("int8", 25.0)
        assert gate.allows("int8")

    def test_collapse_latches_only_when_certified(self):
        gate = tw.EvidenceGate(20.0, 2)
        for _ in range(2):
            gate.observe("int8", 40.0)
        assert gate.allows("int8")
        gate.observe("int8", 5.0)
        assert not gate.allows("int8")
        assert gate.take_collapse("int8")
        assert not gate.take_collapse("int8")  # consumed exactly once

    def test_recertification_clears_stale_collapse(self):
        gate = tw.EvidenceGate(20.0, 2)
        for _ in range(2):
            gate.observe("int8", 40.0)
        gate.observe("int8", 5.0)  # collapse latched
        for _ in range(2):
            gate.observe("int8", 40.0)  # re-certifies
        assert gate.allows("int8")
        assert not gate.take_collapse("int8")

    def test_codec_knob_name_cross_pinned(self):
        from horovod_tpu.tune.policy import KNOB_CODEC

        assert tw.CODEC_KNOB == KNOB_CODEC

    def _policy(self, sink, gate):
        from horovod_tpu.tune.policy import KNOB_CODEC, Knob, \
            TuningPolicy

        return TuningPolicy(
            [Knob("fusion_threshold_bytes", (1,), 0, pinned=True),
             Knob(KNOB_CODEC, ("none", "int8"), 0)],
            window=1, cooldown=0, decision_sink=sink.append,
            propose_gate=tw.PolicyGate(gate))

    def test_policy_blocks_until_certified_then_admits(self):
        from horovod_tpu.tune.policy import KNOB_CODEC

        sink = []
        gate = tw.EvidenceGate(20.0, 3)
        policy = self._policy(sink, gate)
        for _ in range(8):
            decision = policy.observe(1000, 10)
            assert decision is None or decision.knob != KNOB_CODEC
        assert not any(r.get("knob") == KNOB_CODEC for r in sink)
        for _ in range(3):
            gate.observe("int8", 42.0)
        admitted = None
        for _ in range(8):
            decision = policy.observe(1000, 10)
            if decision is not None and decision.knob == KNOB_CODEC:
                admitted = decision
                break
        assert admitted is not None and admitted.value == "int8"
        record = [r for r in sink if r.get("knob") == KNOB_CODEC][-1]
        assert record["evidence"]["certified"]
        assert record["evidence"]["certified_at_sample"] >= 3

    def test_collapse_forces_audited_revert(self):
        from horovod_tpu.tune.policy import KNOB_CODEC

        sink = []
        gate = tw.EvidenceGate(20.0, 2)
        policy = self._policy(sink, gate)
        pg = tw.PolicyGate(gate)
        for _ in range(2):
            gate.observe("int8", 42.0)
        while True:  # drive until the codec move lands
            decision = policy.observe(1000, 10)
            if decision is not None and decision.knob == KNOB_CODEC:
                break
        assert policy.config()[KNOB_CODEC] == "int8"
        gate.observe("int8", 3.0)  # in-flight collapse
        forced = pg.maybe_revert(policy)
        assert forced is not None and forced.action == "revert"
        assert forced.config[KNOB_CODEC] == "none"
        assert policy.config()[KNOB_CODEC] == "none"
        assert policy.reverts == 1
        record = sink[-1]
        assert record["action"] == "revert" and "evidence" in record
        # consumed: no second forced revert, and the knob stays put
        assert pg.maybe_revert(policy) is None

    def test_no_gate_keeps_consent_only_behavior(self):
        """Observatory off = the PR 7 behavior byte-identically: the
        consented codec is proposed on plain consent."""
        from horovod_tpu.tune.policy import KNOB_CODEC, Knob, \
            TuningPolicy

        policy = TuningPolicy(
            [Knob("fusion_threshold_bytes", (1,), 0, pinned=True),
             Knob(KNOB_CODEC, ("none", "int8"), 0)],
            window=1, cooldown=0)
        moved = False
        for _ in range(4):
            decision = policy.observe(1000, 10)
            if decision is not None and decision.knob == KNOB_CODEC:
                moved = True
                break
        assert moved

    def test_autotuner_facade_wires_gate(self, monkeypatch, tmp_path):
        from horovod_tpu.core.config import (
            Config,
            HOROVOD_TENSORWATCH_INTERVAL,
        )
        from horovod_tpu.ops.autotuner import Autotuner

        # disarmed observatory: no gate object on the policy
        monkeypatch.delenv(HOROVOD_TENSORWATCH_INTERVAL, raising=False)
        tw.reset_for_tests()
        tuner = Autotuner(Config(autotune=True), extended=True)
        try:
            assert tuner._gate is None
            assert tuner._backend._propose_gate is None
        finally:
            tuner.close()
        # armed: the facade builds the PolicyGate from the env singleton
        monkeypatch.setenv(HOROVOD_TENSORWATCH_INTERVAL, "2")
        tw.reset_for_tests()
        tuner = Autotuner(
            Config(autotune=True, tensorwatch_interval_steps=2,
                   autotune_codecs=("int8",)), extended=True)
        try:
            assert tuner._gate is not None
            assert tuner._backend._propose_gate is tuner._gate
        finally:
            tuner.close()

    def test_engineless_host_degrades_to_consent_only(self, monkeypatch):
        """A non-member controller host (start_subset_service) runs no
        engine, so nothing in its process could ever feed the evidence
        gate — armed gating there would block the consented codec for
        the life of the job. It degrades to consent-only, warned once
        (the established degrade pattern)."""
        import logging

        from horovod_tpu.core.config import (
            Config,
            HOROVOD_TENSORWATCH_INTERVAL,
        )
        from horovod_tpu.core.logging import LOG
        from horovod_tpu.ops.autotuner import Autotuner

        class _Cap(logging.Handler):
            # LOG has propagate=False: caplog never sees its records
            # (the test_optimizer precedent) — attach directly
            def __init__(self):
                super().__init__(level=logging.WARNING)
                self.messages = []

            def emit(self, record):
                self.messages.append(record.getMessage())

        monkeypatch.setenv(HOROVOD_TENSORWATCH_INTERVAL, "2")
        tw.reset_for_tests()
        cap = _Cap()
        LOG.addHandler(cap)
        try:
            tuner = Autotuner(
                Config(autotune=True, tensorwatch_interval_steps=2,
                       autotune_codecs=("int8",)), extended=True,
                local_observatory=False)
            try:
                assert tuner._gate is None
                assert tuner._backend._propose_gate is None
                assert any("no engine to feed" in m
                           for m in cap.messages)
            finally:
                tuner.close()
        finally:
            LOG.removeHandler(cap)

    def test_from_config_gate_uses_resolved_knobs(self):
        """The gate certifies against the RESOLVED Config floor/window,
        not a second env read — a programmatic Config must not leave
        the watch's floor-miss counter and the gate's certification
        disagreeing about where the floor is."""
        from horovod_tpu.core.config import Config

        cfg = Config(tensorwatch_interval_steps=1,
                     tensorwatch_snr_floor_db=33.0,
                     tensorwatch_snr_window=2)
        watch = tw.from_config(cfg)
        gate = tw.evidence_gate()
        assert watch._gate is gate
        assert gate is not None
        assert gate.floor_db == 33.0 and gate.window == 2


# -- report fold + tool --------------------------------------------------------


def _fam(ftype, samples):
    return {"type": ftype, "help": "", "label_names": [],
            "samples": samples}


def _rank_families(rank, snr, prenorm):
    def g(value, **labels):
        return {"value": value, "labels": labels}

    return {
        tw.FAMILY_SAMPLES: _fam("counter", [g(5)]),
        tw.FAMILY_TENSOR_NORM2: _fam("gauge", [
            g(100.0, tensor="w1"), g(0, tensor="retired")]),
        tw.FAMILY_TENSOR_PRENORM2: _fam("gauge", [
            g(prenorm, tensor="w1")]),
        tw.FAMILY_TENSOR_SNR: _fam("gauge", [g(snr, tensor="w1")]),
        tw.FAMILY_CODEC_SNR: _fam("gauge", [g(snr, codec="int8")]),
        tw.FAMILY_TOPK: _fam("gauge", [
            g(0.4, k="0.1"), g(0.7, k="1"), g(0.95, k="10")]),
    }


class TestReportFold:
    def test_fold_spread_and_worst_snr(self):
        ranks = {0: _rank_families(0, 35.0, 10.0),
                 1: _rank_families(1, 31.5, 40.0)}
        report = tw.build_tensor_report(ranks)
        assert not report["degraded"]
        assert report["samples"] == 10
        row = report["tensors"][0]
        assert row["tensor"] == "w1"
        assert row["worst_snr_db"] == 31.5  # min across ranks
        assert abs(row["spread"] - 4.0) < 1e-9  # 40/10 skew
        assert report["codec_snr_db"]["int8"] == 31.5
        assert report["topk_mass"]["10"] == 0.95
        # zero-valued labels mean "left the worst set" and are skipped
        assert all(r["tensor"] != "retired" for r in report["tensors"])

    def test_fold_degrades_without_families(self):
        report = tw.build_tensor_report({0: {}})
        assert report["degraded"] and report["tensors"] == []

    def test_fold_loads_without_the_package(self):
        """The exec-fallback contract (the straggler_report precedent):
        tensorwatch.py's module level is stdlib-only, so the fold loads
        from the FILE on jax-less boxes."""
        import importlib.util

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "horovod_tpu", "obs", "tensorwatch.py")
        spec = importlib.util.spec_from_file_location("_tw_fold", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        report = mod.build_tensor_report(
            {0: _rank_families(0, 30.0, 1.0)})
        assert report["tensors"][0]["tensor"] == "w1"

    def test_tool_final_line_json_contract(self, tmp_path):
        doc = {"world": {},
               "ranks": {"0": _rank_families(0, 28.0, 4.0),
                         "1": _rank_families(1, 33.0, 1.0)}}
        snap = tmp_path / "snap.json"
        snap.write_text(json.dumps(doc))
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "tools",
                                          "tensorwatch_report.py"),
             str(snap)],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        assert report["tensors"][0]["tensor"] == "w1"
        assert report["tensors"][0]["worst_snr_db"] == 28.0
        assert "numerics observatory" in proc.stdout


# -- disabled-path HLO audit ---------------------------------------------------


class TestHLOAudit:
    def test_reduce_programs_unchanged_when_armed(self, monkeypatch):
        """The observatory's measurement programs are SEPARATE compiles:
        arming it must not add a single scalar output to the fused
        reduce or reduce+apply programs (the disabled-path overhead
        contract, acceptance-pinned)."""
        import types

        from horovod_tpu.core.config import HOROVOD_TENSORWATCH_INTERVAL
        from horovod_tpu.ops.fused_apply import ApplyRule
        from horovod_tpu.ops.xla_plane import XlaDataPlane

        # the text also records the line of THIS file each program was
        # traced from, which differs between the two calls below
        from tools.step_hlo import without_source_locations as bare

        monkeypatch.delenv(HOROVOD_TENSORWATCH_INTERVAL, raising=False)
        plane_off = XlaDataPlane(types.SimpleNamespace(rank=0, size=1))
        hlo_off = plane_off.reduce_donation_hlo(4096)
        apply_off = plane_off.reduce_apply_hlo(4096, ApplyRule("sgd", 0.1))
        monkeypatch.setenv(HOROVOD_TENSORWATCH_INTERVAL, "1")
        tw.reset_for_tests()
        plane_on = XlaDataPlane(types.SimpleNamespace(rank=0, size=1))
        assert bare(plane_on.reduce_donation_hlo(4096)) == bare(hlo_off)
        assert bare(plane_on.reduce_apply_hlo(
            4096, ApplyRule("sgd", 0.1))) == bare(apply_off)


# -- live size-1 engine --------------------------------------------------------


class TestLiveEngine:
    def test_size1_sampled_engine_and_v1_tensors(self, monkeypatch):
        from horovod_tpu.core.config import (
            HOROVOD_AUTOTUNE_CODECS,
            HOROVOD_TENSORWATCH_INTERVAL,
        )

        monkeypatch.setenv(HOROVOD_TENSORWATCH_INTERVAL, "1")
        monkeypatch.setenv(HOROVOD_AUTOTUNE_CODECS, "int8")
        tw.reset_for_tests()
        import horovod_tpu as hvd

        hvd.init()
        try:
            rng = np.random.RandomState(0)
            for step in range(3):
                hvd.allreduce(rng.randn(600).astype(np.float32),
                              name="tw.live", average=False)
            report = hvd.tensor_report()
            assert report["enabled"] and report["samples"] >= 1
            row = report["tensors"]["tw.live"]
            assert math.isfinite(row["snr_db"]["int8"])
            assert 0 < row["topk"]["0.1"] <= row["topk"]["1"] \
                <= row["topk"]["10"] <= 1.0
            assert sum(row["log2_hist"]) == row["nnz"]
            assert report["gate"] is not None
            from horovod_tpu.obs.exposition import metrics_routes

            routes = metrics_routes(lambda: {"world": {}, "ranks": {}})
            resp = routes[("GET", "/v1/tensors")](None, None, None)
            doc = json.loads(resp.body)
            assert doc["enabled"] and "tw.live" in doc["tensors"]
        finally:
            hvd.shutdown()


# -- 2-proc acceptance ---------------------------------------------------------


def _tw_world_fn(steps):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops.engine import get_engine

    hvd.init()
    rank = hvd.rank()
    outs = []
    for step in range(steps):
        for i in range(2):
            out = hvd.allreduce(
                (np.arange(600, dtype=np.float32) - 300.0)
                * float((rank + 1) * (i + 1) * (step + 1)) * 1e-3,
                average=False, name=f"tw.mp.{i}")
            outs.append(np.asarray(out).tolist())
    watch = get_engine()._tensorwatch
    report = watch.report() if watch is not None else None
    hvd.shutdown()
    return {"rank": rank, "results": outs, "report": report}


def _run_world(np_, steps=6, **env):
    from horovod_tpu.runner import run

    pins = {"HOROVOD_PLATFORM": "cpu", "HOROVOD_CYCLE_TIME": "2",
            "HOROVOD_NATIVE_CONTROLLER": "0", **env}
    saved = {k: os.environ.get(k) for k in pins}
    os.environ.update(pins)
    try:
        return run(_tw_world_fn, args=(steps,), np=np_,
                   timeout_s=180.0, start_timeout_s=120.0)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _assert_watch_world(watched, plain, n_ranks):
    by_rank_w = {r["rank"]: r for r in watched}
    by_rank_p = {r["rank"]: r for r in plain}
    for rank in range(n_ranks):
        # the acceptance pin: sampling is bit-exactness-NEUTRAL
        assert by_rank_w[rank]["results"] == by_rank_p[rank]["results"]
        assert by_rank_p[rank]["report"] is None
        report = by_rank_w[rank]["report"]
        assert report is not None and report["samples"] >= 1
        # interval 2 over one-batch-per-allreduce cycles: exactly every
        # second batch sampled (the gating pin), and every sampled
        # tensor carries finite SNR + a monotone coverage curve
        assert report["batches"] == 2 * report["samples"]
        assert report["tensors"], report
        for name, row in report["tensors"].items():
            assert name.startswith("tw.mp."), name
            assert math.isfinite(row["snr_db"]["int8"])
            assert row["snr_db"]["int8"] > 0
            assert 0 < row["topk"]["0.1"] <= row["topk"]["1"] \
                <= row["topk"]["10"] <= 1.0


def test_mp_sampled_world_bit_exact_python_core():
    watched = _run_world(2, HOROVOD_TENSORWATCH_INTERVAL_STEPS="2",
                         HOROVOD_AUTOTUNE_CODECS="int8",
                         HOROVOD_NATIVE_CORE="0")
    plain = _run_world(2, HOROVOD_TENSORWATCH_INTERVAL_STEPS="0",
                       HOROVOD_NATIVE_CORE="0")
    _assert_watch_world(watched, plain, 2)


def test_mp_sampled_world_bit_exact_native_core():
    from horovod_tpu import cc

    if not cc.available():
        pytest.skip(f"native core unavailable: {cc.load_error()}")
    watched = _run_world(2, HOROVOD_TENSORWATCH_INTERVAL_STEPS="2",
                         HOROVOD_AUTOTUNE_CODECS="int8",
                         HOROVOD_NATIVE_CORE="1")
    plain = _run_world(2, HOROVOD_TENSORWATCH_INTERVAL_STEPS="0",
                       HOROVOD_NATIVE_CORE="1")
    _assert_watch_world(watched, plain, 2)


@pytest.mark.slow
def test_dryrun_tensorwatch_subprocess():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c",
         "from __graft_entry__ import dryrun_tensorwatch; "
         "dryrun_tensorwatch()"],
        cwd=repo, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "tensorwatch OK" in proc.stderr
