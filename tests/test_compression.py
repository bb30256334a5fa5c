"""Quantized-allreduce (EQuARX) data plane: wire dtype, accuracy, policy.

The int8/fp8 codecs change the collective PROGRAM, not just its operand
dtype, so the suite pins three independent properties the way this repo
already pins wire dtypes (tests/test_spmd.py's bf16 scan):

* the lowered/compiled program really carries ``s8`` on the cross-replica
  collective operands (flat AND hierarchical — where ONLY the DCN hop may
  be quantized);
* flat-vs-quantized step results agree within the documented error bound
  (``codec.ERROR_BOUND`` x the across-ranks block absmax);
* the eager plane's per-dtype eligibility is deterministic and a world of
  one round-trips through the quantized program correctly.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu.ops import spmd
from horovod_tpu.ops.compression import Compression
from horovod_tpu.parallel import DATA_AXIS, data_parallel_mesh


def _shared_block_bound(xs: np.ndarray, codec, n: int) -> np.ndarray:
    """Per-element error bound: across-ranks block absmax x ERROR_BOUND,
    using the codec's own block geometry (``block_layout``)."""
    elems = xs.shape[1]
    block, padded = codec.block_layout(elems, n)
    absmax = np.zeros((n, padded), np.float32)
    absmax[:, :elems] = np.abs(xs)
    bmax = absmax.max(axis=0).reshape(-1, block).max(axis=1)
    return np.repeat(bmax * codec.ERROR_BOUND, block)[:elems]


@pytest.mark.parametrize("codec_name", ["int8", "fp8"])
def test_quantized_allreduce_matches_flat_within_bound(hvd, codec_name):
    codec = Compression.lookup(codec_name)
    mesh = data_parallel_mesh()
    rng = np.random.RandomState(0)
    # per-rank magnitudes spread over 2 decades: block scales must follow
    # the SHARED max, not each rank's own
    xs = (rng.randn(8, 1000).astype(np.float32)
          * np.logspace(-1, 1, 8)[:, None])
    x = jnp.asarray(xs.reshape(-1))

    def step(v):
        return (spmd.quantized_allreduce(v, DATA_AXIS, average=True,
                                         codec=codec),
                jax.lax.pmean(v, DATA_AXIS))

    quant, flat = jax.jit(shard_map(
        step, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=(P(), P()),
        check_vma=False))(x)
    err = np.abs(np.asarray(quant) - np.asarray(flat))
    bound = _shared_block_bound(xs, codec, 8)
    assert (err <= bound + 1e-7).all(), (
        f"{codec_name} error {err.max()} exceeds documented bound "
        f"{bound.max()}")
    # and the sum variant scales consistently
    s = jax.jit(shard_map(
        lambda v: spmd.quantized_allreduce(v, DATA_AXIS, average=False,
                                           codec=codec),
        mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
        check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(s), np.asarray(quant) * 8,
                               rtol=1e-6, atol=1e-5)


def test_quantized_allreduce_int_passthrough(hvd):
    """Non-float payloads must reduce exactly (eligibility, SPMD side)."""
    mesh = data_parallel_mesh()
    x = jnp.arange(8 * 16, dtype=jnp.int32)

    out = jax.jit(shard_map(
        lambda v: spmd.quantized_allreduce(v, DATA_AXIS, average=False),
        mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
        check_vma=False))(x)
    expect = np.asarray(x).reshape(8, 16).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_quantized_allreduce_empty_leaf(hvd):
    """A zero-element float leaf (empty parameter) must trace, not divide
    by a zero block size."""
    mesh = data_parallel_mesh()
    out = jax.jit(shard_map(
        lambda v: spmd.quantized_allreduce(v, DATA_AXIS, average=False),
        mesh=mesh, in_specs=P(None), out_specs=P(None),
        check_vma=False))(jnp.zeros((0,), jnp.float32))
    assert out.shape == (0,)


def test_int8_dp_step_wire_is_s8(hvd):
    """--int8-allreduce must COMPRESS THE WIRE: the compiled gradient
    reduction carries s8 collective operands (the quantized scatter/gather
    legs), the int8 twin of the bf16 pin in tests/test_spmd.py. Parameters
    stay close to the uncompressed step within the block-relative bound."""
    import optax

    from benchmarks._dp_step import make_dp_train_step
    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import ResNetBlock

    mesh = data_parallel_mesh()
    model = ResNet(stage_sizes=[1], num_filters=8, num_classes=10,
                   block_cls=ResNetBlock, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 16, 16, 3),
                          jnp.float32)
    y = jnp.arange(16, dtype=jnp.int32) % 10
    variables = model.init(jax.random.PRNGKey(0), x)
    params, batch_stats = variables["params"], variables["batch_stats"]

    opt_c = hvd_mod.DistributedOptimizer(optax.sgd(0.01),
                                         axis_name=DATA_AXIS,
                                         compression=Compression.int8)
    # the codec on the optimizer is enough: the builder takes no flag
    step_c = make_dp_train_step(model, opt_c, mesh, axis_name=DATA_AXIS,
                                donate=False)
    hlo = step_c.lower(params, opt_c.init(params), batch_stats, x,
                       y).compile().as_text()
    s8_collectives = re.findall(
        r"s8\[[^\]]*\][^\n]*?(all-to-all|all-gather)", hlo)
    assert s8_collectives, (
        "int8-compressed DP step compiled without an s8-operand "
        "collective — the quantized wire is not carrying the gradients")
    # the f32 psums that remain must be the BN-stat/loss pmeans and the
    # tiny per-block scale pmax, never a gradient-sized payload; assert
    # no f32 all-to-all exists (the quantized route owns the scatter leg)
    assert not re.search(r"f32\[[^\]]*\][^\n]*all-to-all", hlo)

    opt_p = hvd_mod.DistributedOptimizer(optax.sgd(0.01),
                                         axis_name=DATA_AXIS)
    step_p = make_dp_train_step(model, opt_p, mesh, axis_name=DATA_AXIS,
                                donate=False)
    pc, *_ = step_c(params, opt_c.init(params), batch_stats, x, y)
    pp, *_ = step_p(params, opt_p.init(params), batch_stats, x, y)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-2, atol=5e-3), pc, pp)


def test_hierarchical_quantized_only_dcn_hop(hvd):
    """The EQuARX design point: on the (dcn, ici) route the ICI
    reduce-scatter/all-gather legs stay FULL precision and only the DCN
    hop rides the s8 wire — and the s8 collectives' replica groups span
    the DCN axis, not ICI."""
    from horovod_tpu.parallel.hierarchical import (
        hierarchical_quantized_allreduce,
    )

    devices = jax.devices()[:8]
    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("dcn", "ici"))
    rng = np.random.RandomState(1)
    xs = rng.randn(8, 512).astype(np.float32)
    x = jnp.asarray(xs.reshape(-1))

    step = jax.jit(shard_map(
        lambda v: hierarchical_quantized_allreduce(v, "dcn", "ici",
                                                   average=True),
        mesh=mesh, in_specs=P(("dcn", "ici")), out_specs=P(),
        check_vma=False))
    hlo = step.lower(x).compile().as_text()

    # device id = 4*dcn + ici: ici groups are contiguous quads, dcn
    # groups are stride-4 pairs (as in test_spmd's hierarchical test).
    # Match INSTRUCTIONS (`= <shape(s)> <op>(`) — operand references like
    # `%reduce-scatter.1` inside fusion lines must not count.
    ICI = "{{0,1,2,3},{4,5,6,7}}"
    DCN = "{{0,4},{1,5},{2,6},{3,7}}"
    rs = [ln for ln in hlo.splitlines()
          if re.search(r"=[^=]*\sreduce-scatter(-start)?\(", ln)]
    assert rs and all(not re.search(r"=\s*\(?s8\[", ln) for ln in rs), (
        "ICI reduce-scatter leg must stay full precision", rs)
    assert any(ICI in ln for ln in rs), ("reduce-scatter not over ici", rs)
    s8_lines = [ln for ln in hlo.splitlines()
                if re.search(r"=\s*\(?[^=]*?s8\[[^\]]*\][^\n]*?"
                             r"(all-to-all|all-gather)(-start)?\(", ln)]
    assert s8_lines, "no s8 collective — the DCN hop is not quantized"
    assert all(DCN in ln for ln in s8_lines), (
        "an s8 collective spans a non-DCN group", s8_lines)

    # numerics: agrees with the flat mean within the bound of ONE
    # quantized hop over the 1/|ici| reduce-scattered shards
    flat = jax.jit(shard_map(
        lambda v: jax.lax.pmean(v, ("dcn", "ici")), mesh=mesh,
        in_specs=P(("dcn", "ici")), out_specs=P(), check_vma=False))(x)
    err = np.abs(np.asarray(step(x)) - np.asarray(flat)).max()
    # coarse but safe: global absmax of the ici-summed shards / 127
    shard_max = np.abs(xs.reshape(2, 4, 512).sum(axis=1)).max() * 4
    assert err <= shard_max * Compression.int8.ERROR_BOUND, err


def test_eager_int8_world_of_one(monkeypatch):
    """Eager-plane eligibility in a world of one: the negotiated codec
    rides the size-1 XLA data plane — f32 payloads take the quantized
    program (round-trip within bound), ineligible dtypes deterministically
    keep the exact full-precision wire."""
    monkeypatch.setenv("HOROVOD_DATA_PLANE", "xla")
    hvd_mod.init()
    try:
        from horovod_tpu.ops.engine import get_engine
        from horovod_tpu.ops.messages import DataType

        plane = get_engine()._plane
        assert plane is not None, "size-1 xla plane did not come up"
        # deterministic per-dtype eligibility mirrors supports()
        assert plane.supports_quantized(DataType.FLOAT32)
        assert not plane.supports_quantized(DataType.INT32)
        assert not plane.supports_quantized(DataType.BOOL)

        rng = np.random.RandomState(2)
        x = rng.randn(3000).astype(np.float32)
        out = hvd_mod.allreduce(x, average=True,
                                compression=Compression.int8)
        # world of one: the quantized program is a quantize->dequantize
        # round trip; block absmax/127 bounds it. The error must also be
        # NONZERO — an exact result means the codec was silently dropped
        # somewhere in negotiation (the native-negotiator regression this
        # test exists to catch), not that the wire is accurate.
        err = np.abs(np.asarray(out) - x)
        bound = _shared_block_bound(x[None, :], Compression.int8, 1)
        assert (err <= bound + 1e-7).all()
        assert err.max() > 0, (
            "int8 allreduce returned the input bit-exactly — the "
            "quantized program did not run")

        xi = np.arange(100, dtype=np.int32)
        outi = hvd_mod.allreduce(xi, average=False,
                                 compression=Compression.int8)
        np.testing.assert_array_equal(np.asarray(outi), xi)  # exact
    finally:
        hvd_mod.shutdown()


def test_codec_negotiation_and_fusion():
    """Control-plane rules (L1): codec mismatches become coordinator
    errors like dtype mismatches, and fusion never merges different
    codecs into one batch."""
    from horovod_tpu.ops.controller import Negotiator
    from horovod_tpu.ops.messages import (
        DataType,
        Request,
        RequestList,
        RequestType,
        ResponseType,
    )

    def req(rank, name, codec):
        return Request(request_rank=rank,
                       request_type=RequestType.ALLREDUCE,
                       tensor_name=name, tensor_type=DataType.FLOAT32,
                       tensor_shape=(4,), codec=codec)

    neg = Negotiator(2, fusion_threshold_bytes=1 << 20)
    neg.add_request_list(RequestList(rank=0, requests=[
        req(0, "a", "int8"), req(0, "b", "none"), req(0, "c", "int8"),
        req(0, "mix", "int8")]))
    neg.add_request_list(RequestList(rank=1, requests=[
        req(1, "a", "int8"), req(1, "b", "none"), req(1, "c", "int8"),
        req(1, "mix", "none")]))
    responses = neg.construct_response_list().responses

    by_names = {tuple(r.tensor_names): r for r in responses}
    # a+c share the int8 codec but b ("none") sits between them in
    # arrival order, so fusion must produce [a], [b], [c] — never a
    # mixed-codec batch
    for names, resp in by_names.items():
        if "mix" in names:
            assert resp.response_type == ResponseType.ERROR
            assert "compression codec" in resp.error_message.lower()
        else:
            codecs = {"a": "int8", "b": "none", "c": "int8"}
            assert len({codecs[n] for n in names}) == 1, names
            assert resp.tensor_codec == codecs[names[0]]


def test_native_negotiator_codec_stamping():
    """The C++ negotiation core predates the codec field; its Python
    wrapper must stamp negotiated codecs onto responses, keep fused
    batches codec-pure, and turn cross-rank mismatches into coordinator
    ERRORs — the same contract as the Python Negotiator."""
    from horovod_tpu import cc
    from horovod_tpu.ops.messages import (
        DataType,
        Request,
        RequestList,
        RequestType,
        ResponseType,
    )

    if not cc.available():
        pytest.skip("native core not built")

    def req(rank, name, codec):
        return Request(request_rank=rank,
                       request_type=RequestType.ALLREDUCE,
                       tensor_name=name, tensor_type=DataType.FLOAT32,
                       tensor_shape=(4,), codec=codec)

    neg = cc.NativeNegotiator(2, fusion_threshold_bytes=1 << 20)
    for rank in (0, 1):
        neg.add_request_list(RequestList(rank=rank, requests=[
            req(rank, "q", "int8"), req(rank, "p", "none"),
            req(rank, "mix", "int8" if rank == 0 else "none")]))
    responses = neg.construct_response_list().responses
    by_name = {}
    for r in responses:
        for n in r.tensor_names:
            by_name[n] = r
    assert by_name["q"].tensor_codec == "int8"
    assert by_name["p"].tensor_codec == "none"
    # never fused across codecs
    assert set(by_name["q"].tensor_names) != set(by_name["p"].tensor_names)
    assert by_name["mix"].response_type == ResponseType.ERROR
    assert "codec" in by_name["mix"].error_message.lower()


def test_compression_env_knob(monkeypatch):
    """HOROVOD_COMPRESSION resolves the default codec (core/config.py)."""
    from horovod_tpu.core.config import Config
    from horovod_tpu.optimizers import _resolve_compression

    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    assert Config.from_env().compression == "int8"
    assert _resolve_compression(None) is Compression.int8
    # explicit argument always wins over the env
    assert _resolve_compression(Compression.bf16) is Compression.bf16
    monkeypatch.delenv("HOROVOD_COMPRESSION")
    assert _resolve_compression(None) is Compression.none
    with pytest.raises(ValueError):
        Compression.lookup("int4")
