"""The three flash kernels alone compile through Mosaic for the v5e at the
benchmark cells' shape and at shapes that take the other branches of the
schedule — more than one major block, a sequence the preferred tile does
not divide, an offset diagonal, no mask — so that a VMEM overflow or a
Mosaic refusal fails here and not on the chip. The chip is described, not
attached: nothing runs, so this gives no time and no result
(``on-chip-measurement`` guide, section 2.3).

The topology is described inside a fixture, never at import: only one
process at a time may hold the TPU compiler, and every pytest worker
imports this file. The other file of such compiles is
``tests/chipbench/test_chipbench_aot_v5e.py``; where the two land on
different workers without ``ALLOW_MULTIPLE_LIBTPU_LOAD`` one of them skips.
Each case takes a second or two.
"""

import functools
import math
import re

import pytest

TOPOLOGY = "v5e:2x2"


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no {TOPOLOGY} topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


_CASES = {
    # id: (q shape, seq_k, dtype, causal, q_offset[, kv heads, window])
    "gpt2m_cell": ((4, 1024, 16, 64), 1024, "bfloat16", True, 0),
    "chip_smoke_f32": ((8, 1024, 12, 64), 1024, "float32", True, 0),
    "past_the_resident_cap": ((1, 16384, 2, 128), 16384, "bfloat16", True, 0),
    "past_the_cap_f32": ((1, 8192, 2, 128), 8192, "float32", True, 0),
    "noncausal": ((8, 1024, 12, 64), 1024, "bfloat16", False, 0),
    "noncausal_long_wide": ((1, 8192, 1, 256), 8192, "bfloat16", False, 0),
    "preferred_tile_does_not_divide": ((1, 1536, 2, 64), 1536, "bfloat16",
                                       True, 0),
    "later_q_shard": ((2, 1024, 4, 64), 2048, "bfloat16", True, 1024),
    "offset_inside_a_tile": ((2, 1024, 4, 64), 2048, "bfloat16", True, 1000),
    "shorter_than_the_lanes": ((2, 64, 4, 64), 64, "bfloat16", True, 0),
    # the masked tiles as static strips (PR 29) at the shapes of the
    # gpt2m_* cells (16 sequences of 4 heads are their 64 programs) and of
    # laguna_xs2_8k_1chip's full and sliding layers
    "strips_gpt2m": ((16, 1024, 4, 64), 1024, "bfloat16", True, 0),
    "strips_laguna_full": ((2, 8192, 48, 128), 8192, "bfloat16", True, 0,
                           8, None),
    "strips_laguna_window": ((2, 8192, 64, 128), 8192, "bfloat16", True, 0,
                             8, 512),
    # the forward's 1024-row tiles have the window's edge on two of them
    # and cut the diagonal tile alone; the backward's 512 cut both
    "strips_window_no_multiple_of_the_tile": (
        (1, 4096, 8, 128), 4096, "bfloat16", True, 0, 2, 1536),
    "strips_later_q_shard_f32": ((1, 1024, 4, 128), 2048, "float32", True,
                                 1024),
    # smallthinker_16k_1chip's full and window layers: a group of 7 query
    # heads a key/value head, and a window of four forward tiles that spans
    # several majors at 16,384 positions
    "strips_smallthinker_full": ((1, 16384, 28, 128), 16384, "bfloat16",
                                 True, 0, 4, None),
    "strips_smallthinker_window": ((1, 16384, 28, 128), 16384, "bfloat16",
                                   True, 0, 4, 4096),
}


@pytest.mark.parametrize("case", _CASES)
def test_kernels_compile_for_v5e(one_chip, no_compile_cache, case):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_attention as pa

    q_shape, seq_k, dtype, causal, q_offset, *grouped = _CASES[case]
    batch, seq_q, heads, head_dim = q_shape
    kv_heads, window = grouped or (heads, None)
    q = jax.ShapeDtypeStruct(q_shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, seq_k, kv_heads, head_dim), dtype,
                              sharding=one_chip)
    attention = functools.partial(pa.flash_attention, causal=causal,
                                  q_offset=q_offset, window=window,
                                  interpret=False)
    compiled = jax.jit(jax.grad(
        lambda q, k, v: attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    for name in pa._kernel_names(window).values():
        assert f"%{name}" in hlo, name
    if case.startswith("strips_"):
        fwd, bwd = pa._tiles(seq_q, seq_k, head_dim, dtype, None, None,
                             window)
        trimmed = {
            kernel: pa.causal_schedule(
                seq_q, seq_k, q_offset, *tiles, causal, window)[kernel]
            ["trimmed"] for kernel, tiles in (
                ("flash_fwd", fwd), ("flash_bwd_dq", bwd),
                ("flash_bwd_dkv", bwd))}
        assert all(trimmed.values()), trimmed
    if case == "past_the_resident_cap":
        fwd, bwd = pa._tiles(seq_q, seq_k, head_dim, dtype, None, None)
        rows = pa._operand_row_bytes(head_dim, dtype)
        assert pa._major(seq_k, fwd[1], rows + 4 * fwd[0]) < seq_k
        assert pa._major(seq_k, bwd[1], rows) < seq_k


def test_block_diffusion_kernels_compile_for_v5e(one_chip, no_compile_cache):
    """The ``flash_bd_*`` calls at the shape of ``sdar_moe_8k_1chip`` — one
    sequence's 8,192 clean and 8,192 noisy rows, 32 query heads on 4 K/V
    heads of 128, blocks of 4 — with the K/V tile at the q tile's own index
    as two more operands: in strips, within the kernels' VMEM."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_attention as pa

    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), "bfloat16",
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), "bfloat16",
                              sharding=one_chip)
    attention = functools.partial(pa.flash_attention, causal=True,
                                  block_diffusion=4, interpret=False)
    hlo = jax.jit(jax.grad(
        lambda q, k, v: attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    for name in pa._kernel_names(None, block=4).values():
        assert f"%{name}" in hlo, name
    fwd, bwd = pa._tiles(8192, 8192, 128, "bfloat16", None, None)
    assert (fwd, bwd) == ((1024, 1024), (512, 512))
    for kernel, tiles in (("flash_fwd", fwd), ("flash_bwd_dq", bwd),
                          ("flash_bwd_dkv", bwd)):
        executed = pa.causal_schedule(16384, 16384, 0, *tiles, True,
                                      block_diffusion=4)[kernel]
        assert executed["trimmed"] and executed["pair_ratio"] < 1.05


# the kernels of kimi_linear_16k_1chip at the cell's shapes: latent
# attention's flash calls with q and k 192 wide and v 128 (nothing padded,
# the calls named apart), and the delta rule's chain, forward and transposed
def test_split_width_kernels_compile_for_v5e(one_chip, no_compile_cache):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_attention as pa

    qk = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(qk, qk, v).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    for name in pa._kernel_names(None, split=True).values():
        assert f"%{name}" in hlo, name
    assert "bf16[32,16384,256]" not in hlo      # v is not padded to q's lanes
    fwd, bwd = pa._tiles(16384, 16384, 192, jnp.bfloat16, None, None, None,
                         128)
    assert (fwd, bwd) == ((1024, 1024), (512, 512))
    trimmed = pa.causal_schedule(16384, 16384, 0, *bwd, True)
    assert trimmed["flash_bwd_dkv"]["trimmed"] > 0


def test_delta_rule_kernels_compile_for_v5e(one_chip, no_compile_cache):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.kda import kda

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qkv = placed((1, 16384, 32, 128), jnp.bfloat16)
    compiled = jax.jit(jax.grad(
        lambda *a: kda(*a, interpret=False)[0].astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(
            qkv, qkv, qkv, placed((1, 16384, 32, 128), jnp.float32),
            placed((1, 16384, 32), jnp.float32)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("kda_fwd", "kda_bwd"):
        assert f"%{name}" in hlo, name
    # the chunks' starting states are kept in the operands' type
    assert "bf16[256,32,128,128]" in hlo
    # and nothing of a chunk's operands is prepared outside the kernels: no
    # loop, no pair-by-pair tensor of the diagonal sub-blocks
    assert " while(" not in hlo
    assert not re.search(r"f32\[[\d,]*4,16,16,128\]", hlo)


@pytest.mark.parametrize("rows,d,width,held", [
    (2048, 2048, 512, 32), (2048, 2048, 768, 16), (512, 2304, 1024, 8),
    (3072, 2560, 768, 16)], ids=["laguna", "sdar", "kimi", "smallthinker"])
def test_a_slice_of_the_expert_loop_transposes_for_v5e(
        one_chip, no_compile_cache, rows, d, width, held):
    """``grouped_matmul_transposed`` at a slice of the four expert cells
    (SmallThinker's is ``slice_slots(98304, 16, 64)``, 3,072 slots):
    the weights' gradient takes its float32 running sum and returns it in
    the same buffer (four float32 matrices of a group in VMEM at once: 38
    MiB at Kimi-Linear's 2304 x 1024)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.grouped_matmul import (ROW_TILE,
                                                grouped_matmul_transposed)

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = jax.jit(functools.partial(
        grouped_matmul_transposed, interpret=False), donate_argnums=3).lower(
            placed((rows, d), jnp.bfloat16),
            placed((rows, width), jnp.bfloat16),
            placed((held, d, width), jnp.bfloat16),
            placed((held, d, width), jnp.float32),
            placed((rows // ROW_TILE,), jnp.int32),
            placed((), jnp.int32)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    dw = re.search(r"%expert_matmul_bwd_dw\S* = .*", hlo).group(0)
    assert "output_to_operand_aliasing={{}: (4, {})}" in dw, dw
    assert "%expert_matmul_bwd_dx" in hlo


@pytest.mark.parametrize("rows,d", [(2048, 2048), (2048, 2048), (512, 2304),
                                    (3072, 2560)],
                         ids=["laguna", "sdar", "kimi", "smallthinker"])
def test_a_slice_of_the_expert_loop_sums_by_token_for_v5e(
        one_chip, no_compile_cache, rows, d):
    """``moe_rows_add`` at a slice of the four expert cells, as the loop's
    forward pass calls it (bfloat16 rows, a weight a row) and as its
    backward pass does (float32 rows): one Mosaic call each, the carried
    sum ``[N, d / 128, 128]`` returned in its own buffer."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.grouped_matmul import moe_rows_add

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    total, token = placed((16384, d // 128, 128), jnp.float32), \
        placed((rows,), jnp.int32)
    for operands in ((placed((rows, d), jnp.bfloat16),
                      placed((rows, 1), jnp.float32)),
                     (placed((rows, d), jnp.float32), None)):
        hlo = jax.jit(
            lambda total, token, active, rows, scale: moe_rows_add(
                total, rows, token, scale, active, interpret=False),
            donate_argnums=0).lower(total, token, placed((), jnp.int32),
                                    *operands).compile().as_text()
        assert hlo.count('custom_call_target="tpu_custom_call"') == 1
        call = re.search(r"%moe_rows_add\S* = .*", hlo).group(0)
        last = 3 + (operands[1] is not None)   # the sum is the last operand
        assert f"output_to_operand_aliasing={{{{}}: ({last}, {{}})}}" \
            in call, call
        assert f"f32[16384,{d // 128},128]" in call.split(" custom-call(")[0]


def _expert_layer_hlo(one_chip, layer, d, routed: bool = False):
    """The compiled text of one ``ExpertLayer`` forward and backward on
    16,384 tokens of width ``d``, lowered for the v5e inside a ``shard_map``
    over that one chip, as a cell's step is (there the calls follow the
    platform the program is lowered for, not the CPU that lowers it);
    ``routed``: the router reads a tensor of its own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(list(one_chip.device_set), ("data",))

    def placed(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    x = placed((1, 16384, d), jnp.bfloat16, P("data"))
    params = jax.tree_util.tree_map(
        lambda p: placed(p.shape, p.dtype, P()),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"])
    grads = jax.grad(lambda p, x, r: jnp.square(layer.apply(
        {"params": p}, x, routed_by=r if routed else None).astype(
            jnp.float32)).sum(), argnums=(0, 1, 2))
    return jax.jit(jax.shard_map(
        grads, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P("data"), P("data")))).lower(
            params, x, x).compile().as_text()


def test_an_expert_layer_carries_its_sums_in_place_for_v5e(
        one_chip, no_compile_cache):
    """One ``ExpertLayer`` forward and backward at Laguna's shape: the sums
    by token are ``moe_rows_add``'s, one in each loop — no scatter into a
    token-sized float32 operand is left — and neither loop's body copies a
    carried sum."""
    from horovod_tpu.models.experts import ExpertLayer

    hlo = _expert_layer_hlo(one_chip, ExpertLayer(
        num_experts=256, experts_per_token=8, experts_held=(0, 32),
        width=512, shared_width=512, scaling=2.5), 2048)
    assert len(re.findall(r"%moe_rows_add\S* = ", hlo)) == 2
    assert not re.search(r" scatter\(f32\[16384,", hlo)
    assert not re.search(r"= f32\[16384,\S* scatter\(", hlo)
    bodies = set(re.findall(r"body=(%[\w.\-]+)", hlo))
    assert len(bodies) == 2, bodies
    for name in bodies:
        body = hlo[hlo.index(f"\n{name} ("):]
        body = body[:body.index("\n}\n")]
        assert "%moe_rows_add" in body
        assert not re.search(r"= f32\[16384,\S* copy\(", body), body


def test_a_relu_gated_layer_routed_by_its_own_tensor_compiles_for_v5e(
        one_chip, no_compile_cache):
    """The expert layer as ``smallthinker_16k_1chip`` calls it — 16 of 64
    experts of 2560 x 768 held, 6 a token, a ReLU gate, the router reading
    another tensor than the experts — in slices of ``slice_slots(98304, 16,
    64)``: the same two loops and kernels, a ``maximum`` where the SiLU's
    ``logistic`` was, and a gradient for the router's own input."""
    from horovod_tpu.models.experts import ExpertLayer, slice_slots

    assert slice_slots(16384 * 6, 16, 64) == (3072, 256)
    hlo = _expert_layer_hlo(one_chip, ExpertLayer(
        num_experts=64, experts_per_token=6, experts_held=(0, 16),
        width=768, shared_width=0, scoring="softmax", gate="relu"), 2560,
        routed=True)
    assert len(re.findall(r"%moe_rows_add\S* = ", hlo)) == 2
    assert len(set(re.findall(r"body=(%[\w.\-]+)", hlo))) == 2
    for name in ("expert_matmul_fwd", "expert_matmul_bwd_dx",
                 "expert_matmul_bwd_dw"):
        assert f"%{name}" in hlo, name
    assert "bf16[3072,768]" in hlo and "logistic" not in hlo


def test_a_delta_rule_layer_engages_its_kernels(one_chip, no_compile_cache):
    """``obs.kda.record_scan_program`` on a compiled toy step (one KDA
    layer's gradient, lowered for the v5e with no ``interpret`` given): no
    loop under ``hvd.kda.scan``, one call of each kernel, and no tensor as
    large as q moved between heads side by side and heads on an axis of
    their own; on a text that prepares the operands in loops, as before PR
    31, it counts them, and on one with the compiler's copy of a ``[T, H *
    d]`` tensor to ``[T, H, d]``, as before PR 36, that."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import obs
    from horovod_tpu.models.kimi_linear import KDAMixer

    layer = KDAMixer(num_heads=4, head_dim=128)
    x = jax.ShapeDtypeStruct((1, 256, 64), jnp.bfloat16, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x))
    text = jax.jit(jax.grad(lambda p, x: layer.apply(p, x).astype(
        jnp.float32).sum())).lower(params, x).compile().as_text()
    assert obs.kda.record_scan_program("toy", text) == (
        0, {"kda_fwd": 1, "kda_bwd": 1}, 0, 0)

    def gauge(family, **labels):
        return next(s["value"]
                    for s in obs.registry().snapshot()[family]["samples"]
                    if s["labels"] == labels)

    assert gauge("horovod_kda_scan_loops", program="toy") == 0
    assert gauge("horovod_kda_relayouts", program="toy") == 0
    assert gauge("horovod_kda_kernel_calls", program="toy",
                 kernel="kda_bwd") == 1
    looped = text + (
        '\n  %while.7 = (s32[], bf16[32,8,32,64,128]{4,3,2,1,0}) '
        'while(%tuple.3), condition=%cond, body=%body, '
        'metadata={op_name="jit(step)/hvd.kda.scan/while"}'
        '\n  %while.8 = (s32[], f32[8]{0}) while(%tuple.4), condition=%c, '
        'body=%b, metadata={op_name="jit(step)/hvd.optimizer/while"}\n')
    assert obs.kda.record_scan_program("looped", looped)[0] == 1
    # the toy's q is [1, 256, 4 * 128]: a copy of as much to heads in
    # sublanes, one under the scope back, and two that are none (a bitcast;
    # a copy of a smaller tensor)
    end = text.rindex("\n}")
    moved = text[:end] + (
        '\n  %copy.9 = f32[32,8,4,128]{3,2,1,0:T(8,128)} copy(%bitcast.1)'
        '\n  %reshape.9 = f32[1,256,512]{2,1,0:T(8,128)} reshape(%copy.9), '
        'metadata={op_name="jit(step)/hvd.kda/hvd.kda.scan/reshape"}'
        '\n  %bitcast.9 = f32[1,256,4,128]{3,2,1,0:T(8,128)} '
        'bitcast(%copy.9), metadata={op_name="jit(step)/hvd.kda/reshape"}'
        '\n  %copy.10 = f32[32,4,128]{2,1,0:T(8,128)} copy(%bitcast.2)'
        ) + text[end:]
    assert obs.kda.record_scan_program("moved", moved)[2] == 2
    assert gauge("horovod_kda_relayouts", program="moved") == 2


def test_a_recomputed_laguna_step_as_its_gauges_read_it(
        one_chip, no_compile_cache, monkeypatch):
    """``obs.kda.record_scan_program`` on a toy Laguna step's loss gradient
    (a full and a sliding layer, lowered for the v5e with the Mosaic
    kernels): the recomputed full layer keeps ``flash_fwd``'s outputs, the
    sliding one runs ``flash_win_fwd`` again — one rerun, counted since the
    pair ``flash_win_fwd`` / ``flash_win_bwd_dq`` is known. And no float32
    array of the compiled text whose last dimension is the vocabulary is
    larger than a block of ``lm_head_loss``'s rows."""
    import jax
    import jax.numpy as jnp

    from benchmarks._dp_step import lm_step_loss
    from horovod_tpu import obs
    from horovod_tpu.models import LagunaLM, head
    from horovod_tpu.ops import pallas_attention
    from test_laguna_model import TOY

    monkeypatch.setattr(head, "LOSS_ROWS", 128)
    monkeypatch.setattr(
        pallas_attention, "flash_attention",
        functools.partial(pallas_attention.flash_attention, interpret=False))
    vocab = 640
    model = LagunaLM.from_config(
        dict(TOY, num_hidden_layers=2, head_dim=128, vocab_size=vocab,
             mlp_layer_types=["dense", "dense"]), remat=True)
    assert model.layer_types == ("full_attention", "sliding_attention")
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((2, 8), jnp.int32))["params"])
    text = jax.jit(jax.grad(functools.partial(lm_step_loss, model))).lower(
        params, tokens).compile().as_text()
    calls = {kernel: len(re.findall(rf"%{kernel}(?:\.\d+)? = ", text))
             for kernel in ("flash_fwd", "flash_bwd_dq", "flash_win_fwd",
                            "flash_win_bwd_dq")}
    assert calls == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_win_fwd": 2,
                     "flash_win_bwd_dq": 1}
    assert obs.kda.record_scan_program("laguna_toy", text)[3] == 1
    sizes = {math.prod(map(int, dims.split(",")))
             for dims in re.findall(rf"\bf32\[((?:\d+,)*{vocab})\]", text)}
    assert max(sizes) == 128 * vocab < tokens.size * vocab
    assert {s["labels"]["program"]: s["value"] for s in
            obs.registry().snapshot()["horovod_remat_forward_reruns"][
                "samples"] if s["labels"]["program"].startswith("laguna_")} \
        == {"laguna_toy": 1}
