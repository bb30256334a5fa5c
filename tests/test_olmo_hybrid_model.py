"""``models.olmo_hybrid``: the layout ``from_config`` gives the published
pattern and the heads held, the kernels against the written-out backends
(the chunked rule under a decay a head against the token-by-token scan,
flash against dense attention) through the post-norm blocks, the two
shares of a layer's heads adding up to the uncut mixer, a recomputed block
keeping its mixer kernel's outputs, the scopes in a compiled step,
``gdn_stats`` and a compiled step's text as gauges, and the model through
the data-parallel step on two devices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import OlmoHybridLM, lm_loss, olmo_hybrid
from test_kimi_linear_model import gradient_program_counts

TOY = {
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "head_dim": 16,
    "num_attention_heads": 6, "num_key_value_heads": 6,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    "heads_held": {"first": 0, "count": 3},
}
# a layer of each kind (the tests that compile gradients run on these two)
SMALL = dict(TOY, num_hidden_layers=2,
             layer_types=["linear_attention", "full_attention"])


@pytest.fixture(scope="module")
def toy():
    model = OlmoHybridLM.from_config(SMALL, dtype=jnp.float32)
    # a chunk and a half of the delta rule
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 256)
    # the parameters know no backend: the written-out ones trace faster
    params = jax.jit(model.clone(rule="recurrent", attention="dense").init)(
        jax.random.PRNGKey(1), tokens)["params"]
    return model, params, tokens


def test_from_config_lays_out_the_published_pattern_and_the_heads_held():
    model = OlmoHybridLM.from_config(TOY, dtype=jnp.float32)
    assert model.layer_types == ("linear_attention",) * 3 \
        + ("full_attention",)
    assert (model.linear_heads, model.num_heads, model.num_kv_heads) \
        == (3, 3, 3)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            jnp.zeros((2, 96), jnp.int32))["params"]
    mixer = params["block_0"]["gdn"]
    assert mixer["query"]["kernel"].shape == (64, 3 * 16)
    assert mixer["value"]["kernel"].shape == (64, 3 * 32)
    assert mixer["gate"]["kernel"].shape == (64, 3 * 32)    # as wide as v
    assert mixer["conv_k"].shape == (4, 48) and mixer["conv_v"].shape \
        == (4, 96)
    assert mixer["decay"]["kernel"].shape == (64, 3)    # one a head
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (3,)
    assert mixer["out_norm"]["scale"].shape == (32,)
    assert mixer["out"]["kernel"].shape == (96, 64)
    full = params["block_3"]["attn"]
    assert full["query"]["kernel"].shape == full["key"]["kernel"].shape \
        == (64, 3 * 16)
    assert full["q_norm"]["scale"].shape == (48,)   # the channels held
    assert full["out"]["kernel"].shape == (48, 64)
    assert not [path for path, _ in
                jax.tree_util.tree_leaves_with_path(params)
                if "bias" in jax.tree_util.keystr(path)
                and "dt_bias" not in jax.tree_util.keystr(path)]
    whole = OlmoHybridLM.from_config(
        {k: v for k, v in dict(TOY, num_hidden_layers=8).items()
         if k != "heads_held"})
    assert whole.linear_heads == 6 and len(whole.layer_types) == 8
    # without a head_dim key: the hidden size over the published heads
    assert OlmoHybridLM.from_config(
        {k: v for k, v in dict(TOY, hidden_size=96).items()
         if k != "head_dim"}).head_dim == 16
    with pytest.raises(ValueError, match="head counts of their own"):
        OlmoHybridLM.from_config(dict(TOY, num_key_value_heads=2))
    with pytest.raises(ValueError, match="no part of 6 heads"):
        OlmoHybridLM.from_config(dict(TOY, heads_held={"first": 4,
                                                       "count": 3}))
    with pytest.raises(ValueError, match="layer_types"):
        model.clone(layer_types=("sliding_attention",)).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))


def test_kernels_against_the_written_out_backends(toy):
    """Loss and every gradient leaf: ``gdn_fwd`` / ``gdn_bwd`` (3 heads, a
    decay a head, keys 16 and values 32, beta up to 2) against
    ``kda_recurrent``, flash against dense attention, each block
    recomputed against none."""
    model, params, tokens = toy
    assert (model.rule, model.attention) == ("chunked", "flash")

    def value_and_grad(m):
        return jax.jit(jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, tokens), tokens)))(params)

    with jax.default_matmul_precision("highest"):
        loss, grad = value_and_grad(model.clone(remat=True))
        want_loss, want_grad = value_and_grad(
            model.clone(rule="recurrent", attention="dense"))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grad),
            jax.tree_util.tree_leaves(want_grad)):
        assert float(jnp.linalg.norm(got - want)) \
            < 1e-4 * float(jnp.linalg.norm(want)), \
            jax.tree_util.keystr(path)
    for wrong in ({"rule": "scan"}, {"attention": "ring"}):
        with pytest.raises(ValueError, match="must be one of"):
            model.clone(**wrong).apply({"params": params}, tokens)


def _share(params, first, count, *, by_head, by_row=("out",)):
    """``params`` of a mixer cut to ``count`` heads from ``first``: the
    columns (the rows of ``by_row``'s kernels) of every leaf but the
    ``shared`` ones, whose last (first) axis holds all the heads side by
    side."""
    def cut(path, x):
        name = jax.tree_util.keystr(path)
        if not any(f"'{n}'" in name for n in by_head + by_row):
            return x
        axis = 0 if any(f"'{n}'" in name for n in by_row) else x.ndim - 1
        width = x.shape[axis] // 6
        return jax.lax.slice_in_dim(x, first * width,
                                    (first + count) * width, axis=axis)

    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.mark.parametrize("kind", ["linear_attention", "full_attention"])
def test_the_two_shares_add_up_to_the_whole_mixer(kind):
    """This model's cut: two chips share a layer's heads. With heads 0-2
    and 3-5 of 6 held in turn, the delta-rule mixers' outputs (before the
    post-norm) add up to the uncut mixer's; and the full mixers' do from
    the normalised q and k on — their q/k norms take the mean square over
    the channels *held*, so here the second half of the uncut q and k
    projections is the first negated: either half's mean square is then
    the whole's, and the shares normalise as the uncut layer does."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 80, 64)), jnp.float32)

    # the written-out backends: which heads a chip holds is the model's
    # arithmetic, not a kernel's
    def gdn(heads):
        return olmo_hybrid.GatedDeltaMixer(
            num_heads=heads, key_dim=16, value_dim=32, dtype=jnp.float32,
            rule="recurrent")

    def attn(heads):
        return olmo_hybrid.NormedAttention(
            num_heads=heads, num_kv_heads=heads, head_dim=16,
            dtype=jnp.float32, attention="dense")

    layer, by_head = {
        "linear_attention": (gdn, (
            "query", "key", "value", "gate", "decay", "beta", "conv_q",
            "conv_k", "conv_v", "A_log", "dt_bias")),
        "full_attention": (attn, ("query", "key", "value", "q_norm",
                                  "k_norm"))}[kind]
    params = jax.jit(layer(6).init)(jax.random.PRNGKey(4), x)["params"]
    params = jax.tree_util.tree_map(
        lambda p: 8.0 * p if p.ndim == 2 else p, params)
    if layer is attn:
        for name in ("query", "key"):
            half = params[name]["kernel"][:, :48]
            params[name]["kernel"] = jnp.concatenate([half, -half], 1)
        for name in ("q_norm", "k_norm"):
            params[name]["scale"] = jnp.asarray(
                rng.uniform(0.5, 1.5, 96), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(layer(6).apply)({"params": params}, x)
        parts = [jax.jit(layer(3).apply)({"params": _share(
            params, first, 3, by_head=by_head)}, x) for first in (0, 3)]
    assert min(float(jnp.abs(p).max()) for p in parts) > 0.05
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 0.05
    np.testing.assert_allclose(parts[0] + parts[1], want, rtol=1e-4,
                               atol=1e-5)


def test_a_recomputed_block_keeps_its_mixer_kernels_outputs():
    """Every block recomputed, the forward kernels still run once a
    layer (``parts.keep_policy`` over the names the two forward
    rules set); under the default policy they would run twice."""
    model = OlmoHybridLM.from_config(TOY, dtype=jnp.float32, remat=True)
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            tokens)["params"]
    counts = gradient_program_counts(model, params, tokens)
    assert {k: v for k, v in counts.items() if k != "projections"} == {
        "gdn_fwd": 3, "gdn_bwd": 3, "flash_fwd": 1, "flash_bwd_dq": 1,
        "flash_bwd_dkv": 1}
    plain = gradient_program_counts(model.clone(remat=False), params, tokens)
    # 7 + 4 projections forward, their two transposes, and the recomputed
    # block's again
    assert plain["projections"] == 3 * (3 * 7 + 4)
    assert counts["projections"] == plain["projections"] + 3 * 7 + 4


def test_nothing_sees_the_future(toy):
    """Change the last token: no logit before it moves, through the
    convolution, the delta rule and attention alike."""
    model, params, tokens = toy
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % 256)
    delta = np.abs(np.asarray(model.apply({"params": params}, tokens)
                              - model.apply({"params": params}, moved)))
    assert delta[:, :-1].max() == 0.0 and delta[:, -1].max() > 0


def test_gdn_stats_become_gauges(toy):
    from horovod_tpu import obs

    model, params, tokens = toy
    _, state = model.apply({"params": params}, tokens,
                           mutable=["gdn_stats"])
    published = obs.kda.publish_gdn(state["gdn_stats"])
    assert sorted(published) == ["block_0/gdn"]
    stats = published["block_0/gdn"]
    assert 0.2 < stats["mean_decay"] < 1.0
    assert 0.0 < stats["state_abs_max"] < 10.0
    # beta = 2 sigmoid(.) of seeded projections: about half above 1
    assert 0.2 < stats["beta_above_one_share"] < 0.8
    snapshot = obs.registry().snapshot()
    for family, key in (
            ("horovod_gdn_mean_decay", "mean_decay"),
            ("horovod_gdn_state_abs_max", "state_abs_max"),
            ("horovod_gdn_beta_above_one_share", "beta_above_one_share")):
        read = {s["labels"]["layer"]: s["value"]
                for s in snapshot[family]["samples"]}
        assert read["block_0/gdn"] == pytest.approx(stats[key])
    # with beta held to 1 no write is in the negative-eigenvalue regime
    _, tame = model.clone(allow_neg_eigval=False).apply(
        {"params": params}, tokens, mutable=["gdn_stats"])
    assert obs.kda.publish_gdn(tame["gdn_stats"])["block_0/gdn"][
        "beta_above_one_share"] == 0.0
    # a training step does not carry the collection
    assert "gdn_stats" not in model.apply({"params": params}, tokens,
                                          mutable=["intermediates"])[1]


def test_every_delta_rule_layer_starts_at_the_step_drawn():
    """The decay's projection starts at zero, in the model's own
    initialiser and in the benchmark's seeded weights: a post-norm block
    hands its mixer the residual stream as it is (RMS 1.4 after one block,
    2 after two), and under a seeded projection the later layers' steps
    left the range ``dt_bias`` was drawn for — a head forgot its state
    within a token. Every layer's mean decay is then that of ``A_log`` and
    ``dt_bias`` alone: at least exp(-16 * 0.1)."""
    from chipbench.families import olmo_hybrid as family
    from horovod_tpu import obs

    model = OlmoHybridLM.from_config(TOY, dtype=jnp.float32,
                                     rule="recurrent", attention="dense")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 256)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"]
    layers = [f"block_{i}/gdn" for i in range(3)]
    for layer in layers:
        kernel = params[layer.split("/")[0]]["gdn"]["decay"]["kernel"]
        assert float(jnp.abs(kernel).max()) == 0.0
    _, state = model.apply({"params": params}, tokens, mutable=["gdn_stats"])
    published = obs.kda.publish_gdn(state["gdn_stats"])
    assert sorted(published) == layers
    assert all(published[layer]["mean_decay"] > 0.2 for layer in layers)
    (seeded,) = family.init_model_state(TOY, jax.random.PRNGKey(2))
    assert all(float(jnp.abs(seeded[f"block_{i}"]["gdn"]["decay"]["kernel"])
                     .max()) == 0.0 for i in range(3))


def test_a_compiled_steps_text_becomes_gauges():
    """``obs.kda.record_scan_program`` on a step's text cut to its Mosaic
    calls: three delta-rule layers under a decay a head and a full one
    whose recomputed blocks keep their kernels' outputs (0 reruns), and the
    same with every forward kernel run again (4)."""
    from horovod_tpu import obs

    def call(kernel, n):
        shapes = "bf16[1,8192,1440]{2,1,0}, bf16[1,8192,1440]{2,1,0}, " \
            "bf16[1,8192,2880]{2,1,0}, f32[1,4,8192,4]{3,2,1,0}, " \
            "f32[1,4,8192,4]{3,2,1,0}"
        return (f"  %{kernel}.{n} = (bf16[1,8192,2880]{{2,1,0}}) "
                f"custom-call(%p.{n}), "
                'custom_call_target="tpu_custom_call", '
                f"operand_layout_constraints={{{shapes}}}\n")

    def text(forwards, moved=""):
        return "HloModule step\n\nENTRY %main {\n" + "".join(
            call(kernel, n) for kernel, times in (
                ("gdn_fwd", 3 * forwards), ("gdn_bwd", 3),
                ("flash_fwd", forwards), ("flash_bwd_dq", 1),
                ("flash_bwd_dkv", 1))
            for n in range(times)) + moved + "}\n"

    assert obs.kda.record_scan_program("olmo_keeping", text(1)) == (
        0, {"gdn_fwd": 3, "gdn_bwd": 3}, 0, 0)
    assert obs.kda.record_scan_program("olmo_default", text(2))[3] == 4
    # a q-sized copy to heads on an axis of their own, by its shape (15 of
    # the 16 heads in whole groups) or by its scope; a loop under the scope
    moved = (
        "  %copy.9 = bf16[1,8192,15,96]{3,2,1,0} copy(%bitcast.1)\n"
        "  %copy.8 = bf16[1,8192,15,192]{3,2,1,0} copy(%bitcast.2)\n"
        "  %reshape.9 = bf16[1,8192,1440]{2,1,0} reshape(%copy.9), "
        'metadata={op_name="jit(step)/hvd.gdn/hvd.gdn.scan/reshape"}\n'
        "  %copy.10 = bf16[1,8192,12,120]{3,2,1,0} copy(%bitcast.3)\n"
        "  %while.7 = (s32[]) while(%t), condition=%c, body=%b, "
        'metadata={op_name="jit(step)/hvd.gdn/hvd.gdn.scan/while"}\n')
    loops, _, relayouts, _ = obs.kda.record_scan_program(
        "olmo_moved", text(1, moved))
    assert (loops, relayouts) == (1, 3)
    read = {(s["labels"]["program"], s["labels"]["kernel"]): s["value"]
            for s in obs.registry().snapshot()[
                "horovod_kda_kernel_calls"]["samples"]}
    assert read["olmo_keeping", "gdn_fwd"] == 3
    assert read["olmo_keeping", "kda_fwd"] == 0


def test_the_scopes_reach_the_compiled_step(toy):
    model, params, tokens = toy
    text = jax.jit(jax.grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), tokens))).lower(
            params).compile().as_text()
    for scope in ("hvd.gdn/", "hvd.gdn.conv", "hvd.gdn.scan", "hvd.mixer/",
                  "hvd.mixer.proj", "hvd.norm", "hvd.mlp", "hvd.head"):
        assert scope in text, scope


def test_two_devices_train_as_one(toy):
    """Through ``make_lm_train_step`` and ``hvd.DistributedOptimizer`` on a
    data mesh of two: the loss and the updated parameters are those of one
    device on the whole batch (``gdn_fwd`` / ``gdn_bwd`` with a group that
    reaches past the 3 heads, under a vma-checking ``shard_map``)."""
    import optax

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_lm_train_step

    model, params, tokens = toy
    results = []
    for n in (1, 2):
        mesh = hvd.parallel.data_parallel_mesh(jax.devices()[:n])
        opt = hvd.DistributedOptimizer(optax.adamw(1e-2), axis_name="data")
        copy = jax.tree_util.tree_map(jnp.copy, params)
        step = make_lm_train_step(model, opt, mesh)
        new, _, loss = step(copy, jax.jit(opt.init)(copy), tokens)
        results.append((float(loss), new))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(results[0][1]),
                    jax.tree_util.tree_leaves(results[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-4)
