"""``models.kimi_linear``: the layout ``from_config`` gives the published
pattern, the kernels against the written-out backends (the chunked delta
rule against the token-by-token scan, flash against dense attention), the
causal convolution against its definition, a recomputed block keeping its
mixer kernel's outputs and nothing else, the seeded parameter tree pinned,
what feeds the delta rule and what normalises its output with the heads side
by side against both written out by head, the 32 shares of an expert layer
adding up to the uncut one at this model's router, the scopes in a compiled
step, ``kda_stats`` as gauges, and the model through the data-parallel step
on two devices."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import ExpertLayer, KimiLinearLM, LagunaLM, lm_loss
from horovod_tpu.models import delta, kimi_linear, parts, scopes

TOY = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 2, "rms_norm_eps": 1e-5,
    "linear_attn_config": {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
        "head_dim": 32, "num_heads": 2, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "kv_lora_rank": 48, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "num_experts": 16, "num_experts_per_token": 4,
    "routed_scaling_factor": 2.446,
    "experts_held": {"first": 4, "count": 4},
}


# one layer of each kind the model has: KDA + dense, latent + experts,
# KDA + experts (the tests that compile gradients run on these three)
SMALL = dict(TOY, num_hidden_layers=3, linear_attn_config=dict(
    TOY["linear_attn_config"], kda_layers=[1, 3], full_attn_layers=[2]))


@pytest.fixture(scope="module")
def toy():
    model = KimiLinearLM.from_config(SMALL, dtype=jnp.float32)
    # a chunk and a half of the delta rule
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 512)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    return model, params, tokens


def test_from_config_lays_out_the_published_pattern():
    model = KimiLinearLM.from_config(TOY, dtype=jnp.float32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            jnp.zeros((2, 96), jnp.int32))["params"]
    assert model.mixers == ("kda", "kda", "kda", "mla", "kda")
    assert model.mlp_layer_types == ("dense",) + ("sparse",) * 4
    whole = KimiLinearLM.from_config(dict(TOY, num_hidden_layers=8))
    assert whole.mixers == ("kda", "kda", "kda", "mla") * 2
    assert "mlp" in params["block_0"] and "moe" not in params["block_0"]
    mixer = params["block_0"]["kda"]
    assert mixer["query"]["kernel"].shape == (64, 64)      # 2 heads x 32
    assert mixer["conv_k"].shape == (4, 64)
    assert mixer["decay_a"]["kernel"].shape == (64, 32)    # the head width
    assert mixer["decay_b"]["kernel"].shape == (32, 64)
    assert mixer["decay_rate"].shape == (2,)
    assert mixer["decay_bias"].shape == (64,)
    assert mixer["beta"]["kernel"].shape == (64, 2)
    assert mixer["out_norm"]["scale"].shape == (32,)
    latent = params["block_3"]["mla"]
    assert latent["query"]["kernel"].shape == (64, 2, 48)   # 32 + 16
    assert latent["kv_a"]["kernel"].shape == (64, 48 + 16)
    assert latent["kv_b"]["kernel"].shape == (48, 2, 32 + 32)
    assert latent["out"]["kernel"].shape == (2, 32, 64)
    moe = params["block_3"]["moe"]
    assert moe["router"]["kernel"].shape == (64, 16)   # all the experts
    assert moe["experts_w1"].shape == (4, 64, 32)      # the held ones
    biases = [jax.tree_util.keystr(path) for path, _ in
              jax.tree_util.tree_leaves_with_path(params)
              if "bias" in jax.tree_util.keystr(path)]
    assert len(biases) == 4 and all("decay_bias" in b for b in biases)
    with pytest.raises(ValueError, match="head counts of their own"):
        KimiLinearLM.from_config(dict(TOY, linear_attn_config=dict(
            TOY["linear_attn_config"], num_heads=4)))


def test_seeded_decay_forgets_neither_everything_nor_nothing(toy):
    """``A`` in log [1, 16] and softplus(b) in [0.001, 0.1]: alpha between
    e^-1.6 and e^-0.001 a token."""
    mixer = toy[1]["block_0"]["kda"]
    assert toy[0].mixers == ("kda", "mla", "kda")
    rate = np.exp(np.asarray(mixer["decay_rate"]))
    step = np.asarray(jax.nn.softplus(mixer["decay_bias"]))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    assert step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001


def test_causal_conv_against_its_definition():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 9, 3)),
                    jnp.float32)
    taps = jnp.asarray([[1.0, 0.0, 2.0], [0.0, 1.0, 0.0],
                        [0.0, 0.0, 0.0], [0.5, 3.0, 1.0]])
    out = np.asarray(delta.causal_conv(x, taps))
    x = np.asarray(x)
    # the last tap is the token's own; nothing before the sequence
    np.testing.assert_allclose(out[0, 0], [0.5, 3.0, 1.0] * x[0, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(
        out[0, 5, 0], 1.0 * x[0, 2, 0] + 0.5 * x[0, 5, 0], rtol=1e-6)
    np.testing.assert_allclose(
        out[0, 5, 1], 1.0 * x[0, 3, 1] + 3.0 * x[0, 5, 1], rtol=1e-6)
    moved = np.asarray(delta.causal_conv(
        jnp.asarray(x).at[0, 6].add(1.0), taps))
    assert np.abs(moved - out)[0, :6].max() == 0.0


def test_the_parameter_tree_is_what_it_was(toy):
    """Leaf paths, shapes and the seeded values, pinned to the tree before
    the mixer's tensors went ``[B, T, H * d]`` (PR 36): the benchmark's
    seeded weights, its leaf count and its limits depend on it."""
    import hashlib

    leaves = jax.tree_util.tree_leaves_with_path(toy[1])
    digest = hashlib.sha256()
    for path, leaf in leaves:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(str(leaf.shape).encode())
        digest.update(np.asarray(leaf).tobytes())
    assert len(leaves) == 61
    mixer = toy[1]["block_0"]["kda"]
    assert sorted(mixer) == [
        "beta", "conv_k", "conv_q", "conv_v", "decay_a", "decay_b",
        "decay_bias", "decay_rate", "gate_a", "gate_b", "key", "out",
        "out_norm", "query", "value"]
    assert list(mixer["out_norm"]) == ["scale"]
    assert digest.hexdigest() == ("7073841aacfad30043ad0f917c34addae636066a"
                                  "7d26bc9252def0d79b8c8a9f")


def test_heads_side_by_side_against_heads_on_an_axis():
    """``conditioned`` and the output norm keep ``[B, T, H * d]`` and sum
    a head's channels where they lie; written out with the heads on an axis
    of their own, as the model had them, the numbers are the same in
    float32 — values and the gradient of every argument."""
    import flax.linen as nn

    heads, d, seq = 3, 16, 10
    rng = np.random.default_rng(3)
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    args = (*(normal(2, seq, heads * d) for _ in range(4)),
            normal(2, seq, heads), tuple(normal(4, heads * d) / 2
                                         for _ in range(3)),
            normal(heads), normal(heads * d))
    split = lambda a: a.reshape(*a.shape[:2], heads, -1)  # noqa: E731

    def by_head(q, k, v, raw, write, taps, rate, bias):
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
        q, k, v = (split(nn.silu(delta.causal_conv(a, t)))
                   for a, t in zip((q, k, v), taps))
        g = -jnp.exp(rate)[:, None] * split(jax.nn.softplus(raw + bias))
        return unit(q), unit(k), v, g, nn.sigmoid(write)

    def flat(*a):
        *fed, beta = delta.conditioned(*a, heads=heads, dtype=jnp.float32,
                                       conv_scope="hvd.kda.conv")
        assert all(x.shape == (2, seq, heads * d) for x in fed)
        return (*map(split, fed), beta)

    weights = [normal(*x.shape) for x in by_head(*args)]
    loss = lambda fn: lambda *a: sum(  # noqa: E731
        jnp.sum(w * x) for w, x in zip(weights, fn(*a)))
    for got, want in zip(flat(*args), by_head(*args)):
        np.testing.assert_allclose(got, want, atol=1e-6)
    for got, want in zip(*(jax.tree_util.tree_leaves(jax.grad(
            loss(fn), argnums=tuple(range(8)))(*args))
            for fn in (flat, by_head))):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the output norm against flax's over the last axis, one scale shared
    o, scale = normal(2, seq, heads * d), {"scale": 1.0 + normal(d) / 4}
    want = nn.RMSNorm(epsilon=1e-5).apply({"params": scale}, split(o))
    got = delta.HeadRMSNorm(heads, 1e-5, jnp.float32).apply(
        {"params": scale}, o)
    np.testing.assert_allclose(split(got), want, atol=1e-6)


def test_kernels_and_written_out_backends_agree_and_remat_changes_nothing(
        toy):
    model, params, tokens = toy

    def loss_and_grad(m, params=params):
        return jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, tokens), tokens))(params)

    kernels = loss_and_grad(model)
    assert (model.attention, model.kda) == ("flash", "chunked")
    for other in (model.clone(attention="dense", kda="recurrent"),
                  model.clone(remat=True)):
        loss, grad = loss_and_grad(other)
        assert float(loss) == pytest.approx(float(kernels[0]), rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(grad),
                        jax.tree_util.tree_leaves(kernels[1])):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)
    # compiled whole, as a step is: what a recomputed block keeps of its
    # mixer's kernel is what it would have recomputed, so nothing moves
    plain, kept = (jax.jit(functools.partial(loss_and_grad, m))(params)
                   for m in (model, model.clone(remat=True)))
    for a, b in zip(*map(jax.tree_util.tree_leaves, (kept, plain))):
        np.testing.assert_array_equal(a, b)
    for wrong in (dict(kda="scan"), dict(attention="ring")):
        with pytest.raises(ValueError, match="must be one of"):
            model.clone(**wrong).apply({"params": params}, tokens)


def gradient_program_counts(model, params, tokens) -> collections.Counter:
    """Of the jaxpr of the loss's gradient (``params`` may be shapes): the
    ``pallas_call``s by name and, under ``"projections"``, the
    ``dot_general``s traced under ``hvd.mixer.proj``. A ``cond``
    (``lax.platform_dependent``: the kernel interpreted or compiled) counts
    by its first branch."""
    counts = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[eqn.params["name"]] += 1
                continue
            if eqn.primitive.name == "dot_general" and \
                    scopes.MIXER_PROJ in str(eqn.source_info.name_stack):
                counts["projections"] += 1
            inner = list(jax.core.jaxprs_in_params(eqn.params))
            for sub in inner[:1] if eqn.primitive.name == "cond" else inner:
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), tokens)))(params).jaxpr)
    return counts


@pytest.mark.parametrize("mixer, kernel", [("kda", "kda_fwd"),
                                           ("mla", "flash_mla_fwd")])
def test_a_recomputed_block_keeps_its_mixer_kernels_outputs(
        monkeypatch, mixer, kernel):
    """Two layers of one mixer kind: the gradient program of the
    ``remat=True`` model holds the mixer's forward kernel once a layer — as
    the unrecomputed model's does — where ``nn.remat``'s default policy
    runs it twice, and recomputes the rest of the block all the same: the
    projections' products are as many as under the default policy."""
    layers = {"kda": {"kda_layers": [1, 2], "full_attn_layers": []},
              "mla": {"kda_layers": [], "full_attn_layers": [1, 2]}}[mixer]
    model = KimiLinearLM.from_config(
        dict(SMALL, num_hidden_layers=2, linear_attn_config=dict(
            TOY["linear_attn_config"], **layers)),
        dtype=jnp.float32, remat=True)
    assert model.mixers == (mixer, mixer)
    tokens = jnp.zeros((2, 96), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            tokens)["params"]
    keeping = gradient_program_counts(model, params, tokens)
    plain = gradient_program_counts(model.clone(remat=False), params, tokens)
    monkeypatch.setattr(kimi_linear, "keep_policy", lambda *kernels: None)
    default = gradient_program_counts(model, params, tokens)
    assert (plain[kernel], keeping[kernel], default[kernel]) == (2, 2, 4)
    assert keeping["projections"] == default["projections"] \
        > plain["projections"]
    backward = {"kda": ["kda_bwd"],
                "mla": ["flash_mla_bwd_dq", "flash_mla_bwd_dkv"]}[mixer]
    assert all(c[name] == 2 for name in backward
               for c in (plain, keeping, default))


def test_laguna_keeps_its_full_layers_flash_outputs():
    """``models.laguna`` takes the same names: a recomputed full layer keeps
    ``flash_fwd``'s outputs and runs it once, as the unrecomputed model
    does; a sliding layer, whose window kernel is cheap for what keeping
    would hold, recomputes ``flash_win_fwd`` (``laguna.LagunaBlock``). Each
    half of a block is a checkpoint of its own, and the attention's output
    projection is not recomputed: one product of the five under
    ``hvd.mixer.proj`` fewer a layer than a second forward pass has."""
    from test_laguna_model import TOY as LAGUNA_TOY

    model = LagunaLM.from_config(
        dict(LAGUNA_TOY, num_hidden_layers=2), dtype=jnp.float32, remat=True)
    assert model.layer_types == ("full_attention", "sliding_attention")
    tokens = jnp.zeros((2, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(1),
                            tokens)["params"]
    recomputed = gradient_program_counts(model, params, tokens)
    plain = gradient_program_counts(model.clone(remat=False), params, tokens)
    assert (plain["flash_fwd"], recomputed["flash_fwd"]) == (1, 1)
    assert (plain["flash_win_fwd"], recomputed["flash_win_fwd"]) == (1, 2)
    # query, key, value, gate, out: forward and two transposes each, and the
    # first four once more in the recomputed half
    assert (plain["projections"], recomputed["projections"]) \
        == (2 * 15, 2 * 19)


def test_nothing_sees_the_future(toy):
    """Change the last token: no logit before it moves, through the
    convolution, the delta rule and latent attention alike."""
    model, params, tokens = toy
    moved = tokens.at[:, -1].set((tokens[:, -1] + 1) % 512)
    delta = np.abs(np.asarray(model.apply({"params": params}, tokens)
                              - model.apply({"params": params}, moved)))
    assert delta[:, :-1].max() == 0.0 and delta[:, -1].max() > 0


def test_the_32_shares_add_up_to_the_whole_layer():
    """This model's cut: 32 chips share a layer's 256 experts, 8 a chip, 8
    a token, scores renormalised and scaled by 2.446. Over all 32 shares,
    the shared expert counted once, the parts sum to the uncut layer."""
    def layer(held):
        return ExpertLayer(num_experts=256, experts_per_token=8,
                           experts_held=held, width=8, shared_width=8,
                           scaling=2.446, dtype=jnp.float32)

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 48, 16)), jnp.float32)
    whole = layer((0, 256))
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree_util.tree_map(lambda p: 10.0 * p, params)
    with jax.default_matmul_precision("highest"):
        want = whole.apply({"params": params}, x)
        shared_alone = parts.GatedMLP(8, jnp.float32).apply(
            {"params": params["shared"]}, x)
        total = shared_alone
        for share in range(32):
            cut = dict(params, **{
                name: params[name][8 * share:8 * share + 8]
                for name in ("experts_w1", "experts_w3", "experts_w2")})
            part = layer((8 * share, 8)).apply({"params": cut}, x)
            total = total + (part - shared_alone)
    assert float(jnp.abs(want - shared_alone).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_kda_stats_become_gauges(toy):
    from horovod_tpu import obs

    model, params, tokens = toy
    _, state = model.apply({"params": params}, tokens,
                           mutable=["kda_stats"])
    published = obs.kda.publish(state["kda_stats"])
    assert sorted(published) == ["block_0/kda", "block_2/kda"]
    for stats in published.values():
        assert 0.2 < stats["mean_decay"] < 1.0
        assert 0.0 < stats["state_abs_max"] < 10.0
    want = published["block_2/kda"]
    assert want["mean_decay"] == pytest.approx(float(
        state["kda_stats"]["block_2"]["kda"]["mean_decay"][0]))
    snapshot = obs.registry().snapshot()
    for family, key in (("horovod_kda_mean_decay", "mean_decay"),
                        ("horovod_kda_state_abs_max", "state_abs_max")):
        read = {s["labels"]["layer"]: s["value"]
                for s in snapshot[family]["samples"]}
        assert read["block_2/kda"] == pytest.approx(want[key])
    # the routing gauges, for this model's layers
    _, routed = model.apply({"params": params}, tokens,
                            mutable=["moe_stats"])
    assert sorted(obs.moe.publish(routed["moe_stats"])) == [
        "block_1/moe", "block_2/moe"]
    # a training step does not carry the collection
    assert "kda_stats" not in model.apply({"params": params}, tokens,
                                          mutable=["intermediates"])[1]


def test_forward_kernels_run_again_become_a_gauge():
    """``obs.kda.record_scan_program`` on a compiled step's text, cut to
    its Mosaic calls: four KDA layers and a latent one whose recomputed
    blocks run their forward kernel again (5 reruns), the same with the
    first KDA block alone doing so (1), and with every block keeping (0)."""
    from horovod_tpu import obs

    def call(kernel, n):
        return (f"  %{kernel}.{n} = (bf16[1,16384,4096]{{2,1,0}}) "
                f"custom-call(%p.{n}), "
                'custom_call_target="tpu_custom_call"\n')

    def text(kda_fwd, flash_mla_fwd):
        return "ENTRY %main {\n" + "".join(
            call(kernel, n) for kernel, times in (
                ("kda_fwd", kda_fwd), ("kda_bwd", 4),
                ("flash_mla_fwd", flash_mla_fwd), ("flash_mla_bwd_dq", 1),
                ("flash_mla_bwd_dkv", 1), ("expert_matmul_fwd", 48))
            for n in range(times)) + "}\n"

    for program, (kda_fwd, flash_mla_fwd), reruns in (
            ("default", (8, 2), 5), ("all_but_one", (5, 1), 1),
            ("keeping", (4, 1), 0)):
        _, calls, _, counted = obs.kda.record_scan_program(
            program, text(kda_fwd, flash_mla_fwd))
        assert calls == {"kda_fwd": kda_fwd, "kda_bwd": 4}
        assert counted == reruns
    read = {s["labels"]["program"]: s["value"] for s in obs.registry()
            .snapshot()["horovod_remat_forward_reruns"]["samples"]}
    assert (read["default"], read["all_but_one"], read["keeping"]) \
        == (5, 1, 0)


def test_the_scopes_reach_the_compiled_step(toy):
    model, params, tokens = toy
    text = jax.jit(jax.grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), tokens))).lower(
            params).compile().as_text()
    for scope in ("hvd.kda/", "hvd.kda.conv", "hvd.kda.scan", "hvd.mla/",
                  "hvd.mla.attn", "hvd.moe.route", "hvd.moe.experts"):
        assert scope in text, scope


def test_two_devices_train_as_one(toy):
    """Through ``make_lm_train_step`` and ``hvd.DistributedOptimizer`` on a
    data mesh of two: the loss and the updated parameters are those of one
    device on the whole batch (the chunks' preparation, the two chain
    kernels and the split-width flash kernels under a vma-checking
    ``shard_map``)."""
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
