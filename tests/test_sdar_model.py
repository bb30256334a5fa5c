"""``models.sdar``: the noise an input pipeline draws, the expert layer
under a softmax router without a shared expert — the eight shares adding up
to the uncut layer —, ``SdarMoeLM`` with the kernels interpreted against
its written-out attention: its loss, the last layer's skipped clean half,
its gauges and its scopes. (The family's plain reference stands
against the model in ``tests/chipbench/test_chipbench_sdar.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import obs
from horovod_tpu.models import ExpertLayer, SdarMoeLM, experts, sdar

CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 16, "num_experts": 8, "num_experts_per_tok": 2,
    "rope_theta": 1e6, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "experts_held": {"first": 2, "count": 4}}


@pytest.fixture(scope="module")
def toy():
    """The model with its attention written out: what the tests below ask
    does not depend on the kernels."""
    model = SdarMoeLM.from_config(CONFIG, dtype=jnp.float32,
                                  attention="dense")
    clean = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 95)
    noisy, weights = sdar.block_diffusion_noise(
        jax.random.PRNGKey(1), clean, 4, 95)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), clean, noisy)[
        "params"]
    return model, params, (clean, noisy, weights)


@pytest.fixture(scope="module")
def ran(toy):
    """``(loss, the collections it sowed, the step's compiled text)`` and
    the noisy rows' logits under the clean ids and under others."""
    model, params, (clean, noisy, weights) = toy
    step = jax.jit(jax.value_and_grad(lambda p: model.apply(
        {"params": p}, clean, noisy, weights=weights,
        mutable=["bd_stats", "moe_stats"]), has_aux=True))
    (loss, state), _ = step(params)
    logits = jax.jit(model.apply)
    other = jnp.where(clean < 50, clean + 1, clean)
    return (loss, state, step.lower(params).compile().as_text()), \
        [logits({"params": params}, c, noisy) for c in (clean, other)]


def test_noise_masks_a_block_at_its_own_rate_and_weighs_by_it():
    clean = jax.random.randint(jax.random.PRNGKey(0), (64, 1024), 0, 500)
    noisy, weights = sdar.block_diffusion_noise(
        jax.random.PRNGKey(1), clean, 4, 500, eps=1e-3)
    masked = np.asarray(noisy == 500)
    weights = np.asarray(weights)
    assert weights.dtype == np.float32
    assert ((np.asarray(noisy) == np.asarray(clean)) | masked).all()
    assert ((weights > 0) == masked).all()
    assert masked.mean() == pytest.approx(0.5, abs=0.01)
    # E[m / t] = 1 a position; a weight is 1 / rate, one rate a block
    assert weights.mean() == pytest.approx(1.0, abs=0.1)
    assert weights[masked].min() >= 1.0 and weights.max() <= 1000.0
    blocks = weights.reshape(64, 256, 4)
    top = blocks.max(-1, keepdims=True)
    assert ((blocks == 0) | (blocks == top)).all()
    with pytest.raises(ValueError, match="whole blocks"):
        sdar.block_diffusion_noise(jax.random.PRNGKey(1), clean[:, :10], 4, 0)


def _expert_layer(held):
    return ExpertLayer(num_experts=128, experts_per_token=8,
                       experts_held=held, width=8, shared_width=0,
                       scoring="softmax", dtype=jnp.float32)


def test_the_shares_add_up_to_the_whole_layer(monkeypatch):
    """The share test: the expert layer's outputs with experts 0-15, 16-31,
    ..., 112-127 held in turn add up to the uncut 128-expert layer's
    (softmax over all 128, the 8 largest renormalised; no shared expert to
    count once, and no parameter for one)."""
    from horovod_tpu.ops import grouped_matmul as gm

    # the grouped products written out: the shares are the routing's and the
    # layer's matter, and nine layers of interpreted kernels take 10 s
    monkeypatch.setattr(gm, "grouped_matmul", lambda rows, w, group, active,
                        row_tile: gm._tile_by_tile(
                            rows, w, group, jnp.reshape(active, (1,)),
                            row_tile))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 24, 16)), jnp.float32)
    whole = _expert_layer((0, 128))
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"router", "experts_w1", "experts_w3",
                           "experts_w2"}
    # weights large enough that the routed part is no rounding error
    params = jax.tree_util.tree_map(lambda p: 10.0 * p, params)

    @jax.jit
    def both(params):
        total = 0.0
        for share in range(8):
            cut = dict(params, **{
                name: params[name][16 * share:16 * share + 16]
                for name in ("experts_w1", "experts_w3", "experts_w2")})
            total = total + _expert_layer((16 * share, 16)).apply(
                {"params": cut}, x)
        return whole.apply({"params": params}, x), total

    with jax.default_matmul_precision("highest"):
        want, total = both(params)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_softmax_router_keeps_the_largest_and_renormalises_them():
    logits = jnp.log(jnp.asarray([[1.0, 9.0, 5.0, 3.0, 7.0, 2.0]]))
    ids, weights = experts.route(experts.SCORINGS["softmax"](logits), 3, 1.0)
    assert ids.tolist() == [[1, 4, 2]]
    np.testing.assert_allclose(weights, [[9 / 21, 7 / 21, 5 / 21]], rtol=1e-6)
    with pytest.raises(ValueError, match="scoring must be one of"):
        ExpertLayer(num_experts=4, experts_per_token=2, experts_held=(0, 4),
                    width=8, shared_width=0, scoring="tanh").init(
                        jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


def test_the_loss_is_the_weighted_cross_entropy_of_the_noisy_rows(toy, ran):
    """Against the noisy rows' own logits and the clean ids, no shift, over
    ``G * L``. (The interpreted kernels under recomputation stand against
    the family's reference in ``tests/chipbench/test_chipbench_sdar.py``.)"""
    _, _, (clean, _, weights) = toy
    (loss, _, _), (logits, _) = ran
    assert logits.shape == (2, 64, 96) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, clean[..., None], -1)[..., 0]
    assert float(loss) == pytest.approx(
        float(-jnp.sum(weights * picked) / clean.size), rel=1e-5)


def test_the_clean_half_of_the_last_layer_matters_for_keys_alone(ran):
    """Only the noisy rows leave the last layer's attention: its expert
    layer, the final norm and the head run on ``G * L`` rows, and the clean
    ids still reach the logits through every layer's keys and values."""
    (_, state, _), (a, b) = ran
    counts = [int(np.asarray(state["moe_stats"][f"block_{i}"]["moe"][
        "assignments"][0]).sum()) for i in range(2)]
    assert counts == [2 * 128 * 2, 2 * 64 * 2]
    assert float(jnp.abs(a - b).max()) > 1e-4


def test_the_stats_become_gauges(toy, ran):
    model, params, (clean, noisy, weights) = toy
    state = ran[0][1]
    published = obs.bd.publish(state["bd_stats"])
    masked = np.asarray(weights) > 0
    assert published == pytest.approx({
        "masked_share": masked.mean(),
        "mean_weight": np.asarray(weights)[masked].mean()})
    snapshot = obs.registry().snapshot()
    for name, key in (("horovod_bd_masked_share", "masked_share"),
                      ("horovod_bd_mean_weight", "mean_weight")):
        (sample,) = snapshot[name]["samples"]
        assert sample["value"] == pytest.approx(published[key])
    assert sorted(obs.moe.publish(state["moe_stats"])) \
        == ["block_0/moe", "block_1/moe"]
    # a training step does not carry the collections
    assert "bd_stats" not in jax.eval_shape(lambda p: model.apply(
        {"params": p}, clean, noisy, weights=weights,
        mutable=["intermediates"]), params)[1]
    assert obs.bd.publish({}) == {}


def test_the_scopes_reach_the_compiled_step(ran):
    text = ran[0][2]
    for scope in ("hvd.embed", "hvd.norm", "hvd.mixer/", "hvd.mixer.proj",
                  "hvd.bd/", "hvd.bd.attn", "hvd.moe/", "hvd.moe.route",
                  "hvd.moe.experts", "hvd.head"):
        assert scope in text, scope


def test_what_from_config_refuses():
    with pytest.raises(ValueError, match="dense layers"):
        SdarMoeLM.from_config(dict(CONFIG, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="norm_topk_prob"):
        SdarMoeLM.from_config(dict(CONFIG, norm_topk_prob=False))
    model = SdarMoeLM.from_config(dict(CONFIG, block_length=8))
    assert (model.block_length, model.experts_held) == (8, (2, 4))
