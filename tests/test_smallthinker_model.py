"""``models.smallthinker``: ``SmallThinkerLM`` against the plain reference
(``chipbench/families/smallthinker.py``, which imports nothing of the
program) on seeded weights — logits, loss and every gradient leaf, with the
attention written out and with the flash kernels interpreted —, the router
fed from the block's input, full layers without positions beside rotated
window layers, the ReLU gate, the four shares of a layer adding up to the
uncut reference, the gauge of exact zeros and the scopes.

Every comparison runs in float32 under ``highest`` matmul precision; a
tolerance is the float32 summation order's, and a bfloat16 computation of
either side (relative error 4e-3 a product) would fail each by two orders."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import numerics
from chipbench.families import laguna as reference_parts
from chipbench.families import smallthinker as family
from horovod_tpu import obs
from horovod_tpu.models import ExpertLayer, SmallThinkerLM, smallthinker
from horovod_tpu.models.parts import Rotary

# a group of 7 query heads a key/value head, one period of the layout,
# experts 4-7 of 16 held
CONFIG = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "sliding_window_size": 16, "rope_theta": 1.5e6, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "attention": "flash", "remat": True,
    "precision": {"compute": "float32"},
    "deployment": {"num_experts": 16, "experts_held_first": 4,
                   "num_hidden_layers": 4}}


@pytest.fixture(scope="module")
def seeded():
    (params,) = family.init_model_state(CONFIG, jax.random.PRNGKey(0))
    # weights large enough that routing and attention are no rounding error
    params = jax.tree_util.tree_map(
        lambda p: p if p.ndim == 1 else 4.0 * p, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    return params, tokens


def _reference_logits(params, row):
    """One sequence's logits by the reference's own blocks."""
    x = params["tok_embed"]["embedding"][row]
    for i, (windowed, rotated) in enumerate(family.layers(CONFIG)):
        x = family._block(params[f"block_{i}"], x, jnp.arange(row.size),
                          CONFIG, windowed, rotated, numerics.Exact)
    x = reference_parts._rms_norm(x, params["ln_final"], 1e-6)
    return x @ params["lm_head"]["kernel"]


@pytest.mark.parametrize("attention", ("dense", "flash"))
def test_logits_loss_and_gradients_against_the_reference(seeded, attention):
    """``flash``: the ``flash_*`` (no positions) and ``flash_win_*`` kernels
    interpreted, a group of 7, each half of a block recomputed."""
    params, tokens = seeded
    model = family.build(CONFIG).clone(attention=attention)
    assert (model.windowed, model.rotated, model.experts_held,
            model.num_experts, model.remat) == (
        (False, True, True, True),) * 2 + ((4, 4), 16, True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(functools.partial(
            _reference_logits, params)))(tokens)
        got = jax.jit(model.apply)({"params": params}, tokens)
        ref_loss, ref_grad = jax.jit(jax.value_and_grad(functools.partial(
            family.reference_loss, config=CONFIG)))(params, tokens)
        loss, grad = jax.jit(jax.value_and_grad(lambda p: model.apply(
            {"params": p}, tokens, loss_tokens=tokens)))(params)
    assert got.dtype == jnp.float32 and got.shape == (2, 64, 128)
    assert float(jnp.abs(want).max()) > 0.5
    # float32 summation order: 1e-6 a product, some hundreds summed
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    # embedding; a block's two norms, four attention leaves, router and
    # three expert tensors; final norm and head
    assert len(ref) == 1 + 4 * (2 + 4 + 4) + 1 + 1
    assert min(ref.values()) > 0
    assert max(err[k] / ref[k] for k in ref) < 1e-4


def _block(**attn):
    experts = dict(num_experts=16, experts_per_token=3, experts_held=(4, 4),
                   width=32, shared_width=0, scoring="softmax", gate="relu")
    attn = dict(dict(num_heads=14, num_kv_heads=2, head_dim=16,
                     attention="dense"), **attn)
    return smallthinker.SmallThinkerBlock(attn=attn, experts=experts,
                                          dtype=jnp.float32)


def test_the_router_reads_the_blocks_input(seeded, monkeypatch):
    """The router's gradient is the one the equations give, ``x W_r`` on the
    block's input — and not the one a router fed ``RMSNorm_2(a)`` gets, which
    this case tells apart by a factor, not a rounding."""
    params, _ = seeded
    p = params["block_1"]
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64))
    target = jax.random.normal(jax.random.PRNGKey(4), (1, 48, 64))
    positions = jnp.arange(48)[None]

    written, fed = family._experts, []
    monkeypatch.setattr(
        family, "_experts", lambda q, h, r, *rest: written(
            q, h, h if fed[-1] else r, *rest))

    def reference(p, read_normed: bool):
        fed.append(read_normed)
        out = family._block(p, x[0], positions[0], CONFIG, True, True,
                            numerics.Exact)
        return jnp.sum(out * target[0])

    block = _block(window=16, rotary=Rotary(theta=1.5e6, dim=16))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: jnp.sum(block.apply(
            {"params": p}, x, positions) * target))(p)
        want, wrong = (jax.grad(functools.partial(
            reference, read_normed=flag))(p) for flag in (False, True))
    router = lambda g: g["moe"]["router"]["kernel"]  # noqa: E731
    scale = float(jnp.linalg.norm(router(want)))
    assert scale > 1e-3
    # float32 summation order
    assert float(jnp.linalg.norm(router(got) - router(want))) < 1e-4 * scale
    assert float(jnp.linalg.norm(router(got) - router(wrong))) > 0.5 * scale
    for leaf, ref in zip(jax.tree_util.tree_leaves(got),
                         jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(leaf - ref)) \
            < 1e-4 * float(jnp.linalg.norm(ref))


def test_full_layers_have_no_positions_and_window_layers_relative_ones(
        seeded):
    """Shifting every position by a constant changes neither kind of layer:
    a window layer's rotation is relative, a full layer has nothing to
    shift. Giving a full layer a rotation does change it: its positions are
    absent, not zero."""
    params, _ = seeded
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    positions = jnp.arange(48)[None]
    rotary = Rotary(theta=1.5e6, dim=16)
    with jax.default_matmul_precision("highest"):
        for p, fields in ((params["block_0"], dict()),
                          (params["block_1"], dict(window=16,
                                                   rotary=rotary))):
            here, moved = (_block(**fields).apply({"params": p}, x, at)
                           for at in (positions, positions + 1000))
            # float32 cos and sin at angles of 1e3 against angles of 50
            np.testing.assert_allclose(moved, here, atol=2e-4)
            assert float(jnp.abs(here - x).max()) > 0.1
        bare = _block().apply({"params": params["block_0"]}, x, positions)
        turned = _block(rotary=rotary).apply(
            {"params": params["block_0"]}, x, positions)
        unmoved = _block().apply({"params": params["block_0"]}, x,
                                 0 * positions)
    assert float(jnp.abs(turned - bare).max()) > 1e-2
    np.testing.assert_array_equal(unmoved, bare)


def _layer(held, gate="relu"):
    return ExpertLayer(num_experts=64, experts_per_token=6, experts_held=held,
                       width=16, shared_width=0, scoring="softmax",
                       gate=gate, dtype=jnp.float32)


def _written_out(monkeypatch):
    """The grouped products written out: the layer's and the routing's
    matter here, and interpreted kernels take seconds a layer."""
    from horovod_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "grouped_matmul", lambda rows, w, group, active,
                        row_tile: gm._tile_by_tile(
                            rows, w, group, jnp.reshape(active, (1,)),
                            row_tile))


def test_the_four_shares_add_up_to_the_uncut_reference(monkeypatch):
    """The share test: the program's expert layer with experts 0-15, 16-31,
    32-47 and 48-63 held in turn — the router reading one tensor, the
    experts another — adds up to the *reference's* uncut 64-expert layer
    (softmax over all 64, the 6 largest renormalised, ReLU-gated experts;
    no shared expert to count once)."""
    _written_out(monkeypatch)
    rng = np.random.default_rng(5)
    h, r = (jnp.asarray(rng.standard_normal((2, 24, 32)), jnp.float32)
            for _ in range(2))
    params = _layer((0, 64)).init(jax.random.PRNGKey(0), h)["params"]
    assert set(params) == {"router", "experts_w1", "experts_w3",
                           "experts_w2"}
    params = jax.tree_util.tree_map(lambda p: 10.0 * p, params)
    config = dict(CONFIG, moe_num_active_primary_experts=6)

    @jax.jit
    def both(params):
        total = 0.0
        for first in (0, 16, 32, 48):
            cut = dict(params, **{
                name: params[name][first:first + 16]
                for name in ("experts_w1", "experts_w3", "experts_w2")})
            total = total + _layer((first, 16)).apply(
                {"params": cut}, h, routed_by=r)
        uncut = family._experts(params, h.reshape(-1, 32), r.reshape(-1, 32),
                                config, numerics.Exact, first=0, count=64)
        return uncut.reshape(h.shape), total

    with jax.default_matmul_precision("highest"):
        want, total = both(params)
    assert float(jnp.abs(want).max()) > 0.1
    # float32 summation order over 6 experts of 16 + 32 products
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_a_relu_gate_is_not_a_silu_gate(monkeypatch):
    _written_out(monkeypatch)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 32))
    params = jax.tree_util.tree_map(
        lambda p: 10.0 * p,
        _layer((0, 64)).init(jax.random.PRNGKey(0), x)["params"])

    def by_hand(gate):
        scores = jax.nn.softmax(x[0] @ params["router"]["kernel"], -1)
        top, ids = jax.lax.top_k(scores, 6)
        top = top / top.sum(-1, keepdims=True)
        w1, w3, w2 = (params[n][ids] for n in (
            "experts_w1", "experts_w3", "experts_w2"))     # [N, 6, ...]
        hidden = gate(jnp.einsum("nd,nkdf->nkf", x[0], w1)) \
            * jnp.einsum("nd,nkdf->nkf", x[0], w3)
        return jnp.einsum("nkf,nkfd,nk->nd", hidden, w2, top)[None]

    with jax.default_matmul_precision("highest"):
        relu, silu = (_layer((0, 64), gate).apply({"params": params}, x)
                      for gate in ("relu", "silu"))
        np.testing.assert_allclose(relu, by_hand(jax.nn.relu), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(silu, by_hand(jax.nn.silu), rtol=1e-4,
                                   atol=1e-5)
    assert float(jnp.abs(relu - silu).max()) > 0.05
    with pytest.raises(ValueError, match="gate must be one of"):
        _layer((0, 64), "gelu").init(jax.random.PRNGKey(0), x)


def test_the_gauge_of_exact_zeros_and_its_absence_from_a_step(seeded):
    """``horovod_moe_gate_zero_share``: about a half under ``relu`` on
    seeded weights, 0 under ``silu``; a pass that only a caller of the
    collection traces."""
    params, tokens = seeded
    model = family.build(CONFIG).clone(attention="dense")
    _, state = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, mutable=["moe_stats"]))(params)
    published = obs.moe.publish(state["moe_stats"])
    assert sorted(published) == [f"block_{i}/moe" for i in range(4)]
    for layer in published.values():
        assert 0.4 < layer["gate_zero_share"] < 0.6
    samples = obs.registry().snapshot()["horovod_moe_gate_zero_share"]
    read = {s["labels"]["layer"]: s["value"] for s in samples["samples"]}
    assert read["block_2/moe"] == published["block_2/moe"]["gate_zero_share"]

    x = jax.random.normal(jax.random.PRNGKey(7), (1, 64, 32))
    for gate, low, high in (("relu", 0.4, 0.6), ("silu", 0.0, 0.0)):
        layer = ExpertLayer(num_experts=16, experts_per_token=3,
                            experts_held=(4, 4), width=16, shared_width=0,
                            scoring="softmax", gate=gate, dtype=jnp.float32)
        p = layer.init(jax.random.PRNGKey(0), x)
        assert "moe_stats" not in p or "gate_zero_share" not in str(
            jax.tree_util.tree_structure(p["moe_stats"]))
        _, state = layer.apply({"params": p["params"]}, x,
                               mutable=["moe_stats"])
        assert low <= float(state["moe_stats"]["gate_zero_share"][0]) <= high
    # the forward pass holds one loop a layer, the counting pass a second
    loops = [str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, tokens, **kw))(params)).count("while[")
        for kw in ({}, {"mutable": ["moe_stats"]})]
    assert loops == [4, 8]


def test_routing_stays_under_its_own_scope_and_outside_the_mixer(seeded):
    params, tokens = seeded
    model = family.build(CONFIG).clone(attention="dense")
    hlo = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, tokens, loss_tokens=tokens))).lower(
            params).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    routed = [n for n in names if "hvd.moe.route" in n]
    assert routed and not [n for n in routed if "hvd.mixer" in n]
    assert [n for n in routed if "router" in n and "dot_general" in n]
    # (a parameter's own name, ``p['block_0']['moe']['router']...``, is no
    # operation's path)
    assert not [n for n in names if "/router" in n
                and "hvd.moe.route" not in n]
    for scope in ("hvd.mixer/", "hvd.mixer.proj", "hvd.norm", "hvd.embed",
                  "hvd.head", "hvd.moe.experts"):
        assert [n for n in names if scope in n], scope
    # the owners never nest in one another
    owners = re.compile(r"hvd\.(embed|norm|mixer|mlp|head|moe)\b(?!\.)")
    assert not [n for n in names if len(set(owners.findall(n))) > 1]


def test_from_config_reads_the_published_keys():
    published = {k: v for k, v in CONFIG.items()
                 if k not in ("attention", "remat", "precision",
                              "deployment")}
    model = SmallThinkerLM.from_config(published)
    assert (model.num_experts, model.experts_held, model.experts_per_token,
            model.window, model.rope_theta, model.expert_width) == (
        4, (0, 4), 3, 16, 1.5e6, 32)
    assert model.windowed == model.rotated == (False, True, True, True)
    for key, value in (("moe_primary_router_apply_softmax", False),
                       ("norm_topk_prob", False),
                       ("rope_scaling", {"factor": 2})):
        with pytest.raises(ValueError, match="not supported"):
            SmallThinkerLM.from_config(dict(published, **{key: value}))
