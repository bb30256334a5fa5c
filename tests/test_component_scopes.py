"""The component scopes of the five models (``models/scopes.py``;
docs/tracing.md, "Scopes in a compiled step") as they reach the train
step's ``op_name``s, lowered on the CPU at toy sizes: every scope forward
and backward, never one inside another, the shared expert under ``hvd.moe``,
a rematerialised block's operations still owned, and the parameter trees
what they were before the scopes (a scope touches the name stack only)."""

import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmarks._dp_step import make_dp_train_step, make_lm_train_step
from horovod_tpu.models import (KimiLinearLM, LagunaLM, OlmoHybridLM, ResNet,
                                TransformerLM, scopes)
from horovod_tpu.models.resnet import BottleneckResNetBlock

FORWARD, BACKWARD = "jvp(hvd.loss)", "transpose(jvp(hvd.loss))"
SIX = (scopes.EMBED, scopes.NORM, scopes.MIXER, scopes.MIXER_PROJ,
       scopes.MLP, scopes.HEAD)
_COMPONENT = re.compile(r"hvd\.(?:embed|norm|mixer|mlp|head)\b(?!\.proj)")


def _transformer(**fields):
    return TransformerLM(vocab_size=128, num_layers=1, num_heads=2,
                         d_model=32, d_ff=64, max_seq_len=128,
                         attention="flash", **fields)


def _laguna(**fields):
    """``tests/test_laguna_model.py``'s toy cut to a full layer with a
    dense MLP and a window layer with experts."""
    return LagunaLM.from_config({
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 32,
        "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "sliding_window": 16, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": {
            "full_attention": {"rope_type": "default", "rope_theta": 500000,
                               "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": ["full_attention", "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse"],
        "num_attention_heads_per_layer": [6, 8],
        "experts_held": {"first": 4, "count": 4}}, **fields)


def _kimi_linear(**fields):
    """``tests/test_kimi_linear_model.py``'s toy cut to a KDA layer with a
    dense MLP and a latent layer with experts."""
    return KimiLinearLM.from_config({
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "rms_norm_eps": 1e-5,
        "linear_attn_config": {
            "kda_layers": [1], "full_attn_layers": [2], "head_dim": 32,
            "num_heads": 2, "short_conv_kernel_size": 4},
        "first_k_dense_replace": 1, "kv_lora_rank": 48,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "num_experts": 16, "num_experts_per_token": 4,
        "routed_scaling_factor": 2.446,
        "experts_held": {"first": 4, "count": 4}}, **fields)


def _olmo_hybrid(**fields):
    """``tests/test_olmo_hybrid_model.py``'s toy cut to a delta-rule layer
    and a full-attention one, 3 of 6 heads held."""
    return OlmoHybridLM.from_config({
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "head_dim": 16,
        "num_attention_heads": 6, "num_key_value_heads": 6,
        "linear_num_key_heads": 6, "linear_num_value_heads": 6,
        "linear_key_head_dim": 16, "linear_value_head_dim": 32,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "layer_types": ["linear_attention", "full_attention"],
        "heads_held": {"first": 0, "count": 3}}, **fields)


def _resnet():
    return ResNet(stage_sizes=[1, 1], num_filters=8, num_classes=10,
                  block_cls=BottleneckResNetBlock)


MODELS = {"transformer": _transformer, "laguna": _laguna,
          "kimi_linear": _kimi_linear, "olmo_hybrid": _olmo_hybrid,
          "resnet": _resnet}
LMS = ("transformer", "laguna", "kimi_linear", "olmo_hybrid")
TOKENS = jnp.zeros((2, 128), jnp.int32)
IMAGES = jnp.zeros((2, 32, 32, 3), jnp.float32)


def _variables(name, model):
    if name == "resnet":
        return jax.eval_shape(model.init, jax.random.PRNGKey(0), IMAGES)
    return jax.eval_shape(model.init, jax.random.PRNGKey(0), TOKENS)


@functools.lru_cache(maxsize=None)
def _op_names(name, remat=False):
    """Every ``op_name`` of the model's train step as the program issued
    it (``DistributedOptimizer`` over one device), before XLA."""
    import horovod_tpu as hvd

    hvd.init()
    try:
        mesh = hvd.parallel.data_parallel_mesh(jax.devices()[:1])
        model = MODELS[name](remat=True) if remat else MODELS[name]()
        variables = _variables(name, model)
        if name == "resnet":
            opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                           axis_name="data")
            lowered = make_dp_train_step(model, opt, mesh).lower(
                variables["params"], jax.eval_shape(
                    opt.init, variables["params"]),
                variables["batch_stats"], IMAGES,
                jnp.zeros((2,), jnp.int32))
        else:
            opt = hvd.DistributedOptimizer(optax.adamw(3e-4),
                                           axis_name="data")
            lowered = make_lm_train_step(model, opt, mesh).lower(
                variables["params"], jax.eval_shape(
                    opt.init, variables["params"]), TOKENS)
    finally:
        hvd.shutdown()
    return frozenset(re.findall(
        r'op_name="([^"]*)"', lowered.as_text(dialect="hlo",
                                              debug_info=True)))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_every_scope_occurs_forward_and_backward(name):
    names = _op_names(name)
    declared = SIX if name in LMS else (scopes.NORM, scopes.HEAD)
    for scope in declared:
        under = [n for n in names if f"/{scope}/" in n]
        assert any(BACKWARD in n for n in under), scope
        assert any(FORWARD in n and BACKWARD not in n for n in under), scope
    # what the issue gave no owner in ResNet stays without one
    if name == "resnet":
        assert not any(s in n for n in names
                       for s in (scopes.EMBED, scopes.MIXER, scopes.MLP))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_no_operation_has_two_owners(name):
    """The six are set at call sites and never nest: ``hvd.mixer.proj``
    lies inside ``hvd.mixer`` and nothing else inside anything. (A path
    may hold one scope twice: a transformation inside a transformation
    writes the stack again.)"""
    for n in _op_names(name):
        owners = set(_COMPONENT.findall(n))
        assert len(owners) <= 1, n
        if scopes.MIXER_PROJ in n:
            assert owners == {scopes.MIXER}, n
            assert n.index(scopes.MIXER + "/") < n.index(scopes.MIXER_PROJ)
        # an expert layer is no dense MLP's and no mixer's
        if "hvd.moe" in n:
            assert not owners, n


@pytest.mark.parametrize("name", ["laguna", "kimi_linear"])
def test_the_shared_expert_is_the_expert_layers(name):
    shared = [n for n in _op_names(name) if "/shared/w" in n]
    for w in ("w1", "w2", "w3"):
        mine = [n for n in shared if f"/shared/{w}/" in n]
        assert any(BACKWARD in n for n in mine), w
        assert all("hvd.moe.experts" in n and scopes.MLP not in n
                   for n in mine), w
    # and the dense MLP's own three are the dense MLP's
    dense = [n for n in _op_names(name) if re.search(r"/mlp/w[123]/", n)]
    assert dense and all(f"/{scopes.MLP}/mlp/" in n and "hvd.moe" not in n
                         for n in dense)


@pytest.mark.parametrize("name", LMS)
def test_a_recomputed_block_keeps_its_scopes(name):
    recomputed = [n for n in _op_names(name, remat=True)
                  if "rematted_computation" in n]
    assert recomputed
    for scope in (scopes.NORM, scopes.MIXER, scopes.MIXER_PROJ):
        assert any(f"/{scope}/" in n for n in recomputed), scope
    assert any(f"/{scopes.MLP}/" in n or "/hvd.moe/" in n
               for n in recomputed)
    # every operation of a block has an owner, recomputed or not, but the
    # expert layer's residual add and the two reshapes round its scope (a
    # Laguna block's two checkpoints are named ``block_<i>.mix`` / ``.feed``)
    unowned = {re.sub(r".*/block_\d+(\.\w+)?/", "", n) for n in recomputed
               if re.search(r"/block_\d+/", n)
               and not _COMPONENT.search(n) and "hvd.moe" not in n}
    assert unowned <= {"add", "add_any", "moe/reshape"}, unowned


def _dense(prefix, *names, leaves=("kernel",)):
    return [f"{prefix}/{n}/{leaf}" for n in names for leaf in leaves]


_MOE = ["experts_w1", "experts_w2", "experts_w3", "router/kernel",
        "shared/w1/kernel", "shared/w2/kernel", "shared/w3/kernel"]
_GATED = ["w1/kernel", "w2/kernel", "w3/kernel"]
_LM_ENDS = ["ln_final/scale", "lm_head/kernel", "tok_embed/embedding"]
_BOTTLENECK = (
    [f"BatchNorm_{i}/{leaf}" for i in range(3) for leaf in ("bias", "scale")]
    + [f"Conv_{i}/kernel" for i in range(3)]
    + ["conv_proj/kernel", "norm_proj/bias", "norm_proj/scale"])
PATHS = {
    "transformer": (
        _dense("block_0/attn", "query", "key", "value", "out",
               leaves=("kernel", "bias"))
        + _dense("block_0", "mlp_in", "mlp_out", leaves=("kernel", "bias"))
        + _dense("block_0", "ln_attn", "ln_mlp", leaves=("scale", "bias"))
        + ["ln_final/scale", "ln_final/bias", "lm_head/kernel",
           "lm_head/bias", "tok_embed/embedding", "pos_embed/embedding"]),
    "laguna": (
        [f"block_{i}/{leaf}" for i in (0, 1) for leaf in
         _dense("attn", "query", "key", "value", "gate", "out")
         + ["ln_attn/scale", "ln_mlp/scale"]]
        + [f"block_0/mlp/{leaf}" for leaf in _GATED]
        + [f"block_1/moe/{leaf}" for leaf in _MOE] + _LM_ENDS),
    "kimi_linear": (
        _dense("block_0/kda", "query", "key", "value", "decay_a", "decay_b",
               "beta", "gate_a", "gate_b", "out")
        + [f"block_0/kda/{leaf}" for leaf in (
            "conv_q", "conv_k", "conv_v", "decay_rate", "decay_bias",
            "out_norm/scale")]
        + _dense("block_1/mla", "query", "kv_a", "kv_b", "out")
        + ["block_1/mla/kv_norm/scale"]
        + [f"block_{i}/{n}/scale" for i in (0, 1)
           for n in ("ln_attn", "ln_mlp")]
        + [f"block_0/mlp/{leaf}" for leaf in _GATED]
        + [f"block_1/moe/{leaf}" for leaf in _MOE] + _LM_ENDS),
    "olmo_hybrid": (
        _dense("block_0/gdn", "query", "key", "value", "decay", "beta",
               "gate", "out")
        + [f"block_0/gdn/{leaf}" for leaf in (
            "conv_q", "conv_k", "conv_v", "A_log", "dt_bias",
            "out_norm/scale")]
        + _dense("block_1/attn", "query", "key", "value", "out")
        + ["block_1/attn/q_norm/scale", "block_1/attn/k_norm/scale"]
        + [f"block_{i}/{n}/scale" for i in (0, 1)
           for n in ("ln_attn", "ln_mlp")]
        + [f"block_{i}/mlp/{leaf}" for i in (0, 1) for leaf in _GATED]
        + _LM_ENDS),
    "resnet": (
        [f"BottleneckResNetBlock_{i}/{leaf}" for i in (0, 1)
         for leaf in _BOTTLENECK]
        + ["bn_init/bias", "bn_init/scale", "conv_init/kernel",
           "Dense_0/kernel", "Dense_0/bias"]),
}
_MOE_STATS = [f"block_1/moe/{name}/0" for name in (
    "absent", "assignments", "ran", "slices", "slots", "summed")]
COLLECTIONS = {     # what ``init`` leaves beside ``params``
    "transformer": {},
    "laguna": {"moe_stats": _MOE_STATS},
    "kimi_linear": {"moe_stats": _MOE_STATS, "kda_stats": [
        "block_0/kda/mean_decay/0", "block_0/kda/state_max/0"]},
    "olmo_hybrid": {"gdn_stats": [
        "block_0/gdn/beta_above_one/0", "block_0/gdn/mean_decay/0",
        "block_0/gdn/state_max/0"]},
    "resnet": {"batch_stats": [
        f"BottleneckResNetBlock_{i}/{n}/{leaf}" for i in (0, 1)
        for n in ("BatchNorm_0", "BatchNorm_1", "BatchNorm_2", "norm_proj")
        for leaf in ("mean", "var")] + ["bn_init/mean", "bn_init/var"]},
}


def _paths(tree):
    return sorted("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                           for k in path) for path, _ in
                  jax.tree_util.tree_leaves_with_path(tree))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_parameter_tree_is_what_it_was(name):
    variables = dict(_variables(name, MODELS[name]()))
    assert _paths(variables.pop("params")) == sorted(PATHS[name])
    assert {c: _paths(t) for c, t in variables.items()} \
        == {c: sorted(t) for c, t in COLLECTIONS[name].items()}
