"""Family ``resnet``: bottleneck residual networks for image
classification (He et al. 2015), batch-normalised, trained with SGD and
momentum on synthetic images — the reference framework's own benchmark
protocol (``examples/pytorch_synthetic_benchmark.py``).

Same surface as every family file (see ``gpt2.py``): the program's model
and step builder, seeded weights and batches, the shape function behind
``mfu_pct``, and a plain float32 reference trainer that imports nothing of
the program.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp

from chipbench import numerics

# -- the program, through its public surface --------------------------------


def build(config):
    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import BottleneckResNetBlock

    if config["bottleneck_expansion"] != 4:
        raise ValueError("models/resnet.py fixes the bottleneck expansion "
                         "at 4")
    return ResNet(stage_sizes=list(config["stage_sizes"]),
                  block_cls=BottleneckResNetBlock,
                  num_classes=config["num_classes"],
                  num_filters=config["num_filters"])


def optimizer(config):
    import optax

    o = config["optimizer"]
    if o["name"] != "sgd":
        raise ValueError(f"family resnet trains with sgd, not {o['name']!r}")
    return optax.sgd(o["learning_rate"], momentum=o["momentum"])


def make_step(model, opt, mesh):
    """``step(params, opt_state, batch_stats, images, labels) ->
    (params, opt_state, batch_stats, loss)``."""
    from benchmarks._dp_step import make_dp_train_step

    return make_dp_train_step(model, opt, mesh, axis_name="data")


def assemble(model_state, opt_state):
    params, batch_stats = model_state
    return (params, opt_state, batch_stats)


def first_gradient(opt_state, config):
    """Momentum's trace starts at zero, so after one update it is the
    gradient that update was given."""
    del config
    return opt_state.inner[0].trace


# -- seeded weights and batches ---------------------------------------------


def _convolutions(config):
    """Every convolution in forward order as ``(path, kernel_side, c_in,
    c_out, stride, input_side)``; ``path`` is its place in the flax tree
    of ``build(config)``."""
    side = config["image_side"]
    f = config["num_filters"]
    out = [(("conv_init",), 7, config["image_channels"], f, 2, side)]
    side = math.ceil(math.ceil(side / 2) / 2)  # stride-2 stem, 3x3/2 pool
    c_in, block = f, 0
    for stage, n_blocks in enumerate(config["stage_sizes"]):
        width = f * 2 ** stage
        for j in range(n_blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            name = f"BottleneckResNetBlock_{block}"
            c_out = width * config["bottleneck_expansion"]
            out.append(((name, "Conv_0"), 1, c_in, width, 1, side))
            out.append(((name, "Conv_1"), 3, width, width, stride, side))
            after = math.ceil(side / stride)
            out.append(((name, "Conv_2"), 1, width, c_out, 1, after))
            if stride != 1 or c_in != c_out:
                out.append(((name, "conv_proj"), 1, c_in, c_out, stride,
                            side))
            side, c_in, block = after, c_out, block + 1
    return out


_NORM_OF = {"conv_init": "bn_init", "Conv_0": "BatchNorm_0",
            "Conv_1": "BatchNorm_1", "Conv_2": "BatchNorm_2",
            "conv_proj": "norm_proj"}


def _set(tree, path, value):
    for name in path[:-1]:
        tree = tree.setdefault(name, {})
    tree[path[-1]] = value


def init_model_state(config, key):
    """``(params, batch_stats)`` in the layout of ``build(config)``'s flax
    tree, float32: He-normal (fan-out) convolutions; BatchNorm scale 1 and
    bias 0, but scale 0 on the last BatchNorm of every block, so that each
    block starts as the identity (Goyal et al. 2017, and what
    ``models/resnet.py`` itself initialises); running mean 0 and variance
    1; uniform classifier."""
    convs = _convolutions(config)
    keys = iter(jax.random.split(key, len(convs) + 2))
    params, stats = {}, {}
    for path, k, c_in, c_out, _, _ in convs:
        std = math.sqrt(2.0 / (k * k * c_out))
        _set(params, path + ("kernel",), std * jax.random.normal(
            next(keys), (k, k, c_in, c_out), jnp.float32))
        norm = path[:-1] + (_NORM_OF[path[-1]],)
        last_of_block = path[-1] == "Conv_2"
        _set(params, norm + ("scale",), jnp.full(
            (c_out,), 0.0 if last_of_block else 1.0, jnp.float32))
        _set(params, norm + ("bias",), jnp.zeros((c_out,), jnp.float32))
        _set(stats, norm + ("mean",), jnp.zeros((c_out,), jnp.float32))
        _set(stats, norm + ("var",), jnp.ones((c_out,), jnp.float32))
    features = convs[-1][3] if convs[-1][0][-1] != "conv_proj" \
        else convs[-2][3]
    bound = 1.0 / math.sqrt(features)
    params["Dense_0"] = {
        "kernel": jax.random.uniform(
            next(keys), (features, config["num_classes"]), jnp.float32,
            -bound, bound),
        "bias": jax.random.uniform(
            next(keys), (config["num_classes"],), jnp.float32, -bound,
            bound)}
    return (params, stats)


def make_pool(config, traffic, key):
    """``pool`` batches of ``(images, labels)``: standard-normal images
    ``[global_batch, side, side, channels]`` and uniform labels."""
    shape = tuple(traffic["sample_shape"])
    if shape != (config["image_side"], config["image_side"],
                 config["image_channels"]):
        raise ValueError(f"traffic sends {shape} images, the configuration "
                         f"takes {config['image_side']} squared")
    batches = []
    for k in jax.random.split(key, traffic["pool"]):
        ki, kl = jax.random.split(k)
        batches.append((
            jax.random.normal(ki, (traffic["global_batch"],) + shape,
                              jnp.float32),
            jax.random.randint(kl, (traffic["global_batch"],), 0,
                               config["num_classes"], dtype=jnp.int32)))
    return batches


def data_spec(batch_axis):
    from jax.sharding import PartitionSpec as P

    return (P(batch_axis), P(batch_axis))


# -- shape functions --------------------------------------------------------


def forward_flops(config) -> float:
    """FLOPs of one image's forward pass, 2 per multiply-add, in the
    convolutions and the classifier alone. A SAME or stem-padded
    convolution of stride s over a side-n input has ceil(n / s) squared
    output positions."""
    total = 0
    for _, k, c_in, c_out, stride, side in _convolutions(config):
        total += 2 * k * k * c_in * c_out * math.ceil(side / stride) ** 2
    features = config["num_filters"] * 2 ** (len(config["stage_sizes"]) - 1) \
        * config["bottleneck_expansion"]
    return float(total + 2 * features * config["num_classes"])


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one image: three times the forward
    pass's convolution and classifier FLOPs. Nothing for BatchNorm, ReLU,
    pooling, the residual adds or the optimizer."""
    del traffic
    return 3.0 * forward_flops(config)


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """No kernel of the program runs in this family."""
    del config, traffic, per_chip_batch
    return {}


# -- the plain reference ----------------------------------------------------


def _conv(x, kernel, stride, padding, num):
    return num.product(
        lambda a, b: jax.lax.conv_general_dilated(
            a, b, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC")), x, kernel)


def _batch_norm(x, p, eps):
    """Training mode: the batch's own mean and biased variance."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(p, x, stride, eps, num):
    y = _conv(x, p["Conv_0"]["kernel"], 1, "SAME", num)
    y = jax.nn.relu(_batch_norm(y, p["BatchNorm_0"], eps))
    y = _conv(y, p["Conv_1"]["kernel"], stride, "SAME", num)
    y = jax.nn.relu(_batch_norm(y, p["BatchNorm_1"], eps))
    y = _conv(y, p["Conv_2"]["kernel"], 1, "SAME", num)
    y = _batch_norm(y, p["BatchNorm_2"], eps)
    if "conv_proj" in p:
        x = _conv(x, p["conv_proj"]["kernel"], stride, "SAME", num)
        x = _batch_norm(x, p["norm_proj"], eps)
    return jax.nn.relu(x + y)


def reference_loss(params, images, labels, config, num=numerics.Exact):
    """Mean cross entropy of the batch in float32, BatchNorm in training
    mode over the whole batch. Convolutions go through ``num`` (the
    configuration computes them in bfloat16); the classifier stays
    float32. Each block is rematerialised so that the batch fits."""
    eps = config["batch_norm"]["epsilon"]
    x = _conv(images, params["conv_init"]["kernel"], 2, [(3, 3), (3, 3)],
              num)
    x = jax.nn.relu(_batch_norm(x, params["bn_init"], eps))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    block = 0
    for stage, n_blocks in enumerate(config["stage_sizes"]):
        for j in range(n_blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            x = jax.checkpoint(functools.partial(
                _bottleneck, stride=stride, eps=eps, num=num))(
                    params[f"BottleneckResNetBlock_{block}"], x)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    logits = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def _sgd_momentum(params, grad, trace, o):
    """SGD with momentum written out: t = g + momentum * t; p -= lr * t."""
    trace = jax.tree_util.tree_map(
        lambda g, t: g + o["momentum"] * t, grad, trace)
    params = jax.tree_util.tree_map(
        lambda p, t: p - o["learning_rate"] * t, params, trace)
    return params, trace


def reference_run(config, traffic, keys, steps: int, precision="float32"):
    """The reference trainer on one device, the global batch at once
    (BatchNorm ties its rows together). Returns what ``correct`` compares;
    ``precision`` ``"fp8"`` is the control."""
    num = numerics.NUMERICS[precision]
    weight_key, pool_key = keys
    with jax.default_matmul_precision("highest"):
        params0, _ = jax.jit(functools.partial(init_model_state, config))(
            weight_key)
        pool = jax.jit(functools.partial(make_pool, config, traffic))(
            pool_key)
        started = time.perf_counter()
        grad_fn = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, config=config, num=num))).lower(
                params0, *pool[0]).compile()
        compile_s = time.perf_counter() - started
        update = jax.jit(functools.partial(
            _sgd_momentum, o=config["optimizer"]), donate_argnums=(0, 2))
        params = jax.tree_util.tree_map(jnp.copy, params0)
        trace = jax.tree_util.tree_map(jnp.zeros_like, params0)
        losses, grad_norms = [], None
        for i in range(steps):
            images, labels = pool[i % len(pool)]
            loss, grad = grad_fn(params, images, labels)
            losses.append(float(loss))
            if i == 0:
                grad_norms = numerics.leaf_norms(grad)
            params, trace = update(params, grad, trace)
            del grad
        update_norms = numerics.difference_norms(params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "compile_s": compile_s}
