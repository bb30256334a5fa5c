"""Family ``laguna``: decoder-only LM with RMSNorm, rotary positions
(plain on sliding layers, YaRN on half of each head on full ones), grouped
key/value heads, a head count and an attention type per layer, a head-wise
output gate, gated SiLU MLPs and, after a leading dense layer, routed
experts with one shared expert (poolside/Laguna-XS.2's ``config.json``).

The configuration is one chip's share of a deployment in which
``deployment.chips_sharing_a_layer`` chips share each layer: ``num_experts``
of the ``deployment.num_experts`` routed experts and a slice of the
vocabulary live here; the router keeps its width and its experts per
token. What absent experts would add is left out, in the program and in
the reference alike.
The router's gradient is formed, timed and compared like any other, but
its update is withheld while experts are absent (``router_frozen``): the
routed sum is the router's only way into the loss, so what one chip can
form is its part of a sum over the chips that share the layer, and applied
alone it draws the routing to the held experts within a few steps.

What the harness takes from a family file is listed in ``gpt2.py``. The
reference imports nothing of the program: it is ``jax.numpy`` in float32
over the parameter tree that this file itself lays out — dense masked
attention a block of query rows at a time, a loop over the held experts,
its own AdamW — one sequence at a time, the moments kept on the host
between updates, so that it fits beside the weights and their gradient.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp

from chipbench import numerics

HEAD_ROWS = 256      # query rows of dense attention computed at once

# -- the program, through its public surface --------------------------------


def build(config):
    """The program's model for ``config``."""
    from horovod_tpu.models import LagunaLM

    first, count = held(config)
    published = dict(config, num_experts=routed_over(config),
                     experts_held={"first": first, "count": count})
    return LagunaLM.from_config(
        published, attention=config["attention"], remat=config["remat"],
        dtype=jnp.dtype(config["precision"]["compute"]))


def router_frozen(config) -> bool:
    """Whether the routers' update is withheld: while experts are absent."""
    return held(config)[1] < routed_over(config)


def _routers(tree):
    """``tree``'s shape with True at the routers' leaves."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: any(getattr(k, "key", None) == "router"
                            for k in path), tree)


def optimizer(config):
    """The optax transformation the configuration states, unwrapped; where
    the router is frozen its leaves' update (moments kept) is set to zero
    after AdamW has formed it."""
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"family laguna trains with adamw, not "
                         f"{o['name']!r}")
    adamw = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                        eps=o["eps"], weight_decay=o["weight_decay"])
    if not router_frozen(config):
        return adamw
    return optax.chain(adamw, optax.masked(optax.set_to_zero(), _routers))


def make_step(model, opt, mesh):
    """``step(params, opt_state, tokens) -> (params, opt_state, loss)``."""
    from benchmarks._dp_step import make_lm_train_step

    return make_lm_train_step(model, opt, mesh, axis_name="data")


def assemble(model_state, opt_state):
    """The step's state arguments, in its order."""
    (params,) = model_state
    return (params, opt_state)


def first_gradient(opt_state, config):
    """The gradient the optimizer was given at its first update, worked
    out from its state after that one step: Adam's first moment starts at
    zero, so it is then ``(1 - b1) * g``."""
    adamw = opt_state.inner[0] if router_frozen(config) else opt_state.inner
    mu = adamw[0].mu
    return jax.tree_util.tree_map(
        lambda m: m / (1.0 - config["optimizer"]["b1"]), mu)


# -- the configuration's shape ----------------------------------------------


def layers(config) -> list:
    """``[(attention type, query heads, mlp type), ...]`` of the layers
    this configuration keeps: the leading ``num_hidden_layers``."""
    depth = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:depth],
                    config["num_attention_heads_per_layer"][:depth],
                    config["mlp_layer_types"][:depth]))


def held(config) -> tuple:
    """``(first id, count)`` of the routed experts held here:
    ``num_experts`` of the file, which is this chip's share."""
    return config["deployment"]["experts_held_first"], config["num_experts"]


def routed_over(config) -> int:
    """The experts the router chooses among: the published count."""
    return config["deployment"]["num_experts"]


def yarn_inv_freq(p, dim: int):
    """Inverse frequencies ``[dim // 2]`` of one ``rope_parameters`` entry
    over ``dim`` rotated dims, as the ``transformers`` library computes
    them: plain rotary, or YaRN's blend of interpolated and extrapolated
    frequencies between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations."""
    theta = p["rope_theta"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extrapolated = theta ** (-2.0 * i / dim)
    if p.get("rope_type", "default") == "default":
        return extrapolated
    length = p["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(length / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(p["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / (high - low if high > low else 0.001), 0, 1)
    return extrapolated / p["factor"] * ramp + extrapolated * (1 - ramp)


# -- seeded weights and batches (the benchmark's own) -----------------------


def init_model_state(config, key):
    """``(params,)`` in the layout of ``build(config)``'s flax tree: normal
    (0, 0.02) matrices and embeddings, unit RMSNorm scales, all float32,
    no bias anywhere. Traced inside one jitted call by the harness."""
    d, dh, kv = config["hidden_size"], config["head_dim"], \
        config["num_key_value_heads"]
    vocab, count = config["vocab_size"], held(config)[1]
    width, shared = config["moe_intermediate_size"], \
        config["shared_expert_intermediate_size"]
    counter = iter(range(1 << 30))

    def matrix(*shape):
        return 0.02 * jax.random.normal(
            jax.random.fold_in(key, next(counter)), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": matrix(*shape)}

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32)}

    def mlp(inner):
        return {"w1": kernel(d, inner), "w3": kernel(d, inner),
                "w2": kernel(inner, d)}

    params = {"tok_embed": {"embedding": matrix(vocab, d)}}
    for i, (_, heads, kind) in enumerate(layers(config)):
        block = {
            "ln_attn": norm(),
            "attn": {"query": kernel(d, heads, dh), "key": kernel(d, kv, dh),
                     "value": kernel(d, kv, dh), "gate": kernel(d, heads),
                     "out": kernel(heads, dh, d)},
            "ln_mlp": norm()}
        if kind == "dense":
            block["mlp"] = mlp(config["intermediate_size"])
        else:
            block["moe"] = {
                "router": kernel(d, routed_over(config)),
                "experts_w1": matrix(count, d, width),
                "experts_w3": matrix(count, d, width),
                "experts_w2": matrix(count, width, d),
                "shared": mlp(shared)}
        params[f"block_{i}"] = block
    params["ln_final"] = norm()
    params["lm_head"] = kernel(d, vocab)
    return (params,)


def make_pool(config, traffic, key):
    """``pool`` batches, each a tuple of the step's data arguments: uniform
    random tokens ``[global_batch, seq]`` over the vocabulary slice. Every
    row differs."""
    (seq,) = traffic["sample_shape"]
    return [(jax.random.randint(k, (traffic["global_batch"], seq), 0,
                                config["vocab_size"], dtype=jnp.int32),)
            for k in jax.random.split(key, traffic["pool"])]


def data_spec(batch_axis):
    """PartitionSpec entries of one batch's arrays."""
    from jax.sharding import PartitionSpec as P

    return (P(batch_axis),)


# -- shape functions --------------------------------------------------------


def matmul_parameters(config) -> int:
    """Parameters one token's activations are multiplied by on this chip:
    each layer's attention (q, k, v, out, gate), the dense MLP or the
    router, the shared expert and the *expected* share of the routed
    experts — ``experts_per_token * held / num_experts`` experts a token,
    an expectation under a router that spreads tokens evenly — and the
    head over the vocabulary slice. Embedding look-ups and norms do no
    matmul."""
    d, dh, kv = config["hidden_size"], config["head_dim"], \
        config["num_key_value_heads"]
    expert = 3 * d * config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * held(config)[1] \
        / routed_over(config)
    total = d * config["vocab_size"]
    for _, heads, kind in layers(config):
        total += 2 * d * heads * dh + 2 * d * kv * dh + d * heads
        if kind == "dense":
            total += 3 * d * config["intermediate_size"]
        else:
            total += d * routed_over(config) + routed * expert \
                + 3 * d * config["shared_expert_intermediate_size"]
    return int(total)


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs the causal mask keeps, the diagonal included;
    under a window, ``sum over t of min(t + 1, window)``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _pairs_of(config, kind: str, seq: int) -> int:
    return visible_pairs(seq, config["sliding_window"]
                         if kind == "sliding_attention" else None)


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one sequence on this chip: 2 per
    multiply-add, the backward pass twice the forward, so 6 per matmul
    parameter a token meets (``matmul_parameters``); attention's two
    products over the pairs each layer type keeps visible. Nothing for
    recomputation, the optimizer, norms, rotary, softmax, routing's sort
    or the embedding look-up."""
    (seq,) = traffic["sample_shape"]
    attention = sum(3 * 2 * 2 * config["head_dim"] * heads
                    * _pairs_of(config, kind, seq)
                    for kind, heads, _ in layers(config))
    return 6.0 * matmul_parameters(config) * seq + attention


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """FLOPs and HBM bytes one chip's step needs from each kernel.

    ``flash_win`` / ``flash_full``: the Pallas calls of the sliding / full
    layers. Needed products per visible pair and query head, ``2 *
    head_dim`` FLOPs each: FlashAttention-2's seven (``gpt2.kernel_work``).
    Needed bytes: q, o (forward) and q, o, dO, dQ (backward) at the query
    heads' width, k, v and k, v, dK, dV once a group at the key/value
    heads', in the compute type; the row statistics left out. ``calls``
    counts the compiled step's custom calls: with each block recomputed
    the forward kernel runs twice a layer.

    ``expert_matmul``: the grouped products of the held experts, forward
    and backward (each of the three matrices: the product, its input's
    gradient, its weight's gradient). FLOPs from the *expected* rows,
    ``tokens * experts_per_token * held / num_experts`` a layer. Bytes:
    each product's rows in and out, and the held experts' weights read
    (forward, input gradient) or written (weight gradient) once a pass."""
    (seq,) = traffic["sample_shape"]
    width = jnp.dtype(config["precision"]["compute"]).itemsize
    dh, kv = config["head_dim"], config["num_key_value_heads"]
    d, inner = config["hidden_size"], config["moe_intermediate_size"]
    count = held(config)[1]
    calls_a_layer = 4 if config["remat"] else 3
    work = {name: {"flops": 0.0, "bytes": 0.0, "calls": 0}
            for name in ("flash_win", "flash_full", "expert_matmul")}
    rows = per_chip_batch * seq * config["num_experts_per_tok"] * count \
        / routed_over(config)
    for kind, heads, mlp in layers(config):
        flash = work["flash_win" if kind == "sliding_attention"
                     else "flash_full"]
        flash["flops"] += 7.0 * 2 * dh * heads * per_chip_batch \
            * _pairs_of(config, kind, seq)
        flash["bytes"] += 6.0 * per_chip_batch * seq * (heads + kv) * dh \
            * width
        flash["calls"] += calls_a_layer
        if mlp != "dense":
            experts = work["expert_matmul"]
            experts["flops"] += 3 * 3 * 2.0 * d * inner * rows
            experts["bytes"] += 3 * 3 * width * (rows * (d + inner)
                                                 + count * d * inner)
    return {name: w for name, w in work.items() if w["flops"]}


# -- the plain reference ----------------------------------------------------


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _rotate(x, rope, positions):
    """Rotary positions on ``x`` [T, H, D]: the leading
    ``partial_rotary_factor`` of each head, in halves; cos and sin scaled
    by ``attention_factor`` (YaRN)."""
    dim = int(x.shape[-1] * rope.get("partial_rotary_factor", 1))
    angles = positions[:, None].astype(jnp.float32) * yarn_inv_freq(rope, dim)
    factor = rope.get("attention_factor", 1.0)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :] * factor
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :] * factor
    turned, kept = x[..., :dim], x[..., dim:]
    half = jnp.concatenate([-turned[..., dim // 2:], turned[..., :dim // 2]],
                           -1)
    return jnp.concatenate([turned * cos + half * sin, kept], -1)


def _attention(q, k, v, window, num):
    """Dense masked attention of one sequence, ``HEAD_ROWS`` query rows
    against every key at a time. q [T, H, D]; k, v [T, Hkv, D]."""
    seq, heads, dh = q.shape
    k, v = (jnp.repeat(x, heads // k.shape[1], axis=1) for x in (k, v))
    rows = min(HEAD_ROWS, seq)
    product = num.product

    @jax.checkpoint
    def block(q_rows, first):
        scores = product(functools.partial(jnp.einsum, "qhd,khd->hqk"),
                         q_rows / jnp.sqrt(jnp.float32(dh)), k)
        distance = (first + jnp.arange(rows))[:, None] - jnp.arange(seq)
        keep = distance >= 0
        if window is not None:
            keep = keep & (distance < window)
        weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return product(functools.partial(jnp.einsum, "hqk,khd->qhd"),
                       weights, v)

    out = jax.lax.map(lambda a: block(*a), (
        q.reshape(seq // rows, rows, heads, dh),
        jnp.arange(0, seq, rows)))
    return out.reshape(seq, heads, dh)


def _gated_mlp(p, h, num):
    product = num.product
    inner = jax.nn.silu(product(jnp.matmul, h, p["w1"]["kernel"])) \
        * product(jnp.matmul, h, p["w3"]["kernel"])
    return product(jnp.matmul, inner, p["w2"]["kernel"])


def _experts(p, h, config, num):
    """Router in float32 over every expert, the ``num_experts_per_tok``
    largest sigmoid scores normalised to sum 1 and scaled; the shared
    expert, and a loop over the held experts, each on every token with the
    token's weight for it (zero where it was not selected)."""
    first, count = held(config)
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    top, ids = jax.lax.top_k(scores, config["num_experts_per_tok"])
    weights = config["moe_routed_scaling_factor"] * top \
        / jnp.sum(top, -1, keepdims=True)
    product = num.product

    @jax.checkpoint
    def weighted(expert):
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
        out = product(jnp.matmul, jax.nn.silu(product(jnp.matmul, h, w1))
                      * product(jnp.matmul, h, w3), w2)
        return weight[:, None] * out

    # the sum is carried outside the checkpoint: nothing of it is kept
    routed, _ = jax.lax.scan(
        lambda total, expert: (total + weighted(expert), None),
        jnp.zeros_like(h), (jnp.arange(count), p["experts_w1"],
                            p["experts_w3"], p["experts_w2"]))
    return _gated_mlp(p["shared"], h, num) + routed


def _block(p, x, positions, config, kind, num):
    """One block on one sequence ``x`` [T, d]."""
    eps, product = config["rms_norm_eps"], num.product
    sliding = kind == "sliding_attention"
    rope = config["rope_parameters"][kind]
    h = _rms_norm(x, p["ln_attn"], eps)
    a = p["attn"]

    def heads(w):
        return product(functools.partial(jnp.einsum, "td,dhk->thk"), h,
                       w["kernel"])

    q = _rotate(heads(a["query"]), rope, positions)
    k = _rotate(heads(a["key"]), rope, positions)
    mixed = _attention(q, k, heads(a["value"]),
                       config["sliding_window"] if sliding else None, num)
    gate = jax.nn.sigmoid(product(jnp.matmul, h, a["gate"]["kernel"]))
    x = x + product(functools.partial(jnp.einsum, "thk,hkd->td"),
                    mixed * gate[..., None], a["out"]["kernel"])
    h = _rms_norm(x, p["ln_mlp"], eps)
    if "mlp" in p:
        return x + _gated_mlp(p["mlp"], h, num)
    return x + _experts(p["moe"], h, config, num)


def reference_loss(params, tokens, config, num=numerics.Exact):
    """Mean next-token cross entropy of ``tokens`` [B, T] in float32, one
    sequence at a time (all are equally long, so the mean of their means
    is the batch mean). The blocks' products go through ``num`` (the
    configuration computes them in bfloat16); the router and the output
    head stay float32, as the configuration states. Each block is
    rematerialised."""
    positions = jnp.arange(tokens.shape[1])

    @jax.checkpoint
    def sequence(row):
        x = params["tok_embed"]["embedding"][row]
        for i, (kind, _, _) in enumerate(layers(config)):
            x = jax.checkpoint(functools.partial(
                _block, config=config, kind=kind, num=num))(
                    params[f"block_{i}"], x, positions)
        x = _rms_norm(x, params["ln_final"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(x[:-1] @ params["lm_head"]["kernel"], -1)
        return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], -1))

    total, _ = jax.lax.scan(lambda c, row: (c + sequence(row), None),
                            jnp.float32(0.0), tokens)
    return total / tokens.shape[0]


def _adamw(params, grad, mu, nu, count, o, frozen: bool):
    """One AdamW update written out (Loshchilov & Hutter 2019, as optax
    composes it: bias-corrected moments, decoupled decay on every leaf).
    ``frozen``: the routers keep their moments and stay where they are."""
    def leaf(p, g, m, v, router):
        m = o["b1"] * m + (1.0 - o["b1"]) * g
        v = o["b2"] * v + (1.0 - o["b2"]) * jnp.square(g)
        m_hat = m / (1.0 - o["b1"] ** count)
        v_hat = v / (1.0 - o["b2"] ** count)
        step = m_hat / (jnp.sqrt(v_hat) + o["eps"]) + o["weight_decay"] * p
        return (p if frozen and router else p - o["learning_rate"] * step,
                m, v)

    out = jax.tree_util.tree_map(leaf, params, grad, mu, nu,
                                 _routers(params))
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, t: t[i], params, out)
    return pick(0), pick(1), pick(2)


def _to_host(tree):
    """``tree`` as numpy arrays, its device buffers freed."""
    on_host = jax.device_get(tree)
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.delete()
    return on_host


def reference_run(config, traffic, keys, steps: int, precision="float32"):
    """The reference trainer on one device: seeded weights, the first
    ``steps`` batches of the pool, the global batch, AdamW written out.
    Returns what ``correct`` compares: each step's loss, the norm of the
    first gradient and of the parameters' change after ``steps``, leaf by
    leaf. ``precision`` ``"fp8"`` is the control. The seeded weights are
    made again at the end rather than kept."""
    num = numerics.NUMERICS[precision]
    weight_key, pool_key = keys
    with jax.default_matmul_precision("highest"):
        init = jax.jit(functools.partial(init_model_state, config))
        (params,) = init(weight_key)
        pool = jax.jit(functools.partial(make_pool, config, traffic))(
            pool_key)
        started = time.perf_counter()
        grad_fn = jax.jit(jax.value_and_grad(functools.partial(
            reference_loss, config=config, num=num))).lower(
                params, pool[0][0]).compile()
        compile_s = time.perf_counter() - started
        update = jax.jit(functools.partial(_adamw, o=config["optimizer"],
                                           frozen=router_frozen(config)),
                         donate_argnums=(0, 2, 3))
        # the moments wait on the host between updates: weights, gradient
        # and a sequence's activations are all that fits at 8k
        mu = nu = None
        losses, grad_norms = [], None
        for i in range(steps):
            loss, grad = grad_fn(params, pool[i % len(pool)][0])
            losses.append(float(loss))
            if i == 0:
                grad_norms = numerics.leaf_norms(grad)
                mu, nu = (jax.tree_util.tree_map(jnp.zeros_like, grad)
                          for _ in range(2))
            params, mu, nu = update(params, grad, *jax.device_put((mu, nu)),
                                    float(i + 1))
            del grad
            mu, nu = _to_host((mu, nu))
        del mu, nu
        update_norms = numerics.difference_norms(params, init(weight_key)[0])
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "compile_s": compile_s}
