"""Family ``smallthinker``: a sparse-expert decoder whose router reads the
block's input from before the attention (PowerInfer/SmallThinker-21BA3B-
Instruct's ``config.json``): pre-RMSNorm blocks, grouped key/value heads,
*full* causal layers without any positions beside *window* layers that
rotate q and k over the whole head (``sliding_window_layout``,
``rope_layout``), and in every layer routed ReLU-gated experts under a
softmax router, the largest ``moe_num_active_primary_experts`` renormalised,
no shared expert. For block input ``x``::

    a   = x + Attn(RMSNorm_1(x))
    p   = softmax over the k largest of (x W_r)        # x un-normed, float32
    out = a + sum_e p_e W2_e(relu(W1_e h) * (W3_e h)),     h = RMSNorm_2(a)

The configuration is one chip's share of a deployment in which
``deployment.chips_sharing_a_layer`` chips share each layer, as family
``laguna``'s: ``moe_num_primary_experts`` of the ``deployment.num_experts``
routed experts and a slice of the vocabulary live here, the router keeps its
width and its experts per token, what absent experts would add is left out
in the program and in the reference alike, and the routers' update is
withheld while experts are absent (``laguna.router_frozen``).

What does not depend on the architecture — the optimizer with its frozen
routers, AdamW written out, the step builder, the batches, rotary positions,
the norms, dense attention a block of query rows at a time — is family
``laguna``'s, imported from the benchmark's own file; nothing here imports
the program outside ``build``. The reference is ``jax.numpy`` in float32
over the parameter tree that this file itself lays out: a loop over the
held experts, each block rematerialised, the moments on the host between
updates.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from chipbench import numerics
from chipbench.families import laguna as shared

LOSS_ROWS = 2048     # rows of float32 logits formed at once

make_step = shared.make_step
assemble = shared.assemble
make_pool = shared.make_pool
data_spec = shared.data_spec
visible_pairs = shared.visible_pairs


def _as_shared(config) -> dict:
    """``config`` with the experts held here under the key family
    ``laguna``'s functions read."""
    return dict(config, num_experts=config["moe_num_primary_experts"])


def held(config) -> tuple:
    """``(first id, count)`` of the routed experts held here."""
    return shared.held(_as_shared(config))


routed_over = shared.routed_over    # the published count: the router's width


def router_frozen(config) -> bool:
    """Whether the routers' update is withheld: while experts are absent."""
    return shared.router_frozen(_as_shared(config))


def optimizer(config):
    """AdamW as the configuration states it, the routers' update set to
    zero where they are frozen (``laguna.optimizer``)."""
    return shared.optimizer(_as_shared(config))


def first_gradient(opt_state, config):
    """The gradient the optimizer was given at its first update
    (``laguna.first_gradient``)."""
    return shared.first_gradient(opt_state, _as_shared(config))


# -- the program, through its public surface --------------------------------


def build(config):
    """The program's model for ``config``."""
    from horovod_tpu.models import SmallThinkerLM

    first, count = held(config)
    published = dict(config, moe_num_primary_experts=routed_over(config),
                     experts_held={"first": first, "count": count})
    return SmallThinkerLM.from_config(
        published, attention=config["attention"], remat=config["remat"],
        dtype=jnp.dtype(config["precision"]["compute"]))


# -- the configuration's shape ----------------------------------------------


def layers(config) -> list:
    """``[(windowed, rotated), ...]`` of the leading ``num_hidden_layers``
    layers: whether a query sees ``sliding_window_size`` keys only, and
    whether q and k carry rotary positions."""
    depth = config["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in zip(
        config["sliding_window_layout"][:depth],
        config["rope_layout"][:depth])]


# -- seeded weights (the benchmark's own) -----------------------------------


def init_model_state(config, key):
    """``(params,)`` in the layout of ``build(config)``'s flax tree: normal
    (0, 0.02) matrices, but the embedding normal(0, 1) and the two
    projections that write to the residual stream (attention's ``out``, the
    experts' ``w2``) normal(0, 0.02 / sqrt(2 * published layers)), so that a
    token's own embedding and not the mean over its context is what a
    router, which reads the un-normed stream, sees (the configuration's
    ``assumed.initializer`` has the reason and the measurement); unit
    RMSNorm scales; all float32, no bias anywhere. Traced inside one jitted
    call by the harness."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    vocab, count = config["vocab_size"], held(config)[1]
    width = config["moe_ffn_hidden_size"]
    counter = iter(range(1 << 30))

    def matrix(*shape, std=0.02):
        return std * jax.random.normal(
            jax.random.fold_in(key, next(counter)), shape, jnp.float32)

    def kernel(*shape, std=0.02):
        return {"kernel": matrix(*shape, std=std)}

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32)}

    residual = 0.02 / (2 * config["deployment"]["num_hidden_layers"]) ** 0.5
    params = {"tok_embed": {"embedding": matrix(vocab, d, std=1.0)}}
    for i in range(config["num_hidden_layers"]):
        params[f"block_{i}"] = {
            "ln_attn": norm(),
            "attn": {"query": kernel(d, heads, dh), "key": kernel(d, kv, dh),
                     "value": kernel(d, kv, dh),
                     "out": kernel(heads, dh, d, std=residual)},
            "ln_mlp": norm(),
            "moe": {"router": kernel(d, routed_over(config)),
                    "experts_w1": matrix(count, d, width),
                    "experts_w3": matrix(count, d, width),
                    "experts_w2": matrix(count, width, d, std=residual)}}
    params["ln_final"] = norm()
    params["lm_head"] = kernel(d, vocab)
    return (params,)


# -- shape functions --------------------------------------------------------


def matmul_parameters(config) -> float:
    """Parameters one token's activations are multiplied by on this chip: a
    layer's attention (q, k, v, out), its router and the *expected* share of
    the routed experts — ``experts per token * held / routed over`` experts
    a token, an expectation under a router that spreads tokens evenly — and
    the head over the vocabulary slice."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    routed = config["moe_num_active_primary_experts"] * held(config)[1] \
        / routed_over(config)
    layer = 2 * d * (heads + kv) * dh + d * routed_over(config) \
        + routed * 3 * d * config["moe_ffn_hidden_size"]
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def _pairs_of(config, windowed: bool, seq: int) -> int:
    return visible_pairs(seq,
                         config["sliding_window_size"] if windowed else None)


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one sequence on this chip, by
    ``laguna.flops_per_sample``'s convention: 6 per matmul parameter a token
    meets, attention's two products over the pairs each kind of layer keeps
    visible, forward and twice that backward. Nothing for recomputation,
    the optimizer, norms, rotary, softmax, routing's sort or the embedding
    look-up."""
    (seq,) = traffic["sample_shape"]
    pairs = sum(_pairs_of(config, windowed, seq)
                for windowed, _ in layers(config))
    return 6.0 * matmul_parameters(config) * seq + 3 * 2 * 2 \
        * config["head_dim"] * config["num_attention_heads"] * pairs


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """FLOPs and HBM bytes one chip's step needs from each kernel, as
    ``laguna.kernel_work`` counts them.

    ``flash_win`` / ``flash_full``: the Pallas calls of the window / full
    layers, FlashAttention-2's seven products of ``2 * head_dim`` FLOPs over
    the pairs the kind keeps and the query heads (a group of
    ``heads / kv heads`` a key/value head); bytes q, o (forward) and q, o,
    dO, dQ (backward) at the query heads' width, k, v and k, v, dK, dV once
    a group at the key/value heads'. ``calls``: three a layer, a recomputed
    block keeping its forward kernel's outputs.

    ``expert_matmul``: the grouped products of the held experts, forward
    and backward, at the *expected* rows a layer, ``tokens * experts per
    token * held / routed over``."""
    (seq,) = traffic["sample_shape"]
    width = jnp.dtype(config["precision"]["compute"]).itemsize
    dh, heads = config["head_dim"], config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    d, inner = config["hidden_size"], config["moe_ffn_hidden_size"]
    count = held(config)[1]
    rows = per_chip_batch * seq * config["moe_num_active_primary_experts"] \
        * count / routed_over(config)
    work = {name: {"flops": 0.0, "bytes": 0.0, "calls": 0}
            for name in ("flash_win", "flash_full", "expert_matmul")}
    for windowed, _ in layers(config):
        flash = work["flash_win" if windowed else "flash_full"]
        flash["flops"] += 7.0 * 2 * dh * heads * per_chip_batch \
            * _pairs_of(config, windowed, seq)
        flash["bytes"] += 6.0 * per_chip_batch * seq * (heads + kv) * dh \
            * width
        flash["calls"] += 3
        experts = work["expert_matmul"]
        experts["flops"] += 3 * 3 * 2.0 * d * inner * rows
        experts["bytes"] += 3 * 3 * width * (rows * (d + inner)
                                             + count * d * inner)
    return {name: w for name, w in work.items() if w["flops"]}


# -- the plain reference ----------------------------------------------------


def _experts(p, h, r, config, num, first=None, count=None):
    """The router in float32 over every expert on ``r``, the block's own
    input: softmax over all of them, the ``moe_num_active_primary_experts``
    largest renormalised to sum 1. Then a loop over the held experts
    (``first .. first + count``: the configuration's, unless given), each
    ``relu(h W1) * (h W3)`` times ``W2`` on every token with the token's
    weight for it (zero where it was not selected). No shared expert."""
    if first is None:
        first, count = held(config)
    top, ids = jax.lax.top_k(jax.nn.softmax(r @ p["router"]["kernel"], -1),
                             config["moe_num_active_primary_experts"])
    weights = top / jnp.sum(top, -1, keepdims=True)
    product = num.product

    @jax.checkpoint
    def weighted(expert):
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
        out = product(jnp.matmul, jax.nn.relu(product(jnp.matmul, h, w1))
                      * product(jnp.matmul, h, w3), w2)
        return weight[:, None] * out

    # the sum is carried outside the checkpoint: nothing of it is kept
    routed, _ = jax.lax.scan(
        lambda total, expert: (total + weighted(expert), None),
        jnp.zeros_like(h), (jnp.arange(count), p["experts_w1"],
                            p["experts_w3"], p["experts_w2"]))
    return routed


def _block(p, x, positions, config, windowed, rotated, num):
    """One block on one sequence ``x`` [T, d]."""
    eps, product = config["rms_norm_eps"], num.product
    h = shared._rms_norm(x, p["ln_attn"], eps)
    a = p["attn"]

    def heads(w):
        return product(functools.partial(jnp.einsum, "td,dhk->thk"), h,
                       w["kernel"])

    q, k = heads(a["query"]), heads(a["key"])
    if rotated:     # halves of the whole head: pairs (j, j + 64)
        rope = {"rope_theta": config["rope_theta"]}
        q, k = (shared._rotate(t, rope, positions) for t in (q, k))
    mixed = shared._attention(
        q, k, heads(a["value"]),
        config["sliding_window_size"] if windowed else None, num)
    mid = x + product(functools.partial(jnp.einsum, "thk,hkd->td"), mixed,
                      a["out"]["kernel"])
    return mid + _experts(p["moe"], shared._rms_norm(mid, p["ln_mlp"], eps),
                          x, config, num)


def _next_token_loss(x, row, head):
    """Mean next-token cross entropy of one sequence's final states ``x``
    [T, d] against ``row``'s ids, ``LOSS_ROWS`` rows of float32 logits at a
    time (a quarter of the vocabulary at 16,384 rows is 2.5 GB a copy); the
    last row, which has no next token, weighs nothing."""
    seq = row.size
    rows = min(LOSS_ROWS, seq)

    @jax.checkpoint
    def block(part):
        states, targets, counted = part
        logp = jax.nn.log_softmax(states @ head, -1)
        return -jnp.sum(counted * jnp.take_along_axis(
            logp, targets[:, None], -1)[:, 0])

    parts = jax.lax.map(block, (
        x.reshape(seq // rows, rows, -1), jnp.roll(row, -1).reshape(-1, rows),
        (jnp.arange(seq) < seq - 1).reshape(-1, rows)))
    return jnp.sum(parts) / (seq - 1)


def reference_loss(params, tokens, config, num=numerics.Exact):
    """Mean next-token cross entropy of ``tokens`` [B, T] in float32, one
    sequence at a time. The blocks' products go through ``num`` (the
    configuration computes them in bfloat16); the router and the output
    head stay float32, as the configuration states. Each block is
    rematerialised."""
    positions = jnp.arange(tokens.shape[1])

    @jax.checkpoint
    def sequence(row):
        x = params["tok_embed"]["embedding"][row]
        for i, (windowed, rotated) in enumerate(layers(config)):
            x = jax.checkpoint(functools.partial(
                _block, config=config, windowed=windowed, rotated=rotated,
                num=num))(params[f"block_{i}"], x, positions)
        x = shared._rms_norm(x, params["ln_final"], config["rms_norm_eps"])
        return _next_token_loss(x, row, params["lm_head"]["kernel"])

    total, _ = jax.lax.scan(lambda c, row: (c + sequence(row), None),
                            jnp.float32(0.0), tokens)
    return total / tokens.shape[0]


def reference_run(config, traffic, keys, steps: int, precision="float32"):
    """The reference trainer on one device, as ``laguna.reference_run``:
    seeded weights, the first ``steps`` batches of the pool, AdamW written
    out (the routers' update withheld while experts are absent), the
    moments kept on the host between updates. Returns what ``correct``
    compares."""
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
        update = jax.jit(functools.partial(
            shared._adamw, o=config["optimizer"],
            frozen=router_frozen(config)), donate_argnums=(0, 2, 3))
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
            mu, nu = shared._to_host((mu, nu))
        del mu, nu
        update_norms = numerics.difference_norms(params, init(weight_key)[0])
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "compile_s": compile_s}
