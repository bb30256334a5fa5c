"""Family ``sdar``: a sparse-expert decoder trained as a block-diffusion
model (JetLM/SDAR-30B-A3B-Chat's ``config.json``, ``model_type``
``sdar_moe``; BD3-LM, arXiv:2503.09573, and SDAR, arXiv:2510.06303):
pre-RMSNorm blocks, grouped heads with an RMSNorm a head on q and k before a
rotation over the whole head, every layer 128 routed experts under a
softmax router (the 8 largest renormalised, no scaling, no shared expert).

A step is given, for each sequence, its clean ids ``x0 [L]``, a noisy copy
``xt`` in which positions are replaced by ``mask_token_id`` at a rate drawn
a block of ``block_length`` positions, and a weight a position (``1 /
rate`` where it was masked, else 0): ``make_pool`` draws all three from
``--seed``, as an input pipeline would before the step. Both copies go
through the stack side by side, 2 L rows, each half at positions ``0 .. L -
1``, under one mask: a noisy row sees the noisy rows of its own block and
the clean rows of the blocks before it, a clean row the clean rows of its
own block and of those before it. The loss is the noisy rows' cross entropy
against their own clean ids, no shift, times the weights, over ``G * L``.

The configuration is one chip's share of a deployment in which
``deployment.chips_sharing_a_layer`` chips share each layer, as family
``laguna``'s: ``num_experts`` of the ``deployment.num_experts`` routed
experts and a slice of the vocabulary live here, the router keeps its width
and its experts per token, what absent experts would add is left out in the
program and in the reference alike, and the routers' update is withheld
while experts are absent (``laguna.router_frozen``).

What does not depend on the architecture — the optimizer with its frozen
routers, AdamW written out, rotary positions, the norms — is family
``laguna``'s, imported from the benchmark's own file; nothing here imports
the program outside ``build`` and ``make_step``. The reference is
``jax.numpy`` in float32 over the parameter tree that this file itself lays
out, its rows in the order of the equations (``[noisy ; clean]``; the
program's is the other): dense attention under the mask built from its
definition, a block of query rows at a time, a loop over the held experts,
each block rematerialised, the moments on the host between updates.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from chipbench import numerics
from chipbench.families import laguna as shared

HEAD_ROWS = 128      # query rows of dense attention computed at once

router_frozen = shared.router_frozen
optimizer = shared.optimizer
assemble = shared.assemble
first_gradient = shared.first_gradient
held = shared.held
routed_over = shared.routed_over


# -- the program, through its public surface --------------------------------


def build(config):
    """The program's model for ``config``."""
    from horovod_tpu.models import SdarMoeLM

    first, count = held(config)
    published = dict(config, num_experts=routed_over(config),
                     experts_held={"first": first, "count": count})
    return SdarMoeLM.from_config(
        published, attention=config["attention"], remat=config["remat"],
        dtype=jnp.dtype(config["precision"]["compute"]))


def make_step(model, opt, mesh):
    """``step(params, opt_state, clean, noisy, weights) -> (params,
    opt_state, loss)``."""
    from benchmarks._dp_step import make_bd_train_step

    return make_bd_train_step(model, opt, mesh, axis_name="data")


# -- seeded weights and batches (the benchmark's own) -----------------------


def init_model_state(config, key):
    """``(params,)`` in the layout of ``build(config)``'s flax tree: normal
    (0, 0.02) matrices, but the embedding normal(0, 1) and the two
    projections that write to the residual stream (attention's ``out``, the
    experts' ``w2``) normal(0, 0.02 / sqrt(2 * published layers)), so that a
    row's own token and not the mean over its context drives its routing
    (the configuration's ``assumed.initializer`` has the reason and the
    measurement); unit RMSNorm scales (the blocks', and q's and k's one of
    ``head_dim`` each); all float32, no bias anywhere. Traced inside one
    jitted call by the harness."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    vocab, count = config["vocab_size"], held(config)[1]
    width = config["moe_intermediate_size"]
    counter = iter(range(1 << 30))

    def matrix(*shape, std=0.02):
        return std * jax.random.normal(
            jax.random.fold_in(key, next(counter)), shape, jnp.float32)

    def kernel(*shape, std=0.02):
        return {"kernel": matrix(*shape, std=std)}

    residual = 0.02 / (2 * config["deployment"]["num_hidden_layers"]) ** 0.5

    def norm(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    params = {"tok_embed": {"embedding": matrix(vocab, d, std=1.0)}}
    for i in range(config["num_hidden_layers"]):
        params[f"block_{i}"] = {
            "ln_attn": norm(),
            "attn": {"query": kernel(d, heads, dh), "key": kernel(d, kv, dh),
                     "value": kernel(d, kv, dh), "q_norm": norm(dh),
                     "k_norm": norm(dh),
                     "out": kernel(heads, dh, d, std=residual)},
            "ln_mlp": norm(),
            "moe": {"router": kernel(d, routed_over(config)),
                    "experts_w1": matrix(count, d, width),
                    "experts_w3": matrix(count, d, width),
                    "experts_w2": matrix(count, width, d, std=residual)}}
    params["ln_final"] = norm()
    params["lm_head"] = kernel(d, vocab)
    table = params["tok_embed"]["embedding"]
    params["tok_embed"]["embedding"] = table.at[config["mask_token_id"]].set(
        _placed(table[config["mask_token_id"]], params, config))
    return (params,)


# the router logit, before the row is normalised, that the mask token gives
# the one held expert it uses: its seven others clear about 1.5
_MASK_LOGIT = 4.0


def _placed(row, params, config):
    """The mask token's seeded embedding moved, within the span of the held
    experts' router columns of every layer, to where the deployment says its
    experts live: of the eight it uses in a layer one is here, the first
    held — the expectation, 8 x 16 / 128 — and none of the other held ones
    (their logits are zero, the first's ``_MASK_LOGIT``). Every masked
    position is this one token, a quarter of a step's rows: left as drawn it
    would send them all to 0 to 4 of the held experts as the weights fall,
    and a near-tie between its eighth and ninth expert would send them one
    way in bfloat16 and the other in float32 (``assumed.mask_token`` of the
    configuration has the measurements)."""
    first, count = held(config)
    columns = jnp.concatenate([
        params[f"block_{i}"]["moe"]["router"]["kernel"][:, first:first + count]
        for i in range(config["num_hidden_layers"])], axis=1)
    wanted = jnp.tile(jnp.zeros((count,)).at[0].set(_MASK_LOGIT),
                      config["num_hidden_layers"])
    return row + columns @ jnp.linalg.solve(columns.T @ columns,
                                            wanted - columns.T @ row)


def noise(key, clean, config):
    """``(noisy, weights)`` for clean ids ``[G, L]``: a rate a block, ``t =
    eps + (1 - eps) u`` with ``u`` uniform (the linear schedule), each
    position masked with probability ``t``, a masked position's weight ``1 /
    t`` and any other's 0. The benchmark's own draw, not the program's
    ``block_diffusion_noise`` (a test holds the two to the same law)."""
    block, eps = config["block_length"], config["noise_eps"]
    batch, seq = clean.shape
    rate_key, mask_key = jax.random.split(key)
    rate = jnp.repeat(eps + (1.0 - eps) * jax.random.uniform(
        rate_key, (batch, seq // block), jnp.float32), block, axis=1)
    masked = jax.random.uniform(mask_key, clean.shape, jnp.float32) < rate
    return (jnp.where(masked, jnp.int32(config["mask_token_id"]), clean),
            jnp.where(masked, 1.0 / rate, 0.0))


def make_pool(config, traffic, key):
    """``pool`` batches, each ``(clean, noisy, weights)`` ``[global_batch,
    seq]``: clean ids uniform over the vocabulary slice less the mask
    token, which is its last id, and each batch's own noise."""
    (seq,) = traffic["sample_shape"]
    if config["mask_token_id"] != config["vocab_size"] - 1:
        raise ValueError("the mask token is the slice's last id")
    pool = []
    for k in jax.random.split(key, traffic["pool"]):
        token_key, noise_key = jax.random.split(k)
        clean = jax.random.randint(
            token_key, (traffic["global_batch"], seq), 0,
            config["mask_token_id"], dtype=jnp.int32)
        pool.append((clean, *noise(noise_key, clean, config)))
    return pool


def data_spec(batch_axis):
    """PartitionSpec entries of one batch's arrays."""
    from jax.sharding import PartitionSpec as P

    return (P(batch_axis),) * 3


# -- shape functions --------------------------------------------------------


def layer_parameters(config) -> dict:
    """Parameters a row's activations are multiplied by in one layer:
    ``attention`` (q, k, v, out), of which ``kv`` are k's and v's,
    ``router``, and ``experts``, the *expected* share of the routed experts —
    ``experts_per_token * held / num_experts`` experts a row, an
    expectation under a router that spreads rows evenly."""
    d, dh = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    routed = config["num_experts_per_tok"] * held(config)[1] \
        / routed_over(config)
    return {"attention": 2 * d * (heads + kv) * dh, "kv": 2 * d * kv * dh,
            "router": d * routed_over(config),
            "experts": routed * 3 * d * config["moe_intermediate_size"]}


def needed_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the three-part mask keeps for one sequence and
    head: ``seq * (seq + block)`` — the clean rows' ``seq (seq + block) /
    2``, the noisy rows' ``seq (seq - block) / 2`` clean keys and ``seq *
    block`` noisy ones."""
    return seq * (seq + block)


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one clean sequence of ``L`` on this chip,
    over the *needed* work: 2 per multiply-add, the backward pass twice the
    forward. Every layer but the last takes 2 L rows through its attention
    projections, router and expected held expert and keeps ``L (L +
    block)`` pairs a head; the last needs its noisy half whole, of the clean
    half the key and value projections alone, and the noisy rows' half of
    the pairs; the head reads the L noisy rows. Nothing for recomputation,
    the optimizer, norms, rotary, softmax, routing's sort or the embedding
    look-up."""
    (seq,) = traffic["sample_shape"]
    depth = config["num_hidden_layers"]
    p = layer_parameters(config)
    layer = p["attention"] + p["router"] + p["experts"]
    rows = (2 * (depth - 1) + 1) * seq * layer + seq * p["kv"] \
        + seq * config["hidden_size"] * config["vocab_size"]
    pairs = (depth - 0.5) * needed_pairs(seq, config["block_length"])
    return 6.0 * rows + 3 * 2 * 2 * config["head_dim"] \
        * config["num_attention_heads"] * pairs


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """FLOPs and HBM bytes one chip's step *needs* from each kernel.

    ``flash_bd``: the three ``flash_bd_*`` calls. FlashAttention-2's seven
    products of ``2 * head_dim`` FLOPs over the needed pairs and query
    heads (the last layer: the noisy rows' half). Bytes as
    ``laguna.kernel_work`` counts a full layer: q, o (forward) and q, o, dO,
    dQ (backward) at the query heads' width over the rows that query (2 L;
    L in the last layer), k, v and k, v, dK, dV once a group over all 2 L,
    in the compute type; the row statistics left out; nothing for
    recomputation. ``calls``: three a layer, a recomputed block keeping its
    forward kernel's outputs.

    ``expert_matmul``: as ``laguna.kernel_work``'s, at the *expected* rows a
    layer, ``rows * experts_per_token * held / num_experts`` of the rows
    that reach its expert layer (2 L; L in the last)."""
    (seq,) = traffic["sample_shape"]
    width = jnp.dtype(config["precision"]["compute"]).itemsize
    dh, heads = config["head_dim"], config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    d, inner = config["hidden_size"], config["moe_intermediate_size"]
    count, depth = held(config)[1], config["num_hidden_layers"]
    share = config["num_experts_per_tok"] * count / routed_over(config)
    work = {name: {"flops": 0.0, "bytes": 0.0, "calls": 0}
            for name in ("flash_bd", "expert_matmul")}
    for layer in range(depth):
        last = layer == depth - 1
        queries = per_chip_batch * seq * (1 if last else 2)
        flash = work["flash_bd"]
        flash["flops"] += 7.0 * 2 * dh * heads * per_chip_batch \
            * needed_pairs(seq, config["block_length"]) * (0.5 if last else 1)
        flash["bytes"] += 6.0 * dh * width * (
            queries * heads + 2 * per_chip_batch * seq * kv)
        flash["calls"] += 3
        experts, rows = work["expert_matmul"], queries * share
        experts["flops"] += 3 * 3 * 2.0 * d * inner * rows
        experts["bytes"] += 3 * 3 * width * (rows * (d + inner)
                                             + count * d * inner)
    return work


# -- the plain reference ----------------------------------------------------

_rms_norm = shared._rms_norm
_rotate = shared._rotate     # halves of the whole head: pairs (j, j + 64)


def visible(config, seq: int, first: int, rows: int):
    """``[rows, 2 seq]`` bool: what the query rows ``first .. first + rows``
    of ``[noisy ; clean]`` see among all ``2 seq`` rows, from the mask's
    definition."""
    block = config["block_length"]
    q, k = first + jnp.arange(rows)[:, None], jnp.arange(2 * seq)[None, :]
    q_noisy, k_noisy = q < seq, k < seq
    q_block, k_block = q % seq // block, k % seq // block
    return jnp.where(q_noisy,
                     jnp.where(k_noisy, k_block == q_block,
                               k_block < q_block),
                     ~k_noisy & (k_block <= q_block))


def _attention(q, k, v, config, num):
    """Dense attention of one sequence's ``[noisy ; clean]`` rows under the
    three-part mask, ``HEAD_ROWS`` query rows against every key at a time.
    q [2 L, H, D]; k, v [2 L, Hkv, D]."""
    both, heads, dh = q.shape
    k, v = (jnp.repeat(x, heads // k.shape[1], axis=1) for x in (k, v))
    rows = min(HEAD_ROWS, both)
    product = num.product

    @jax.checkpoint
    def block(q_rows, first):
        scores = product(functools.partial(jnp.einsum, "qhd,khd->hqk"),
                         q_rows / jnp.sqrt(jnp.float32(dh)), k)
        keep = visible(config, both // 2, first, rows)
        weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return product(functools.partial(jnp.einsum, "hqk,khd->qhd"),
                       weights, v)

    out = jax.lax.map(lambda a: block(*a), (
        q.reshape(both // rows, rows, heads, dh),
        jnp.arange(0, both, rows)))
    return out.reshape(both, heads, dh)


def _experts(p, h, config, num):
    """Router in float32 over every expert, softmax over all of them, the
    ``num_experts_per_tok`` largest renormalised to sum 1; a loop over the
    held experts, each on every row with the row's weight for it (zero
    where it was not selected). No shared expert."""
    first, count = held(config)
    top, ids = jax.lax.top_k(jax.nn.softmax(h @ p["router"]["kernel"], -1),
                             config["num_experts_per_tok"])
    weights = top / jnp.sum(top, -1, keepdims=True)
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
    return routed


def _block(p, x, positions, config, num):
    """One block on one sequence's rows ``x`` [2 L, d]."""
    eps, product = config["rms_norm_eps"], num.product
    rope = {"rope_theta": config["rope_theta"]}
    h = _rms_norm(x, p["ln_attn"], eps)
    a = p["attn"]

    def heads(w):
        return product(functools.partial(jnp.einsum, "td,dhk->thk"), h,
                       w["kernel"])

    q = _rotate(_rms_norm(heads(a["query"]), a["q_norm"], eps), rope,
                positions)
    k = _rotate(_rms_norm(heads(a["key"]), a["k_norm"], eps), rope,
                positions)
    mixed = _attention(q, k, heads(a["value"]), config, num)
    x = x + product(functools.partial(jnp.einsum, "thk,hkd->td"), mixed,
                    a["out"]["kernel"])
    return x + _experts(p["moe"], _rms_norm(x, p["ln_mlp"], eps), config,
                        num)


def reference_loss(params, clean, noisy, weights, config,
                   num=numerics.Exact):
    """The weighted cross entropy at the masked positions, ``1 / (G L)`` of
    the sum over the rows, in float32, one sequence at a time. The blocks'
    products go through ``num`` (the configuration computes them in
    bfloat16); the router and the output head stay float32, as the
    configuration states. Each block is rematerialised. Every layer is
    computed for all 2 L rows; the noisy ones alone reach the head."""
    seq = clean.shape[1]
    positions = jnp.tile(jnp.arange(seq), 2)

    @jax.checkpoint
    def sequence(row):
        x0, xt, w = row
        x = params["tok_embed"]["embedding"][jnp.concatenate([xt, x0])]
        for i in range(config["num_hidden_layers"]):
            x = jax.checkpoint(functools.partial(
                _block, config=config, num=num))(
                    params[f"block_{i}"], x, positions)
        z = _rms_norm(x[:seq], params["ln_final"], config["rms_norm_eps"])
        logits = z @ params["lm_head"]["kernel"]
        hit = jnp.take_along_axis(logits, x0[:, None], -1)[:, 0]
        return jnp.sum(w * (jax.nn.logsumexp(logits, -1) - hit))

    total, _ = jax.lax.scan(lambda c, row: (c + sequence(row), None),
                            jnp.float32(0.0), (clean, noisy, weights))
    return total / clean.size


def reference_run(config, traffic, keys, steps: int, precision="float32"):
    """The reference trainer on one device, as ``laguna.reference_run``:
    seeded weights, the first ``steps`` batches of the pool with their
    noise, AdamW written out (the routers' update withheld while experts
    are absent), the moments kept on the host between updates. Returns what
    ``correct`` compares."""
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
                params, *pool[0]).compile()
        compile_s = time.perf_counter() - started
        update = jax.jit(functools.partial(
            shared._adamw, o=config["optimizer"],
            frozen=router_frozen(config)), donate_argnums=(0, 2, 3))
        mu = nu = None
        losses, grad_norms = [], None
        for i in range(steps):
            loss, grad = grad_fn(params, *pool[i % len(pool)])
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
