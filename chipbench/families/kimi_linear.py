"""Family ``kimi_linear``: decoder-only LM with RMSNorm and no positional
encoding, layers of Kimi Delta Attention (a gated delta rule over a short
causal convolution) mixed 3 : 1 with latent attention whose q/k and v
widths differ, gated SiLU MLPs and, after a leading dense layer, routed
experts with one shared expert
(moonshotai/Kimi-Linear-48B-A3B-Instruct's ``config.json``).

The configuration is one chip's share of a deployment, cut as family
``laguna``'s is: ``num_experts`` of the ``deployment.num_experts`` routed
experts and a slice of the vocabulary live here, the router keeps its
width and its experts per token, what absent experts would add is left
out in the program and in the reference alike, and the routers' update is
withheld while experts are absent (``laguna.router_frozen``). What does not
depend on the architecture — the optimizer, the step builder, the seeded
batches, AdamW written out — is family ``laguna``'s, imported from the
benchmark's own file; nothing here imports the program outside ``build``
and ``make_step``.

The reference is ``jax.numpy`` in float32 over the parameter tree that this
file itself lays out: the delta rule **token by token** (a ``lax.scan`` over
the sequence, no chunk algebra; checkpointed a block of ``SCAN_BLOCK``
tokens at a time so that its backward fits — blocks of the same
recurrence), dense masked attention a block of query rows at a time, a loop
over the held experts. The recurrence itself (decay, state, its three
products) is float32 under every ``precision``, as the configuration
states it; the control rounds the projections, the attention and the MLPs.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp

from chipbench import numerics
from chipbench.families import laguna as shared

HEAD_ROWS = 256      # query rows of dense attention computed at once
SCAN_BLOCK = 128     # tokens of the recurrence recomputed together

router_frozen = shared.router_frozen
optimizer = shared.optimizer
make_step = shared.make_step
assemble = shared.assemble
first_gradient = shared.first_gradient
held = shared.held
routed_over = shared.routed_over
make_pool = shared.make_pool
data_spec = shared.data_spec
visible_pairs = shared.visible_pairs


# -- the program, through its public surface --------------------------------


def build(config):
    """The program's model for ``config``."""
    from horovod_tpu.models import KimiLinearLM

    first, count = held(config)
    published = dict(config, num_experts=routed_over(config),
                     experts_held={"first": first, "count": count})
    return KimiLinearLM.from_config(
        published, attention=config["attention"], kda=config["kda"],
        remat=config["remat"],
        dtype=jnp.dtype(config["precision"]["compute"]))


# -- the configuration's shape ----------------------------------------------


def layers(config) -> list:
    """``[(mixer, mlp type), ...]`` of the layers this configuration keeps,
    the leading ``num_hidden_layers``: mixer ``"kda"`` or ``"mla"`` as
    ``linear_attn_config`` names the layers (counted from 1), a dense MLP in
    the first ``first_k_dense_replace``."""
    linear = config["linear_attn_config"]
    out = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(f"layer {i} is not exactly one of KDA and full")
        out.append(("kda" if i in linear["kda_layers"] else "mla",
                    "dense" if i <= config["first_k_dense_replace"]
                    else "sparse"))
    return out


def _widths(config) -> dict:
    linear = config["linear_attn_config"]
    return dict(
        d=config["hidden_size"], heads=linear["num_heads"],
        dh=linear["head_dim"], taps=linear["short_conv_kernel_size"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        dv=config["v_head_dim"], rank=config["kv_lora_rank"],
        expert=config["moe_intermediate_size"],
        shared=config["moe_intermediate_size"] * config["num_shared_experts"])


# -- seeded weights (the benchmark's own) -----------------------------------


def init_model_state(config, key):
    """``(params,)`` in the layout of ``build(config)``'s flax tree: normal
    (0, 0.02) matrices and embeddings, unit RMSNorm scales, convolution taps
    uniform in +-1/2, the decay's rate ``A`` the log of uniform [1, 16] a
    head and its bias ``b`` with softplus(b) log-uniform in [0.001, 0.1] a
    channel; all float32, no matrix bias anywhere. Traced inside one jitted
    call by the harness."""
    w = _widths(config)
    d, heads, dh = w["d"], w["heads"], w["dh"]
    wide = heads * dh
    vocab, count = config["vocab_size"], held(config)[1]
    counter = iter(range(1 << 30))
    fresh = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def matrix(*shape):
        return 0.02 * jax.random.normal(fresh(), shape, jnp.float32)

    def kernel(*shape):
        return {"kernel": matrix(*shape)}

    def norm(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def mlp(inner):
        return {"w1": kernel(d, inner), "w3": kernel(d, inner),
                "w2": kernel(inner, d)}

    def taps():
        bound = 1.0 / math.sqrt(w["taps"])
        return jax.random.uniform(fresh(), (w["taps"], wide), jnp.float32,
                                  -bound, bound)

    def kda():
        step = jnp.exp(jax.random.uniform(
            fresh(), (wide,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "query": kernel(d, wide), "key": kernel(d, wide),
            "value": kernel(d, wide), "conv_q": taps(), "conv_k": taps(),
            "conv_v": taps(),
            "decay_rate": jnp.log(jax.random.uniform(
                fresh(), (heads,), jnp.float32, 1.0, 16.0)),
            "decay_bias": step + jnp.log(-jnp.expm1(-step)),
            "decay_a": kernel(d, dh), "decay_b": kernel(dh, wide),
            "beta": kernel(d, heads), "gate_a": kernel(d, dh),
            "gate_b": kernel(dh, wide), "out_norm": norm(dh),
            "out": kernel(wide, d)}

    def mla():
        return {
            "query": kernel(d, heads, w["nope"] + w["rope"]),
            "kv_a": kernel(d, w["rank"] + w["rope"]),
            "kv_norm": norm(w["rank"]),
            "kv_b": kernel(w["rank"], heads, w["nope"] + w["dv"]),
            "out": kernel(heads, w["dv"], d)}

    params = {"tok_embed": {"embedding": matrix(vocab, d)}}
    for i, (mixer, kind) in enumerate(layers(config)):
        block = {"ln_attn": norm(), "ln_mlp": norm(),
                 mixer: kda() if mixer == "kda" else mla()}
        if kind == "dense":
            block["mlp"] = mlp(config["intermediate_size"])
        else:
            block["moe"] = {
                "router": kernel(d, routed_over(config)),
                "experts_w1": matrix(count, d, w["expert"]),
                "experts_w3": matrix(count, d, w["expert"]),
                "experts_w2": matrix(count, w["expert"], d),
                "shared": mlp(w["shared"])}
        params[f"block_{i}"] = block
    params["ln_final"] = norm()
    params["lm_head"] = kernel(d, vocab)
    return (params,)


# -- shape functions --------------------------------------------------------


def matmul_parameters(config) -> int:
    """Parameters one token's activations are multiplied by on this chip:
    each KDA layer's projections (q, k, v, the decay's and the gate's two
    low-rank factors, beta, out) and convolution taps, each latent layer's
    (q, the compression, the expansion, out), the dense MLP or the router,
    the shared expert and the *expected* share of the routed experts —
    ``experts_per_token * held / num_experts`` experts a token — and the
    head over the vocabulary slice. Embedding look-ups and norms do no
    matmul."""
    w = _widths(config)
    d, wide = w["d"], w["heads"] * w["dh"]
    routed = config["num_experts_per_token"] * held(config)[1] \
        / routed_over(config)
    total = d * config["vocab_size"]
    for mixer, kind in layers(config):
        if mixer == "kda":
            total += 4 * d * wide + 2 * (d * w["dh"] + w["dh"] * wide) \
                + d * w["heads"] + 3 * w["taps"] * wide
        else:
            total += d * w["heads"] * (w["nope"] + w["rope"]) \
                + d * (w["rank"] + w["rope"]) \
                + w["rank"] * w["heads"] * (w["nope"] + w["dv"]) \
                + w["heads"] * w["dv"] * d
        if kind == "dense":
            total += 3 * d * config["intermediate_size"]
        else:
            total += d * routed_over(config) + 3 * d * w["shared"] \
                + routed * 3 * d * w["expert"]
    return int(total)


# FLOPs of the recurrence a token and head, as multiples of d_k * d_v: the
# decay (1), k^T S (2), the rank-one write (2) and the read S^T q (2)
_RECURRENCE = 7


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one sequence on this chip: 2 per
    multiply-add, the backward pass twice the forward, so 6 per matmul
    parameter a token meets (``matmul_parameters``); latent attention's two
    products over the causal pairs at their own widths; the recurrence's
    own products a token and head (``_RECURRENCE``). Nothing for
    recomputation, the chunked form's extra products, the optimizer, norms,
    softmax, routing's sort or the embedding look-up."""
    (seq,) = traffic["sample_shape"]
    w = _widths(config)
    mixing = 0.0
    for mixer, _ in layers(config):
        if mixer == "kda":
            mixing += 3.0 * _RECURRENCE * w["dh"] * w["dh"] * w["heads"] * seq
        else:
            mixing += 3.0 * 2 * (w["nope"] + w["rope"] + w["dv"]) \
                * w["heads"] * visible_pairs(seq)
    return 6.0 * matmul_parameters(config) * seq + mixing


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """FLOPs and HBM bytes one chip's step *needs* from each new kernel,
    whatever implements it.

    ``kda``: the recurrence's own products a token and head
    (``_RECURRENCE`` times ``d_k * d_v`` forward, twice that backward) —
    not the chunked algorithm's extra products (the triangular solve, the
    decayed Gram matrices), not the recomputation. Bytes: q, k, v and o in
    the compute type, g and beta in float32, once forward; q, k, v, g, beta
    and dO read and the five gradients written, once backward. ``calls``:
    the compiled step's ``kda_fwd`` (twice a layer with each block
    recomputed) and ``kda_bwd``.

    ``flash_mla``: per causal pair and head, the forward's two products (q
    k^T at the q/k width, P v at v's) and the backward's five needed ones
    (three at the q/k width, two at v's). Bytes as ``laguna.kernel_work``
    counts them, at the two widths: q, k, dQ, dK... each operand once
    forward and twice backward. ``calls``: forward twice a layer under
    ``remat``, dQ and dK/dV once."""
    (seq,) = traffic["sample_shape"]
    width = jnp.dtype(config["precision"]["compute"]).itemsize
    w = _widths(config)
    heads, dh = w["heads"], w["dh"]
    qk, dv = w["nope"] + w["rope"], w["dv"]
    tokens = per_chip_batch * seq
    forwards = 2 if config["remat"] else 1
    work = {name: {"flops": 0.0, "bytes": 0.0, "calls": 0}
            for name in ("kda", "flash_mla")}
    for mixer, _ in layers(config):
        if mixer == "kda":
            one_way = tokens * heads * (4 * dh * width + dh * 4 + 4)
            kda = work["kda"]
            kda["flops"] += 3.0 * _RECURRENCE * dh * dh * heads * tokens
            kda["bytes"] += 3.0 * one_way - tokens * heads * dh * width
            kda["calls"] += forwards + 1
        else:
            mla = work["flash_mla"]
            mla["flops"] += (2.0 * (qk + dv) + 2.0 * (3 * qk + 2 * dv)) \
                * heads * per_chip_batch * visible_pairs(seq)
            mla["bytes"] += 6.0 * tokens * heads * (qk + dv) * width
            mla["calls"] += forwards + 2
    return {name: x for name, x in work.items() if x["flops"]}


# -- the plain reference ----------------------------------------------------

_rms_norm = shared._rms_norm
_gated_mlp = shared._gated_mlp


def _conv(x, taps):
    """Depthwise causal convolution of one sequence ``x`` [T, C] with
    ``taps`` [n, C]: the last tap on the token itself."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
    return sum(padded[j:j + x.shape[0]] * taps[j] for j in range(n))


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule of one sequence, token by token. q, k, g
    ``[T, H, d_k]``, v ``[T, H, d_v]``, beta ``[T, H]``; float32
    throughout. ``SCAN_BLOCK`` tokens are recomputed together in the
    backward pass (the state is kept once a block)."""
    seq, heads, d_k = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   beta_t[:, None] * (v_t - seen))
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    size = math.gcd(seq, SCAN_BLOCK)
    _, out = jax.lax.scan(
        block, jnp.zeros((heads, d_k, v.shape[-1]), jnp.float32),
        tuple(x.reshape(seq // size, size, *x.shape[1:])
              for x in (q, k, v, g, beta)))
    return out.reshape(seq, heads, -1) / jnp.sqrt(jnp.float32(d_k))


def _kda(p, h, config, num):
    """The KDA mixer on one normalised sequence ``h`` [T, d]."""
    w = _widths(config)
    heads, dh = w["heads"], w["dh"]
    product = num.product
    by_head = lambda a: a.reshape(a.shape[0], heads, dh)  # noqa: E731

    def dense(x, name):
        return product(jnp.matmul, x, p[name]["kernel"])

    q, k, v = (jax.nn.silu(_conv(dense(h, name), p[taps]))
               for name, taps in (("query", "conv_q"), ("key", "conv_k"),
                                  ("value", "conv_v")))
    q, k = (a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                              + 1e-6) for a in (by_head(q), by_head(k)))
    g = -jnp.exp(p["decay_rate"])[:, None] * by_head(jax.nn.softplus(
        dense(dense(h, "decay_a"), "decay_b") + p["decay_bias"]))
    beta = jax.nn.sigmoid(dense(h, "beta"))
    o = _delta_rule(q, k, by_head(v), g, beta)
    o = _rms_norm(o, p["out_norm"], config["rms_norm_eps"])
    gate = jax.nn.sigmoid(dense(dense(h, "gate_a"), "gate_b"))
    return dense(o.reshape(h.shape[0], heads * dh) * gate, "out")


def _attention(q, k, v, num):
    """Dense causal attention of one sequence, ``HEAD_ROWS`` query rows
    against every key at a time. q, k [T, H, D]; v [T, H, Dv]."""
    seq, heads, dh = q.shape
    rows = min(HEAD_ROWS, seq)
    product = num.product

    @jax.checkpoint
    def block(q_rows, first):
        scores = product(functools.partial(jnp.einsum, "qhd,khd->hqk"),
                         q_rows / jnp.sqrt(jnp.float32(dh)), k)
        keep = (first + jnp.arange(rows))[:, None] >= jnp.arange(seq)
        weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return product(functools.partial(jnp.einsum, "hqk,khd->qhd"),
                       weights, v)

    out = jax.lax.map(lambda a: block(*a), (
        q.reshape(seq // rows, rows, heads, dh), jnp.arange(0, seq, rows)))
    return out.reshape(seq, heads, v.shape[-1])


def _mla(p, h, config, num):
    """The latent-attention mixer on one normalised sequence ``h`` [T, d];
    no rotation on the ``qk_rope_head_dim`` dims (``mla_use_nope``)."""
    w = _widths(config)
    product = num.product
    q = product(functools.partial(jnp.einsum, "td,dhk->thk"), h,
                p["query"]["kernel"])
    latent, k_pe = jnp.split(product(jnp.matmul, h, p["kv_a"]["kernel"]),
                             [w["rank"]], axis=-1)
    latent = _rms_norm(latent, p["kv_norm"], config["rms_norm_eps"])
    k_nope, v = jnp.split(
        product(functools.partial(jnp.einsum, "tr,rhk->thk"), latent,
                p["kv_b"]["kernel"]), [w["nope"]], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, None, :], (*k_nope.shape[:2], w["rope"]))], axis=-1)
    return product(functools.partial(jnp.einsum, "thk,hkd->td"),
                   _attention(q, k, v, num), p["out"]["kernel"])


def _experts(p, h, config, num):
    """Router in float32 over every expert, the ``num_experts_per_token``
    largest sigmoid scores normalised to sum 1 and scaled; the shared
    expert, and a loop over the held experts, each on every token with the
    token's weight for it (zero where it was not selected)."""
    first, count = held(config)
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])
    top, ids = jax.lax.top_k(scores, config["num_experts_per_token"])
    weights = config["routed_scaling_factor"] * top \
        / jnp.sum(top, -1, keepdims=True)
    product = num.product

    @jax.checkpoint
    def weighted(expert):
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
        out = product(jnp.matmul, jax.nn.silu(product(jnp.matmul, h, w1))
                      * product(jnp.matmul, h, w3), w2)
        return weight[:, None] * out

    routed, _ = jax.lax.scan(
        lambda total, expert: (total + weighted(expert), None),
        jnp.zeros_like(h), (jnp.arange(count), p["experts_w1"],
                            p["experts_w3"], p["experts_w2"]))
    return _gated_mlp(p["shared"], h, num) + routed


def _block(p, x, config, mixer, num):
    """One block on one sequence ``x`` [T, d]."""
    eps = config["rms_norm_eps"]
    h = _rms_norm(x, p["ln_attn"], eps)
    x = x + (_kda(p["kda"], h, config, num) if mixer == "kda"
             else _mla(p["mla"], h, config, num))
    h = _rms_norm(x, p["ln_mlp"], eps)
    if "mlp" in p:
        return x + _gated_mlp(p["mlp"], h, num)
    return x + _experts(p["moe"], h, config, num)


def reference_loss(params, tokens, config, num=numerics.Exact):
    """Mean next-token cross entropy of ``tokens`` [B, T] in float32, one
    sequence at a time. The blocks' products go through ``num`` (the
    configuration computes them in bfloat16); the recurrence, the router
    and the output head stay float32, as the configuration states. Each
    block is rematerialised."""

    @jax.checkpoint
    def sequence(row):
        x = params["tok_embed"]["embedding"][row]
        for i, (mixer, _) in enumerate(layers(config)):
            x = jax.checkpoint(functools.partial(
                _block, config=config, mixer=mixer, num=num))(
                    params[f"block_{i}"], x)
        x = _rms_norm(x, params["ln_final"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(x[:-1] @ params["lm_head"]["kernel"], -1)
        return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], -1))

    total, _ = jax.lax.scan(lambda c, row: (c + sequence(row), None),
                            jnp.float32(0.0), tokens)
    return total / tokens.shape[0]


def reference_run(config, traffic, keys, steps: int, precision="float32"):
    """The reference trainer on one device, as ``laguna.reference_run``:
    seeded weights, the first ``steps`` batches of the pool, AdamW written
    out, the moments kept on the host between updates. Returns what
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
