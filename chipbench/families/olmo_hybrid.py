"""Family ``olmo_hybrid``: decoder-only LM in dense **post-norm** blocks
(``h = x + norm(mixer(x))``, ``h + norm(mlp(h))``, RMSNorm, gated SiLU
MLPs), three layers of a gated delta rule — one decay a head and token,
keys and values of their own widths, write strengths up to 2, over a short
causal convolution — to one of full attention with normalised q and k and
no positions (allenai/Olmo-Hybrid-7B's ``config.json``).

The configuration is one chip's share of a deployment in which
``deployment.chips_sharing_a_layer`` chips share each layer and divide its
*heads*: the four head counts of the file are this chip's share (heads
``deployment.heads_held_first`` on), the published counts stand under
``deployment``, and the vocabulary is a slice. A head reads the whole input
and writes its own rows of the output projection, so a chip's mixer output
is its part of a sum over the chips; what the absent heads would add is
left out, in the program and in the reference alike, and that partial
result is what goes on. The MLP, the norms and the embedding are whole.

What does not depend on the architecture — the step builder, the seeded
batches, AdamW written out — is family ``laguna``'s, and the reference's
convolution, dense attention and scan over tokens family ``kimi_linear``'s,
imported from the benchmark's own files; nothing here imports the program
outside ``build`` and ``make_step``.

The reference is ``jax.numpy`` in float32 over the parameter tree that this
file itself lays out: the delta rule **token by token** (a ``lax.scan``, no
chunk algebra; checkpointed a block of tokens at a time so that its
backward fits), dense masked attention a block of query rows at a time,
each block rematerialised, the moments on the host between updates. The
recurrence itself (decay, state, its three products) is float32 under every
``precision``, as the configuration states it; the control rounds the
projections, the attention and the MLPs.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp

from chipbench import numerics
from chipbench.families import kimi_linear as kin
from chipbench.families import laguna as shared

make_step = shared.make_step
assemble = shared.assemble
make_pool = shared.make_pool
data_spec = shared.data_spec
visible_pairs = shared.visible_pairs


# -- the program, through its public surface --------------------------------


def build(config):
    """The program's model for ``config``: the published head counts, and
    which of them are held here."""
    from horovod_tpu.models import OlmoHybridLM

    deployment = config["deployment"]
    published = dict(
        config, **{key: deployment[key] for key in _HEAD_COUNTS},
        heads_held={"first": deployment["heads_held_first"],
                    "count": config["num_attention_heads"]})
    return OlmoHybridLM.from_config(
        published, attention=config["attention"], rule=config["rule"],
        remat=config["remat"],
        dtype=jnp.dtype(config["precision"]["compute"]))


def optimizer(config):
    """The optax transformation the configuration states, unwrapped."""
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"family olmo_hybrid trains with adamw, not "
                         f"{o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


def first_gradient(opt_state, config):
    """The gradient the optimizer was given at its first update: Adam's
    first moment starts at zero, so it is then ``(1 - b1) * g``."""
    return jax.tree_util.tree_map(
        lambda m: m / (1.0 - config["optimizer"]["b1"]),
        opt_state.inner[0].mu)


# -- the configuration's shape ----------------------------------------------

_HEAD_COUNTS = ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads")


def layers(config) -> list:
    """The mixers of the layers kept, the leading ``num_hidden_layers``:
    ``"linear_attention"`` or ``"full_attention"``."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for kind in kinds:
        if kind not in ("linear_attention", "full_attention"):
            raise ValueError(f"unknown layer type {kind!r}")
    return list(kinds)


def _widths(config) -> dict:
    """The widths, and the heads held here. One count for every kind of
    head, as published."""
    heads = {config[key] for key in _HEAD_COUNTS}
    if len(heads) != 1:
        raise ValueError(f"the file's head counts differ: "
                         f"{[config[key] for key in _HEAD_COUNTS]}")
    (heads,) = heads
    return dict(d=config["hidden_size"], heads=heads,
                dk=config["linear_key_head_dim"],
                dv=config["linear_value_head_dim"],
                taps=config["linear_conv_kernel_dim"],
                dh=config["head_dim"], mlp=config["intermediate_size"])


# -- seeded weights (the benchmark's own) -----------------------------------


def init_model_state(config, key):
    """``(params,)`` in the layout of ``build(config)``'s flax tree: normal
    (0, 0.02) matrices and embeddings, unit RMSNorm scales, convolution taps
    uniform in +-1/2, ``A_log`` the log of uniform [1, 16] and ``dt_bias``
    with softplus(dt_bias) log-uniform in [0.001, 0.1], a head each, the
    decay's projection zero (every layer starts at the step its
    ``dt_bias`` was drawn for, whatever the scale of the residual stream a
    post-norm block hands it: the configuration's ``assumed.gdn_decay``);
    all float32, no matrix bias anywhere. Traced inside one jitted call by the
    harness."""
    w = _widths(config)
    d, heads = w["d"], w["heads"]
    keys, values, full = heads * w["dk"], heads * w["dv"], heads * w["dh"]
    vocab = config["vocab_size"]
    counter = iter(range(1 << 30))
    fresh = lambda: jax.random.fold_in(key, next(counter))  # noqa: E731

    def kernel(*shape):
        return {"kernel": 0.02 * jax.random.normal(fresh(), shape,
                                                   jnp.float32)}

    def norm(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def taps(width):
        bound = 1.0 / math.sqrt(w["taps"])
        return jax.random.uniform(fresh(), (w["taps"], width), jnp.float32,
                                  -bound, bound)

    def gdn():
        step = jnp.exp(jax.random.uniform(
            fresh(), (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "query": kernel(d, keys), "key": kernel(d, keys),
            "value": kernel(d, values), "conv_q": taps(keys),
            "conv_k": taps(keys), "conv_v": taps(values),
            "A_log": jnp.log(jax.random.uniform(
                fresh(), (heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "decay": {"kernel": jnp.zeros((d, heads), jnp.float32)},
            "beta": kernel(d, heads),
            "gate": kernel(d, values), "out_norm": norm(w["dv"]),
            "out": kernel(values, d)}

    def attn():
        return {"query": kernel(d, full), "key": kernel(d, full),
                "value": kernel(d, full), "q_norm": norm(full),
                "k_norm": norm(full), "out": kernel(full, d)}

    params = {"tok_embed": {"embedding": 0.02 * jax.random.normal(
        fresh(), (vocab, d), jnp.float32)}}
    for i, kind in enumerate(layers(config)):
        mixer = {"gdn": gdn()} if kind == "linear_attention" \
            else {"attn": attn()}
        params[f"block_{i}"] = {
            **mixer, "ln_attn": norm(), "ln_mlp": norm(),
            "mlp": {"w1": kernel(d, w["mlp"]), "w3": kernel(d, w["mlp"]),
                    "w2": kernel(w["mlp"], d)}}
    params["ln_final"] = norm()
    params["lm_head"] = kernel(d, vocab)
    return (params,)


# -- shape functions --------------------------------------------------------


def matmul_parameters(config) -> int:
    """Parameters one token's activations are multiplied by on this chip:
    each delta-rule layer's projections (q, k, v, the gate, the decay's and
    the write strength's, out) and convolution taps, each full layer's four
    projections, the MLPs, and the head over the vocabulary slice.
    Embedding look-ups and norms do no matmul."""
    w = _widths(config)
    d, heads = w["d"], w["heads"]
    keys, values = heads * w["dk"], heads * w["dv"]
    total = d * config["vocab_size"]
    for kind in layers(config):
        if kind == "linear_attention":
            total += d * (2 * keys + 3 * values + 2 * heads) \
                + w["taps"] * (2 * keys + values)
        else:
            total += 4 * d * heads * w["dh"]
        total += 3 * d * w["mlp"]
    return total


# FLOPs of the recurrence a token and head, as multiples of d_k * d_v: the
# decay (1), k^T S (2), the rank-one write (2) and the read S^T q (2)
_RECURRENCE = 7


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one sequence on this chip: 2 per
    multiply-add, the backward pass twice the forward, so 6 per matmul
    parameter a token meets (``matmul_parameters``); full attention's two
    products over the causal pairs; the recurrence's own products a token
    and head (``_RECURRENCE``), as family ``kimi_linear`` counts them.
    Nothing for recomputation, the chunked form's extra products, the
    optimizer, norms, softmax or the embedding look-up."""
    (seq,) = traffic["sample_shape"]
    w = _widths(config)
    mixing = 0.0
    for kind in layers(config):
        if kind == "linear_attention":
            mixing += 3.0 * _RECURRENCE * w["dk"] * w["dv"] * w["heads"] * seq
        else:
            mixing += 3.0 * 2 * 2 * w["dh"] * w["heads"] * visible_pairs(seq)
    return 6.0 * matmul_parameters(config) * seq + mixing


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """FLOPs and HBM bytes one chip's step *needs* from each kernel,
    whatever implements it.

    ``gdn``: the recurrence's own products a token and head
    (``_RECURRENCE`` times ``d_k * d_v`` forward, twice that backward) —
    not the chunked algorithm's extra products (the triangular solve, the
    decayed Gram matrix), not a recomputation. Bytes: q, k (``d_k``), v and
    o (``d_v``) in the compute type, g and beta one float32 a head and
    token, once forward; q, k, v, g, beta and dO read and the five
    gradients written, once backward. ``calls``: the compiled step's
    ``gdn_fwd`` (once a layer: a recomputed block keeps its outputs) and
    ``gdn_bwd``.

    ``flash``: the full layers' three Pallas calls as
    ``laguna.kernel_work`` counts a full layer: FlashAttention-2's seven
    products of ``2 * head_dim`` FLOPs over the visible pairs and query
    heads; q, o, k, v once forward, q, o, dO, dQ, k, v, dK, dV once
    backward, the row statistics left out; no recomputation."""
    (seq,) = traffic["sample_shape"]
    width = jnp.dtype(config["precision"]["compute"]).itemsize
    w = _widths(config)
    heads, dk, dv, dh = w["heads"], w["dk"], w["dv"], w["dh"]
    tokens = per_chip_batch * seq
    work = {name: {"flops": 0.0, "bytes": 0.0, "calls": 0}
            for name in ("gdn", "flash")}
    for kind in layers(config):
        if kind == "linear_attention":
            one_way = tokens * heads * ((2 * dk + 2 * dv) * width + 2 * 4)
            gdn = work["gdn"]
            gdn["flops"] += 3.0 * _RECURRENCE * dk * dv * heads * tokens
            gdn["bytes"] += 3.0 * one_way - tokens * heads * dv * width
            gdn["calls"] += 2
        else:
            flash = work["flash"]
            flash["flops"] += 7.0 * 2 * dh * heads * per_chip_batch \
                * visible_pairs(seq)
            flash["bytes"] += 6.0 * tokens * 2 * heads * dh * width
            flash["calls"] += 3
    return {name: x for name, x in work.items() if x["flops"]}


# -- the plain reference ----------------------------------------------------

_rms_norm = shared._rms_norm
_gated_mlp = shared._gated_mlp
_conv = kin._conv               # depthwise, causal, one sequence [T, C]
_attention = kin._attention     # dense, causal, a block of query rows a time


def _delta_rule(q, k, v, g, beta):
    """The gated delta rule of one sequence, token by token: ``S_t = (I -
    beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
    q_t / sqrt(d_k)``. q, k ``[T, H, d_k]``, v ``[T, H, d_v]``, g and beta
    ``[T, H]``; float32 throughout: family ``kimi_linear``'s scan over
    tokens, given a head's one decay for every one of its channels."""
    return kin._delta_rule(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                           beta)


def _gdn(p, x, config, num):
    """The delta-rule mixer's part, of the heads held, on one sequence
    ``x`` [T, d]."""
    w = _widths(config)
    heads = w["heads"]
    product = num.product
    by_head = lambda a: a.reshape(a.shape[0], heads, -1)  # noqa: E731

    def dense(h, name):
        return product(jnp.matmul, h, p[name]["kernel"])

    q, k, v = (by_head(jax.nn.silu(_conv(dense(x, name), p[taps])))
               for name, taps in (("query", "conv_q"), ("key", "conv_k"),
                                  ("value", "conv_v")))
    q, k = (a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True)
                              + 1e-6) for a in (q, k))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        dense(x, "decay") + p["dt_bias"])
    strongest = 2.0 if config["linear_allow_neg_eigval"] else 1.0
    beta = strongest * jax.nn.sigmoid(dense(x, "beta"))
    o = _rms_norm(_delta_rule(q, k, v, g, beta), p["out_norm"],
                  config["rms_norm_eps"])
    gate = jax.nn.silu(dense(x, "gate"))
    return dense(o.reshape(x.shape[0], -1) * gate, "out")


def _full(p, x, config, num):
    """The full-attention mixer's part, of the heads held, on one sequence
    ``x`` [T, d]: q and k RMS-normalised over the channels held, no
    rotation."""
    w = _widths(config)
    eps = config["rms_norm_eps"]
    product = num.product
    by_head = lambda a: a.reshape(a.shape[0], w["heads"], -1)  # noqa: E731
    q = _rms_norm(product(jnp.matmul, x, p["query"]["kernel"]),
                  p["q_norm"], eps)
    k = _rms_norm(product(jnp.matmul, x, p["key"]["kernel"]),
                  p["k_norm"], eps)
    v = product(jnp.matmul, x, p["value"]["kernel"])
    out = _attention(by_head(q), by_head(k), by_head(v), num)
    return product(jnp.matmul, out.reshape(x.shape[0], -1),
                   p["out"]["kernel"])


def _block(p, x, config, kind, num):
    """One post-norm block on one sequence ``x`` [T, d]."""
    eps = config["rms_norm_eps"]
    mixed = _gdn(p["gdn"], x, config, num) if kind == "linear_attention" \
        else _full(p["attn"], x, config, num)
    h = x + _rms_norm(mixed, p["ln_attn"], eps)
    return h + _rms_norm(_gated_mlp(p["mlp"], h, num), p["ln_mlp"], eps)


def reference_loss(params, tokens, config, num=numerics.Exact):
    """Mean next-token cross entropy of ``tokens`` [B, T] in float32, one
    sequence at a time. The blocks' products go through ``num`` (the
    configuration computes them in bfloat16); the recurrence and the output
    head stay float32, as the configuration states. Each block is
    rematerialised."""

    @jax.checkpoint
    def sequence(row):
        x = params["tok_embed"]["embedding"][row]
        for i, kind in enumerate(layers(config)):
            x = jax.checkpoint(functools.partial(
                _block, config=config, kind=kind, num=num))(
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
            shared._adamw, o=config["optimizer"], frozen=False),
            donate_argnums=(0, 2, 3))
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
