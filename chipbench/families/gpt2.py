"""Family ``gpt2``: decoder-only LM with learned positions, pre-LayerNorm
blocks, tanh-GELU and causal attention (Radford et al. 2019).

What the harness takes from a family file: the program's model through its
public constructor (``build``), the step builder to call (``make_step``),
seeded weights and batches (``init_model_state``, ``make_pool``), the
shape functions behind ``mfu_pct`` and the kernels' roofline shares
(``flops_per_sample``, ``kernel_work``), and the plain reference trainer
(``reference_run``) that ``correct`` compares the compiled step with. The
reference imports nothing of the program: it is ``jax.numpy`` in float32
over the parameter tree that this file itself lays out.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from chipbench import numerics

# -- the program, through its public surface --------------------------------


def build(config):
    """The program's model for ``config``."""
    from horovod_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=config["vocab_size"], num_layers=config["n_layer"],
        num_heads=config["n_head"], d_model=config["n_embd"],
        d_ff=config["n_inner"], max_seq_len=config["n_positions"],
        attention=config["attention"], remat=config["remat"])


def optimizer(config):
    """The optax transformation the configuration states, unwrapped."""
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"family gpt2 trains with adamw, not {o['name']!r}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], weight_decay=o["weight_decay"])


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
    mu = opt_state.inner[0].mu
    return jax.tree_util.tree_map(
        lambda m: m / (1.0 - config["optimizer"]["b1"]), mu)


# -- seeded weights and batches (the benchmark's own) -----------------------


def init_model_state(config, key):
    """``(params,)`` in the layout of ``build(config)``'s flax tree: normal
    (0, 0.02) matrices and embeddings, zero biases, unit LayerNorm scales,
    all float32. Traced inside one jitted call by the harness. Each kind
    of matrix is drawn once for all layers and cut by layer, which keeps
    the program (and its compilation) small."""
    d, heads, ff = config["n_embd"], config["n_head"], config["n_inner"]
    vocab, dh = config["vocab_size"], config["n_embd"] // config["n_head"]
    layers = config["n_layer"]
    keys = iter(jax.random.split(key, 9))

    def matrix(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def dense(kernel, *bias_shape):
        return {"kernel": kernel, "bias": jnp.zeros(bias_shape, jnp.float32)}

    params = {"tok_embed": {"embedding": matrix(vocab, d)},
              "pos_embed": {"embedding": matrix(config["n_positions"], d)}}
    query, key_, value = (matrix(layers, d, heads, dh) for _ in range(3))
    out, mlp_in = matrix(layers, heads, dh, d), matrix(layers, d, ff)
    mlp_out = matrix(layers, ff, d)
    for i in range(layers):
        params[f"block_{i}"] = {
            "ln_attn": norm(),
            "attn": {"query": dense(query[i], heads, dh),
                     "key": dense(key_[i], heads, dh),
                     "value": dense(value[i], heads, dh),
                     "out": dense(out[i], d)},
            "ln_mlp": norm(),
            "mlp_in": dense(mlp_in[i], ff),
            "mlp_out": dense(mlp_out[i], d)}
    params["ln_final"] = norm()
    params["lm_head"] = dense(matrix(d, vocab), vocab)
    return (params,)


def make_pool(config, traffic, key):
    """``pool`` batches, each a tuple of the step's data arguments: uniform
    random tokens ``[global_batch, seq]``. Every row differs."""
    (seq,) = traffic["sample_shape"]
    if seq > config["n_positions"]:
        raise ValueError(f"sequences of {seq} exceed n_positions")
    return [(jax.random.randint(k, (traffic["global_batch"], seq), 0,
                                config["vocab_size"], dtype=jnp.int32),)
            for k in jax.random.split(key, traffic["pool"])]


def data_spec(batch_axis):
    """PartitionSpec entries of one batch's arrays."""
    from jax.sharding import PartitionSpec as P

    return (P(batch_axis),)


# -- shape functions --------------------------------------------------------


def matmul_parameters(config) -> int:
    """Parameters that multiply activations: the blocks' six matrices and
    the output head. Embedding look-ups, biases and norms do no matmul."""
    d, ff = config["n_embd"], config["n_inner"]
    return config["n_layer"] * (4 * d * d + 2 * d * ff) \
        + d * config["vocab_size"]


def causal_pairs(seq: int) -> int:
    """(query, key) pairs a causal mask keeps, the diagonal included."""
    return seq * (seq + 1) // 2


def flops_per_sample(config, traffic) -> float:
    """Required training FLOPs of one sequence: 2 per multiply-add, the
    backward pass twice the forward, so 6 per matmul parameter per token;
    attention's two products (scores, weighted values) over the causal
    pairs only. Nothing for the optimizer, LayerNorm, GELU, softmax, the
    residual adds or the embedding look-up."""
    (seq,) = traffic["sample_shape"]
    attention = 3 * 2 * 2 * config["n_embd"] * causal_pairs(seq) \
        * config["n_layer"]
    return 6.0 * matmul_parameters(config) * seq + attention


def kernel_work(config, traffic, per_chip_batch: int) -> dict:
    """FLOPs and HBM bytes one chip's step needs from each kernel.

    ``flash``: the three Pallas calls of every layer together (forward,
    dQ, dK/dV). Needed products per causal pair and head, ``2 * head_dim``
    FLOPs each: scores and weighted values forward; scores again, dV, dP,
    dQ and dK backward (FlashAttention-2's seven — the second recomputation
    that two backward kernels cost is not needed work). Needed bytes: q, k,
    v read and o written forward; q, k, v, o, dO read and dQ, dK, dV
    written backward, in the compute type; the row statistics are left
    out, which undercounts."""
    (seq,) = traffic["sample_shape"]
    width = jnp.dtype(config["precision"]["compute"]).itemsize
    per_layer_flops = 7 * 2 * config["n_embd"] * causal_pairs(seq) \
        * per_chip_batch
    per_layer_bytes = 12 * per_chip_batch * seq * config["n_embd"] * width
    return {"flash": {"flops": float(config["n_layer"] * per_layer_flops),
                      "bytes": float(config["n_layer"] * per_layer_bytes),
                      "calls": 3 * config["n_layer"]}}


# -- the plain reference ----------------------------------------------------


def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, num):
    """One pre-LayerNorm block on ``x`` [B, T, d]."""
    seq = x.shape[1]
    product = num.product
    h = _layer_norm(x, p["ln_attn"])
    a = p["attn"]

    def heads(w):
        return product(functools.partial(jnp.einsum, "btd,dhk->bthk"), h,
                       w["kernel"]) + w["bias"]

    q, k, v = heads(a["query"]), heads(a["key"]), heads(a["value"])
    scores = product(functools.partial(jnp.einsum, "bqhk,bshk->bhqs"),
                     q / jnp.sqrt(jnp.float32(q.shape[-1])), k)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    mixed = product(functools.partial(jnp.einsum, "bhqs,bshk->bqhk"),
                    weights, v)
    x = x + product(functools.partial(jnp.einsum, "bqhk,hkd->bqd"), mixed,
                    a["out"]["kernel"]) + a["out"]["bias"]
    h = _layer_norm(x, p["ln_mlp"])
    h = _gelu_tanh(product(jnp.matmul, h, p["mlp_in"]["kernel"])
                   + p["mlp_in"]["bias"])
    return x + product(jnp.matmul, h, p["mlp_out"]["kernel"]) \
        + p["mlp_out"]["bias"]


def reference_loss(params, tokens, config, num=numerics.Exact):
    """Mean next-token cross entropy of ``tokens`` [B, T] in float32. The
    blocks' products go through ``num`` (the configuration computes them
    in bfloat16); the output head stays float32, as the configuration
    states. The blocks are run as one scan over their stacked parameters
    (one block to compile, not ``n_layer``), each rematerialised so that
    the sequences fit."""
    seq = tokens.shape[1]
    x = params["tok_embed"]["embedding"][tokens] \
        + params["pos_embed"]["embedding"][:seq]
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"block_{i}"] for i in range(config["n_layer"])))
    block = jax.checkpoint(functools.partial(_block, num=num))
    x, _ = jax.lax.scan(lambda x, p: (block(p, x), None), x, stacked)
    x = _layer_norm(x, params["ln_final"])
    logits = x @ params["lm_head"]["kernel"] + params["lm_head"]["bias"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def _loss_and_grad(params, tokens, config, num, rows):
    """Loss and gradient over the whole batch, ``rows`` sequences at a
    time (all sequences are equally long, so the mean of the blocks' means
    is the batch mean)."""
    blocks = tokens.reshape(-1, rows, tokens.shape[-1])

    def one(carry, block):
        loss, grad = jax.value_and_grad(reference_loss)(
            params, block, config, num)
        return (carry[0] + loss,
                jax.tree_util.tree_map(jnp.add, carry[1], grad)), None

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grad), _ = jax.lax.scan(one, zero, blocks)
    n = blocks.shape[0]
    return loss / n, jax.tree_util.tree_map(lambda g: g / n, grad)


def _adamw(params, grad, mu, nu, count, o):
    """One AdamW update written out (Loshchilov & Hutter 2019, as optax
    composes it: bias-corrected moments, decoupled decay on every leaf)."""
    def leaf(p, g, m, v):
        m = o["b1"] * m + (1.0 - o["b1"]) * g
        v = o["b2"] * v + (1.0 - o["b2"]) * jnp.square(g)
        m_hat = m / (1.0 - o["b1"] ** count)
        v_hat = v / (1.0 - o["b2"] ** count)
        step = m_hat / (jnp.sqrt(v_hat) + o["eps"]) + o["weight_decay"] * p
        return p - o["learning_rate"] * step, m, v

    out = jax.tree_util.tree_map(leaf, params, grad, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, t: t[i], params, out)
    return pick(0), pick(1), pick(2)


def reference_run(config, traffic, keys, steps: int, precision="float32"):
    """The reference trainer on one device: seeded weights, the first
    ``steps`` batches of the pool, the global batch, AdamW written out.
    Returns what ``correct`` compares: each step's loss, the norm of the
    first gradient and of the parameters' change after ``steps``, leaf by
    leaf. ``precision`` ``"fp8"`` is the control."""
    num = numerics.NUMERICS[precision]
    weight_key, pool_key = keys
    rows = 2 if traffic["global_batch"] % 2 == 0 else 1
    with jax.default_matmul_precision("highest"):
        (params0,) = jax.jit(functools.partial(init_model_state, config))(
            weight_key)
        pool = jax.jit(functools.partial(make_pool, config, traffic))(
            pool_key)
        started = time.perf_counter()
        grad_fn = jax.jit(functools.partial(
            _loss_and_grad, config=config, num=num, rows=rows)).lower(
                params0, pool[0][0]).compile()
        compile_s = time.perf_counter() - started
        update = jax.jit(functools.partial(_adamw, o=config["optimizer"]),
                         donate_argnums=(0, 2, 3))
        params = jax.tree_util.tree_map(jnp.copy, params0)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params0)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params0)
        losses, grad_norms = [], None
        for i in range(steps):
            loss, grad = grad_fn(params, pool[i % len(pool)][0])
            losses.append(float(loss))
            if i == 0:
                grad_norms = numerics.leaf_norms(grad)
            params, mu, nu = update(params, grad, mu, nu, float(i + 1))
            del grad
        update_norms = numerics.difference_norms(params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms, "compile_s": compile_s}
