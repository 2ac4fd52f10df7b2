"""Plain reference of the GPT-2 family: forward, loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32, every matrix product under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product runs
in bf16 passes unless told otherwise). No kernel, no cache, no batching, and
nothing imported from ``sparkflow_tpu``: the benchmark makes the weights here
from ``--seed`` and hands the same tree to the program and to this file.

Follows the published GPT-2 (Radford et al. 2019; Hugging Face
``modeling_gpt2.py``): learned token and position embeddings, pre-LN blocks
of causal multi-head attention and a ``gelu_new`` MLP with biases, a final
layer norm and the token embedding as the output head. One departure, which
the configuration file lists under ``reduced``: ``layer_norm_epsilon`` is the
program's 1e-6.

``matmul`` is a hook for the control of the comparison: ``int8_matmul``
computes the same model in the nearest precision below the configuration's
bf16, and the comparison has to fail it (chipbench/tests/test_runs.py).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def param_shapes(cfg: dict) -> Dict[str, Dict[str, Tuple[tuple, str]]]:
    """The weight tree as ``{group: {leaf: (shape, law)}}``, in the layout
    the registry's ``transformer_lm`` takes. ``law`` is ``normal``, ``ones``
    or ``zeros``."""
    h, m = cfg["n_embd"], cfg["n_inner"]
    block = {
        "ln1_scale": ((h,), "ones"), "ln1_bias": ((h,), "zeros"),
        "qkv_kernel": ((h, 3 * h), "normal"), "qkv_bias": ((3 * h,), "zeros"),
        "o_kernel": ((h, h), "normal"), "o_bias": ((h,), "zeros"),
        "ln2_scale": ((h,), "ones"), "ln2_bias": ((h,), "zeros"),
        "fc1_kernel": ((h, m), "normal"), "fc1_bias": ((m,), "zeros"),
        "fc2_kernel": ((m, h), "normal"), "fc2_bias": ((h,), "zeros"),
    }
    tree = {"embed": {"tok": ((cfg["vocab_size"], h), "normal"),
                      "pos": ((cfg["n_positions"], h), "normal")}}
    for i in range(cfg["n_layer"]):
        tree[f"block_{i}"] = dict(block)
    tree["final_ln"] = {"scale": ((h,), "ones"), "bias": ((h,), "zeros")}
    return tree


def init_params(cfg: dict, seed: int):
    """Every weight from ``seed`` in ONE jitted call on the device: float32,
    kernels and embeddings N(0, initializer_range), zero biases, unit
    layer-norm scales (GPT-2's own initialisation)."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])

    def make(key):
        out, n = {}, 0
        for group, leaves in shapes.items():
            out[group] = {}
            for name, (shape, law) in leaves.items():
                if law == "normal":
                    out[group][name] = std * jax.random.normal(
                        jax.random.fold_in(key, n), shape, jnp.float32)
                elif law == "ones":
                    out[group][name] = jnp.ones(shape, jnp.float32)
                else:
                    out[group][name] = jnp.zeros(shape, jnp.float32)
                n += 1
        return out

    # a seed may need more than 32 signed bits: fold it in two halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(make)(key)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, cfg: dict, matmul: Callable = jnp.matmul):
    """One pre-LN block on ``x [B, S, hidden]`` (float32)."""
    b, s, h = x.shape
    nh = cfg["n_head"]
    hd = h // nh
    eps = cfg["layer_norm_epsilon"]
    y = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
    qkv = (matmul(y, p["qkv_kernel"]) + p["qkv_bias"]).reshape(b, s, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + matmul(att.reshape(b, s, h), p["o_kernel"]) + p["o_bias"]
    y = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
    y = _gelu_new(matmul(y, p["fc1_kernel"]) + p["fc1_bias"])
    return x + matmul(y, p["fc2_kernel"]) + p["fc2_bias"]


def embed(params, ids):
    s = ids.shape[-1]
    return params["embed"]["tok"][ids] + params["embed"]["pos"][:s]


def head(params, x, cfg: dict, matmul: Callable = jnp.matmul):
    x = _layer_norm(x, params["final_ln"]["scale"], params["final_ln"]["bias"],
                    cfg["layer_norm_epsilon"])
    return matmul(x, params["embed"]["tok"].T)


def stack_blocks(params, cfg: dict):
    """The blocks' weights stacked along a new leading axis, so that one
    traced block serves every layer (``jax.lax.scan``)."""
    return {name: jnp.stack([params[f"block_{i}"][name]
                             for i in range(cfg["n_layer"])])
            for name in params["block_0"]}


def forward(params, ids, cfg: dict, matmul: Callable = jnp.matmul,
            remat: bool = False):
    """``ids [B, S] -> logits [B, S, vocab]``: embed, the blocks one after
    another (a scan over the stacked layers, so a deep model is traced and
    compiled as one block), final layer norm and the tied head."""
    step = lambda x, p: (block(x, p, cfg, matmul), None)
    if remat:
        step = jax.checkpoint(step)
    x, _ = jax.lax.scan(step, embed(params, ids), stack_blocks(params, cfg))
    return head(params, x, cfg, matmul)


def loss(params, ids, cfg: dict, matmul: Callable = jnp.matmul):
    """Next-token cross entropy: the mean over the rows of each row's mean
    over its ``S - 1`` predicted positions. Blocks are rematerialised so a
    row's activations fit beside the weights and the optimizer's state."""
    logits = forward(params, ids, cfg, matmul, remat=True)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jnp.mean(nll, axis=-1))


# ---------------------------------------------------------------------------
# training: gradients row block by row block, and Adam
# ---------------------------------------------------------------------------


def leaf_names(cfg: dict) -> List[str]:
    return [f"{g}/{n}" for g, leaves in param_shapes(cfg).items()
            for n in leaves]


def leaf_norms(tree, cfg: dict, of_root: bool = False) -> np.ndarray:
    """The L2 norm of every leaf, in ``leaf_names`` order (one jitted call);
    ``of_root`` takes each leaf's elementwise square root first, so that
    Adam's second moment gives the gradients' energy."""
    flat = [tree[g][n] for g, leaves in param_shapes(cfg).items()
            for n in leaves]
    square = (lambda l: l) if of_root else jnp.square
    return np.asarray(jax.jit(
        lambda ls: jnp.stack([jnp.sqrt(jnp.sum(square(
            l.astype(jnp.float32)))) for l in ls]))(flat), np.float64)


def train_steps(params, batches, cfg: dict, *, learning_rate: float,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                row_block: int = 1, matmul: Callable = jnp.matmul):
    """Follow one Adam step (optax's ``adam``: bias-corrected moments, no
    weight decay) on each of ``batches [T, B, S]``, in their order. Returns
    every step's loss (taken before its update), and per leaf the norm of
    the root of the second moment after the last step (the energy of all the
    steps' gradients, as the optimizer got them) and the norm of the
    parameters' change. A batch's gradient is the mean of the gradients of
    its blocks of ``row_block`` rows, taken one block at a time so that the
    activations of one block are all that is alive."""
    batches = jnp.asarray(batches, jnp.int32)
    steps, n, s = batches.shape
    if n % row_block:
        raise ValueError(f"{n} rows do not divide into blocks of {row_block}")
    blocks = n // row_block
    grad = jax.value_and_grad(lambda p, ids: loss(p, ids, cfg, matmul))

    def step(state, batch, t):
        p, delta, mu, nu = state

        def add_block(acc, ids):
            l, g = grad(p, ids)
            return jax.tree.map(lambda a, x: a + x / blocks, acc, (l, g)), None

        zero = (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, p))
        (l, g), _ = jax.lax.scan(add_block, zero,
                                 batch.reshape(blocks, row_block, s))
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        upd = jax.tree.map(
            lambda m, v: -learning_rate * (m / c1) / (jnp.sqrt(v / c2) + eps),
            mu, nu)
        return (jax.tree.map(jnp.add, p, upd),
                jax.tree.map(jnp.add, delta, upd), mu, nu), l

    with jax.default_matmul_precision(PRECISION):
        step = jax.jit(step, donate_argnums=0)
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        # a copy of the weights: the step donates its state
        state = (jax.tree.map(jnp.array, params), zeros(params),
                 zeros(params), zeros(params))
        losses = []
        for t in range(steps):
            state, l = step(state, batches[t], jnp.float32(t + 1))
            losses.append(l)
        _, delta, _, nu = state
        return dict(losses=[float(l) for l in losses],
                    energy_norms=leaf_norms(nu, cfg, of_root=True),
                    change_norms=leaf_norms(delta, cfg))


# ---------------------------------------------------------------------------
# the control: the same model with int8 matrix products
# ---------------------------------------------------------------------------


def _q8(x, axis):
    """Symmetric int8 with one scale per slice along ``axis``, returned in
    float32: the values a dynamic int8 product multiplies."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def int8_matmul(x, w):
    """``x [..., K] @ w [K, N]`` with both operands rounded to int8
    (activations per row, weights per column) and exact accumulation."""
    return jnp.matmul(_q8(x, -1), _q8(w, 0))


def _int8_fwd(x, w):
    return int8_matmul(x, w), (x, w)


def _int8_bwd(res, g):
    x, w = res
    gq = _q8(g, -1)
    dx = jnp.matmul(gq, _q8(w, 0).T)
    x2, g2 = x.reshape(-1, x.shape[-1]), gq.reshape(-1, g.shape[-1])
    return dx, jnp.matmul(_q8(x2, 0).T, g2)


int8_matmul.defvjp(_int8_fwd, _int8_bwd)


# ---------------------------------------------------------------------------
# the numbers of the comparison
# ---------------------------------------------------------------------------


def leaf_gaps(program: Sequence[float], reference: Sequence[float]
              ) -> np.ndarray:
    """Every leaf's gap between the program's norm and the reference's (not
    the norm of their difference), against the reference's norm of that leaf
    or of the median leaf, whichever is larger: some gradients are all but
    zero."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    return np.abs(program - reference) / np.maximum(
        reference, float(np.median(reference)))
