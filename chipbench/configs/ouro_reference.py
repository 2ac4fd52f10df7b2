"""Plain reference of Ouro-2.6B's looped training step: forward of every
pass, the expected loss under the exit distribution, gradients and Adam.

Straightforward ``jax.numpy`` in float32, every matrix product under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product runs
in bf16 passes unless told otherwise). No kernel, no cache, no batching, and
nothing imported from ``sparkflow_tpu``: the benchmark makes the weights here
from ``--seed`` and hands the same tree to the program and to this file.

The model (ByteDance/Ouro-2.6B ``config.json``, ``model_type: ouro``; "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741). A row of
``S`` ids; ``T = total_ut_steps``; ``L = num_hidden_layers``; ``N(.; g)`` is
RMSNorm with scale ``g`` and ``rms_norm_eps``::

    x = E[ids]
    for t = 1..T:                                   # the same L layers each time
      for l = 1..L:
        y = N(x; g1_l)
        q, k, v = y Wq_l, y Wk_l, y Wv_l            # heads of head_dim, no bias
        q, k = rope(q), rope(k)                     # rotate-half, whole head
        a = softmax_causal(q k^T / sqrt(head_dim)) v  Wo_l
        x = x + N(a; g2_l)                          # a norm after the sub-layer too
        y = N(x; g3_l)
        m = (silu(y Wg_l) * (y Wu_l)) Wd_l
        x = x + N(m; g4_l)
      h_t = N(x; g_f);  x = h_t                     # the normed state goes on
      z_t = h_t W_head                              # logits [S, vocab], untied
      lam_t = sigmoid(h_t . w_e + b_e)              # the exit gate, [S]
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j);  p_T = prod_{j<T}(1 - lam_j)
    loss(row) = mean over the S-1 predicted positions i of
                sum_t p_t(i) CE(z_t(i), ids[i+1])  -  beta * H(p(i))

``H`` is the entropy of a token's ``T``-way exit distribution; gradients flow
through the ``p_t`` into the gate and the trunk. ``lam_T`` enters nothing.

What the published ``config.json`` (the catalog's ``config``) does not carry,
each listed under ``assumed`` in the configuration file with where it comes
from: the sandwich placement of the norms (one before and one after each
sub-layer: ``g1 .. g4``); that the final norm's output feeds the next pass;
the gate's form (one linear map of the normed state to a scalar with a bias,
a sigmoid); ``beta`` (``exit_entropy_weight``, 0.1: the paper's stage-I
objective, the expected task loss with a uniform prior over the exit steps);
that there is no bias elsewhere; that the loss is per token. Inference-time
early exit (``early_exit_threshold``) is not used: training runs every pass.

Departures that change no number: the exit distribution is made from
``log_sigmoid`` (``log p_t`` is a sum, so ``p log p`` is finite wherever a gate
saturates); attention is computed a block of queries at a time
(``query_block``), so are a pass's logits and cross-entropies, and both are
made again in the backward pass (``jax.checkpoint``), so that a 4096-token row over a vocabulary
of 49 152 fits beside 510 M parameters and their Adam state.

``matmul`` is a hook for the control of the comparison: ``int8_matmul``
computes the same model in the nearest precision below the configuration's
bf16, and the comparison has to fail it (chipbench/tests/test_runs_ouro.py).
The gate's own product stays float32, as the program keeps it.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PRECISION = "highest"
NEG = -1e30


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    """The sizes the model is made of, under short names."""
    return dict(
        h=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        d=cfg["head_dim"], m=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        passes=cfg["total_ut_steps"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        beta=float(cfg["exit_entropy_weight"]))


def param_shapes(cfg: dict) -> Dict[str, Dict[str, Tuple[tuple, str]]]:
    """The weight tree as ``{group: {leaf: (shape, law)}}``, in the layout
    the registry's ``looped_lm`` takes. ``law`` is ``normal``, ``ones`` or
    ``zeros``."""
    z = sizes(cfg)
    h, hd, m = z["h"], z["heads"] * z["d"], z["m"]
    block = {
        "ln1_scale": ((h,), "ones"),
        "q_kernel": ((h, hd), "normal"), "k_kernel": ((h, hd), "normal"),
        "v_kernel": ((h, hd), "normal"), "o_kernel": ((hd, h), "normal"),
        "ln1_post_scale": ((h,), "ones"),
        "ln2_scale": ((h,), "ones"),
        "gate_kernel": ((h, m), "normal"), "up_kernel": ((h, m), "normal"),
        "down_kernel": ((m, h), "normal"),
        "ln2_post_scale": ((h,), "ones"),
    }
    tree = {"embed": {"tok": ((z["vocab"], h), "normal")}}
    for i in range(z["layers"]):
        tree[f"block_{i}"] = dict(block)
    tree["final_ln"] = {"scale": ((h,), "ones")}
    tree["lm_head"] = {"kernel": ((h, z["vocab"]), "normal")}
    tree["exit_gate"] = {"kernel": ((h, 1), "normal"), "bias": ((1,), "zeros")}
    return tree


def init_params(cfg: dict, seed: int):
    """Every weight from ``seed`` in ONE jitted call on the device: float32,
    kernels and embeddings N(0, initializer_range), unit norm scales, the
    gate's bias 0."""
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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * scale


def rope(x, theta: float):
    """Rotary positions over the whole last axis of ``x [S, heads, D]``
    (rotate-half: the pairs are ``(i, i + D/2)``); row ``i`` is at position
    ``i``."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attend_rows(q, k, v, first_row):
    """A block of queries (the indices ``first_row ..``) of one row against
    its keys, causally: ``q [Q, heads, d]``, ``k, v [S, heads, d]``."""
    t, _, d = q.shape
    mask = (first_row + jnp.arange(t))[:, None] >= jnp.arange(k.shape[0])
    s = jnp.einsum("tnd,snd->nts", q, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
    return jnp.einsum("nts,snd->tnd", p, v)


def block(x, p, cfg: dict, matmul: Callable = jnp.matmul,
          query_block: int = 0):
    """One layer on one row ``x [S, hidden]`` (float32)."""
    z = sizes(cfg)
    s = x.shape[0]
    y = rms_norm(x, p["ln1_scale"], z["eps"])
    q = rope(matmul(y, p["q_kernel"]).reshape(s, z["heads"], z["d"]),
             z["theta"])
    k = rope(matmul(y, p["k_kernel"]).reshape(s, z["heads"], z["d"]),
             z["theta"])
    v = matmul(y, p["v_kernel"]).reshape(s, z["heads"], z["d"])
    qb = query_block if query_block and s % query_block == 0 else s
    rows = jax.checkpoint(lambda a: attend_rows(a[0], k, v, a[1]))
    att = jax.lax.map(rows, (q.reshape((s // qb, qb) + q.shape[1:]),
                             jnp.arange(0, s, qb)))
    a = matmul(att.reshape(s, z["heads"] * z["d"]), p["o_kernel"])
    x = x + rms_norm(a, p["ln1_post_scale"], z["eps"])
    y = rms_norm(x, p["ln2_scale"], z["eps"])
    m = matmul(jax.nn.silu(matmul(y, p["gate_kernel"]))
               * matmul(y, p["up_kernel"]), p["down_kernel"])
    return x + rms_norm(m, p["ln2_post_scale"], z["eps"])


def one_pass(params, x, cfg: dict, matmul: Callable = jnp.matmul,
             query_block: int = 0, remat: bool = False):
    """The ``L`` layers once, then the final norm: ``x [S, h] -> h_t``."""
    step = lambda a, p: block(a, p, cfg, matmul, query_block)
    if remat:
        step = jax.checkpoint(step)
    for i in range(cfg["num_hidden_layers"]):
        x = step(x, params[f"block_{i}"])
    return rms_norm(x, params["final_ln"]["scale"], cfg["rms_norm_eps"])


def gate_logit(params, h):
    """The exit gate before its sigmoid, ``[S]``, in float32 whatever
    ``matmul`` the rest of the model uses."""
    return (jnp.matmul(h, params["exit_gate"]["kernel"])[:, 0]
            + params["exit_gate"]["bias"][0])


def exit_log_probs(logits):
    """``log p_t`` of the exit distribution from every pass's gate logit
    ``[T, S]`` (the last pass's is not asked): ``[T, S]``."""
    asked = logits[:-1]
    # stay[t] = log prod_{j<t}(1 - lam_j)
    stay = jnp.concatenate([jnp.zeros_like(logits[:1]),
                            jnp.cumsum(jax.nn.log_sigmoid(-asked), axis=0)])
    return jnp.concatenate([stay[:-1] + jax.nn.log_sigmoid(asked), stay[-1:]])


def forward_row(params, ids, cfg: dict, matmul: Callable = jnp.matmul,
                query_block: int = 0):
    """``ids [S] -> (logits of every pass [T, S, vocab], log p [T, S])``."""
    x = params["embed"]["tok"][ids]
    logits, gates = [], []
    for _ in range(cfg["total_ut_steps"]):
        x = one_pass(params, x, cfg, matmul, query_block)
        logits.append(matmul(x, params["lm_head"]["kernel"]))
        gates.append(gate_logit(params, x))
    return jnp.stack(logits), exit_log_probs(jnp.stack(gates))


def forward(params, ids, cfg: dict, matmul: Callable = jnp.matmul):
    """``ids [rows, S] -> logits of every pass [rows, T, S, vocab]``."""
    return jax.lax.map(
        lambda row: forward_row(params, row, cfg, matmul)[0], ids)


def row_losses(params, ids, cfg: dict, matmul: Callable = jnp.matmul,
               query_block: int = 0):
    """Each row's loss ``[rows]`` and what it is made of: every pass's mean
    cross-entropy ``ce [rows, T]``, the mean exit distribution ``exit_mass
    [rows, T]`` and the mean entropy ``entropy [rows]``, all over the ``S -
    1`` predicted positions."""
    beta = float(cfg["exit_entropy_weight"])

    @jax.checkpoint
    def stretch_ce(a):
        h, tgt = a
        logp = jax.nn.log_softmax(matmul(h, params["lm_head"]["kernel"]),
                                  axis=-1)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    def one(row):
        s = row.shape[0]
        qb = query_block if query_block and s % query_block == 0 else s
        # the last position predicts nothing: a filler target, dropped below
        tgt = jnp.concatenate([row[1:], row[:1]]).reshape(s // qb, qb)

        def again(x, _):
            x = one_pass(params, x, cfg, matmul, query_block, remat=True)
            ce = jax.lax.map(stretch_ce, (x.reshape(s // qb, qb, -1), tgt))
            return x, (ce.reshape(s)[:-1], gate_logit(params, x[:-1]))

        # a loop the compiler keeps, so that the passes' gradients of the
        # shared weights add up in one set of sums
        _, (ce, gates) = jax.lax.scan(again, params["embed"]["tok"][row],
                                      None, length=cfg["total_ut_steps"])
        logp = exit_log_probs(gates)                          # [T, S - 1]
        p = jnp.exp(logp)
        entropy = -jnp.sum(p * logp, axis=0)
        loss = jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
        return loss, (jnp.mean(ce, axis=1), jnp.mean(p, axis=1),
                      jnp.mean(entropy))

    total, (ce, mass, entropy) = jax.lax.map(one, ids)
    return total, dict(ce=ce, exit_mass=mass, entropy=entropy)


def loss(params, ids, cfg: dict, matmul: Callable = jnp.matmul,
         query_block: int = 0):
    return jnp.mean(row_losses(params, ids, cfg, matmul, query_block)[0])


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
                row_block: int = 1, matmul: Callable = jnp.matmul,
                query_block: int = 0, again: Callable = None,
                first_moment: bool = False):
    """Follow one Adam step (optax's ``adam``) on each of ``batches [T, B,
    S]``, in their order. ``again()`` gives the same ``params`` once more:
    the steps then take ``params``' own buffers and the change is taken
    against ``again()`` at the end. Returns every step's loss (taken before
    its update), and per leaf the norm of the root of the second moment after
    the last step and the norm of the parameters' change; with
    ``first_moment`` also Adam's first moment after the last step, as a tree
    (after one step a tenth of the gradient, after few a fixed mix of theirs:
    a caller can take the gradients' DIFFERENCE from another run's, which a
    norm's gap hardly feels). A batch's gradient
    is the mean of the gradients of its blocks of ``row_block`` rows; the
    loss is a mean over rows, so the blocks' mean is the batch's. Each
    block's gradient is a program of its own and the sums are kept between
    them: in one program with the update the compiler held five trees of 510
    M float32 parameters beside the weights and Adam's two moments (16.2 GB
    by its count, of the chip's 16.9); this way four (13.8 GB)."""
    batches = jnp.asarray(batches, jnp.int32)
    steps, n, s = batches.shape
    if n % row_block:
        raise ValueError(f"{n} rows do not divide into blocks of {row_block}")
    blocks = n // row_block

    def add_block(acc, new):
        return jax.tree.map(lambda a, x: a + x / blocks, acc, new)

    def update(state, g, t):
        p, mu, nu = state
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        upd = jax.tree.map(
            lambda m, v: -learning_rate * (m / c1) / (jnp.sqrt(v / c2) + eps),
            mu, nu)
        return jax.tree.map(jnp.add, p, upd), mu, nu

    first = None if again else params
    with jax.default_matmul_precision(PRECISION):
        grad = jax.jit(jax.value_and_grad(
            lambda p, ids: loss(p, ids, cfg, matmul, query_block)))
        add_block = jax.jit(add_block, donate_argnums=0)
        update = jax.jit(update, donate_argnums=0)
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        state = (params if again else jax.tree.map(jnp.array, params),
                 zeros(params), zeros(params))
        del params
        losses = []
        for t in range(steps):
            acc = (jnp.float32(0.0), zeros(state[0]))
            for ids in batches[t].reshape(blocks, row_block, s):
                acc = add_block(acc, grad(state[0], ids))
            losses.append(acc[0])
            state = update(state, acc[1], jnp.float32(t + 1))
            del acc
        p, mu, nu = state
        del state
        energy = leaf_norms(nu, cfg, of_root=True)
        del nu
        delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                        donate_argnums=0)(p, again() if again else first)
        out = dict(losses=[float(l) for l in losses], energy_norms=energy,
                   change_norms=leaf_norms(delta, cfg))
        if first_moment:
            out["first_moment"] = mu
        return out


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
