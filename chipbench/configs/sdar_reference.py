"""Plain reference of SDAR-30B-A3B-Chat's block-diffusion training as one
chip's share of a deployment holds it: forward, masked-token loss, gradients
and Adam.

Straightforward ``jax.numpy`` in float32, every matrix product under
``jax.default_matmul_precision("highest")``. No kernel, no cache, no grouped
product, and nothing imported from ``sparkflow_tpu``: the benchmark makes the
weights here from a seed (the one its traffic mix fixes: every run of a cell
trains the same model on the rows ``--seed`` makes) and hands the same tree
to the program and to this file.

A training row is ``r = [c ; n]`` of ``2L`` ids: ``c`` the ``L`` clean
tokens, ``n`` their noised copy, ``n_i`` either ``c_i`` or ``mask_token_id``.
With ``block_length`` ``B``, for an index ``p`` in ``[0, 2L)``: ``pos(p) = p
mod L``, ``blk(p) = pos(p) // B``, and ``p`` is *clean* if ``p < L``, *noised*
else (ISSUE 32; what the published ``config.json`` does not settle is listed
under ``assumed`` in the configuration file).

1. ``x_p = E[r_p]``: ``E`` holds the vocabulary slice and one more row, the
   mask token's.
2. Each layer, pre-norm and residual: ``h = RMSNorm(x)``; ``q, k, v = W_q h,
   W_k h, W_v h`` (32 query heads over 4 KV heads of 128, no bias); RMSNorm
   over each head of ``q`` and ``k``; rotate-half rotary positions over all
   128 at ``pos(p)`` (the two copies of a token share a position); softmax
   attention with scale ``1 / sqrt(128)`` over the keys ``s`` that ``p`` may
   see:

   - ``p`` clean: ``s`` clean and ``blk(s) <= blk(p)``;
   - ``p`` noised: ``s`` clean and ``blk(s) < blk(p)``, or ``s`` noised and
     ``blk(s) == blk(p)``.

   ``x += W_o att``. Then ``h' = RMSNorm(x)``; the router's softmax over all
   ``published_num_experts`` in float32; the token's top
   ``num_experts_per_tok``, their probabilities normalised to one; ``x +=
   sum_{e in top, e held here} g_e W2_e (silu(W1_e h') * W3_e h')``. What the
   experts not held here would add is left out. All ``2L`` positions go
   through every layer.
3. Final RMSNorm and the untied head on the noised half only. The row's loss
   is the mean over its ``L / B`` blocks of the block's loss; a block's loss
   is the mean, over its masked positions ``i``, of the cross-entropy of the
   logits at index ``L + i`` against ``c_i`` (no shift), over the vocabulary
   held; plus ``router_aux_loss_coef`` times each layer's balance loss over
   all experts and all ``2L`` positions (``E * sum_e f_e p_e``).

Departures from the published description, each because ``config.json`` and
the catalog say nothing else: the block length, the noise law, the unshifted
targets and the mask token's id are the configuration file's ``assumed``; the
share (experts ``experts_held_start ..``, the vocabulary slice) is its
``reduced``. The mask is built from the rule above for a block of queries at
a time (``query_block``), so that 8192 positions fit: nothing else is in
blocks.

``matmul`` is a hook for the control of the comparison: ``int8_matmul``
computes the same model in the nearest precision below the configuration's
bf16, and the comparison has to fail it.
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
    """The sizes a layer is made of, under short names."""
    return dict(
        h=cfg["hidden_size"], nq=cfg["num_attention_heads"],
        nkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        e_all=cfg["published_num_experts"], e_held=cfg["num_experts"],
        e_start=cfg.get("experts_held_start", 0),
        per_tok=cfg["num_experts_per_tok"], m=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], v_start=cfg.get("vocab_held_start", 0),
        mask_id=cfg["mask_token_id"], block=cfg["block_length"],
        layers=cfg["num_hidden_layers"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]))


def param_shapes(cfg: dict) -> Dict[str, Dict[str, Tuple[tuple, str]]]:
    """The weight tree as ``{group: {leaf: (shape, law)}}``, in the layout
    the registry's ``block_diffusion_lm`` takes. ``law`` is ``normal`` or
    ``ones``. The embedding's last row is the mask token's."""
    z = sizes(cfg)
    h = z["h"]
    block = {
        "ln1_scale": ((h,), "ones"),
        "q_kernel": ((h, z["nq"] * z["d"]), "normal"),
        "k_kernel": ((h, z["nkv"] * z["d"]), "normal"),
        "v_kernel": ((h, z["nkv"] * z["d"]), "normal"),
        "q_norm": ((z["d"],), "ones"), "k_norm": ((z["d"],), "ones"),
        "o_kernel": ((z["nq"] * z["d"], h), "normal"),
        "ln2_scale": ((h,), "ones"),
        "router": ((h, z["e_all"]), "normal"),
        "experts_w1": ((z["e_held"], h, z["m"]), "normal"),
        "experts_w3": ((z["e_held"], h, z["m"]), "normal"),
        "experts_w2": ((z["e_held"], z["m"], h), "normal"),
    }
    tree = {"embed": {"tok": ((z["vocab"] + 1, h), "normal")}}
    for i in range(z["layers"]):
        tree[f"block_{i}"] = dict(block)
    tree["final_ln"] = {"scale": ((h,), "ones")}
    tree["lm_head"] = {"kernel": ((h, z["vocab"]), "normal")}
    return tree


def init_params(cfg: dict, seed: int):
    """Every weight from ``seed`` in ONE jitted call on the device: float32,
    kernels and embeddings N(0, initializer_range), unit norm scales."""
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
                else:
                    out[group][name] = jnp.ones(shape, jnp.float32)
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


def rope(x, pos, theta: float):
    """Rotary positions over the whole last axis of ``x [S, ..., D]``
    (rotate-half: the pairs are ``(i, i + D/2)``); row ``i`` is at position
    ``pos[i]``."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def sees(p, s, length: int, block: int):
    """The rule: may the query at index ``p`` see the key at index ``s``
    (arrays that broadcast), in a row of ``2 * length`` indices."""
    p_clean, s_clean = p < length, s < length
    bp, bs = (p % length) // block, (s % length) // block
    return ((p_clean & s_clean & (bs <= bp))
            | (~p_clean & s_clean & (bs < bp))
            | (~p_clean & ~s_clean & (bs == bp)))


def attend_rows(q, k, v, first_row, length: int, block: int):
    """A block of queries (the indices ``first_row ..``) of one row against
    all its keys: ``q [T, nq, d]``, ``k, v [2L, nkv, d]`` -> ``[T, nq, d]``."""
    t, nq, d = q.shape
    nkv = k.shape[1]
    mask = sees(first_row + jnp.arange(t)[:, None],
                jnp.arange(k.shape[0])[None, :], length, block)
    # query head j reads key/value head j // (nq / nkv)
    qg = q.reshape(t, nkv, nq // nkv, d)
    s = jnp.einsum("tngd,snd->ngts", qg, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(mask, s, NEG), axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("ngts,snd->tngd", p, v).reshape(t, nq, d)


def experts(y, p, cfg: dict, matmul: Callable):
    """The held experts' part of the layer's output for the positions ``y [N,
    h]``, the row's balance loss, and how many positions each held expert
    got."""
    z = sizes(cfg)
    logits = jnp.matmul(y, p["router"])              # the router stays float32
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, z["per_tok"])
    gates = top / jnp.sum(top, axis=-1, keepdims=True) \
        if cfg.get("norm_topk_prob", True) else top
    chosen = jax.nn.one_hot(idx, z["e_all"], dtype=jnp.float32)   # [N, k, E]
    balance = z["e_all"] * jnp.sum(
        jnp.sum(jnp.mean(chosen, axis=0), axis=0) * jnp.mean(probs, axis=0))
    gate_of = jnp.einsum("nk,nke->ne", gates, chosen)             # [N, E]
    held = gate_of[:, z["e_start"]:z["e_start"] + z["e_held"]]

    def one(acc, ew):
        w1, w3, w2, g = ew
        hidden = jax.nn.silu(matmul(y, w1)) * matmul(y, w3)
        return acc + g[:, None] * matmul(hidden, w2), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          (p["experts_w1"], p["experts_w3"], p["experts_w2"],
                           held.T))
    load = jnp.sum(chosen, axis=(0, 1))[z["e_start"]:z["e_start"]
                                        + z["e_held"]]
    return out, balance, load


def block(x, p, cfg: dict, matmul: Callable = jnp.matmul,
          query_block: int = 0):
    """One layer on one row ``x [2L, hidden]`` (float32). Returns the row,
    the balance loss and the positions each held expert got."""
    z = sizes(cfg)
    s = x.shape[0]
    length = s // 2
    pos = jnp.arange(s) % length
    y = rms_norm(x, p["ln1_scale"], z["eps"])
    q = matmul(y, p["q_kernel"]).reshape(s, z["nq"], z["d"])
    k = matmul(y, p["k_kernel"]).reshape(s, z["nkv"], z["d"])
    v = matmul(y, p["v_kernel"]).reshape(s, z["nkv"], z["d"])
    q = rope(rms_norm(q, p["q_norm"], z["eps"]), pos, z["theta"])
    k = rope(rms_norm(k, p["k_norm"], z["eps"]), pos, z["theta"])

    qb = query_block if query_block and s % query_block == 0 else s
    rows = jax.checkpoint(
        lambda a: attend_rows(a[0], k, v, a[1], length, z["block"]))
    att = jax.lax.map(rows, (q.reshape((s // qb, qb) + q.shape[1:]),
                             jnp.arange(0, s, qb)))
    x = x + matmul(att.reshape(s, z["nq"] * z["d"]), p["o_kernel"])

    y = rms_norm(x, p["ln2_scale"], z["eps"])
    out, balance, load = experts(y, p, cfg, matmul)
    return x + out, balance, load


def stack_blocks(params, cfg: dict):
    return {name: jnp.stack([params[f"block_{i}"][name]
                             for i in range(cfg["num_hidden_layers"])])
            for name in params["block_0"]}


def forward_row(params, ids, cfg: dict, matmul: Callable = jnp.matmul,
                remat: bool = False, query_block: int = 0):
    """``ids [2L] -> (logits [L, vocab held] of the noised half, balance
    loss summed over the layers, expert load [layers, held])``."""
    z = sizes(cfg)

    def step(x, p):
        x, balance, load = block(x, p, cfg, matmul, query_block)
        return x, (balance, load)

    if remat:
        step = jax.checkpoint(step)
    x = params["embed"]["tok"][jnp.where(ids == z["mask_id"], z["vocab"],
                                         ids - z["v_start"])]
    x, (balance, load) = jax.lax.scan(step, x, stack_blocks(params, cfg))
    x = rms_norm(x[ids.shape[0] // 2:], params["final_ln"]["scale"], z["eps"])
    return matmul(x, params["lm_head"]["kernel"]), jnp.sum(balance), load


def noised_logits(params, row, cfg: dict, count: int,
                  matmul: Callable = jnp.matmul, query_block: int = 0):
    """The logits of one row's first ``count`` noised positions, ``[count,
    vocab held]``, at the precision of :func:`train_steps`. The earliest
    blocks see the fewest keys, so one key too many or too few (a rule wrong
    by a block) moves these logits most, and a whole row's gradient least."""
    with jax.default_matmul_precision(PRECISION):
        return jax.jit(lambda p, r: forward_row(
            p, r, cfg, matmul, query_block=query_block)[0][:count])(
                params, jnp.asarray(row, jnp.int32))


def forward(params, ids, cfg: dict, matmul: Callable = jnp.matmul):
    """``ids [rows, 2L] -> logits [rows, L, vocab held]``."""
    return jax.lax.map(
        lambda row: forward_row(params, row, cfg, matmul)[0], ids)


def row_losses(params, ids, cfg: dict, matmul: Callable = jnp.matmul,
               query_block: int = 0):
    """Each row's loss ``[rows]`` and its two parts."""
    z = sizes(cfg)

    def one(row):
        length = row.shape[0] // 2
        logits, balance, _ = forward_row(params, row, cfg, matmul, remat=True,
                                         query_block=query_block)
        logp = jax.nn.log_softmax(logits, axis=-1)
        clean = row[:length] - z["v_start"]
        nll = -jnp.take_along_axis(logp, clean[:, None], axis=-1)[:, 0]
        masked = (row[length:] == z["mask_id"]).reshape(-1, z["block"])
        per_block = (jnp.sum(jnp.where(masked, nll.reshape(masked.shape), 0.0),
                             axis=-1)
                     / jnp.maximum(jnp.sum(masked, axis=-1), 1))
        return jnp.mean(per_block), balance

    ce, balance = jax.lax.map(one, ids)
    total = ce + float(cfg.get("router_aux_loss_coef", 0.001)) * balance
    return total, dict(ce=ce, balance=balance)


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
    against ``again()`` at the end (four trees of 456 M float32 parameters,
    a gradient and a row's activations do not fit one chip together). Returns every step's loss (taken before its
    update), and per leaf the norm of the root of the second moment after
    the last step and the norm of the parameters' change; with
    ``first_moment`` also Adam's first moment after the last step, as a tree:
    after one step a tenth of the gradient, after few a fixed mix of theirs,
    so that a caller can take the gradients' DIFFERENCE from another run's
    (a norm's gap hardly feels a gradient that points elsewhere). A batch's gradient
    is the mean of the gradients of its blocks of ``row_block`` rows, taken
    one block at a time; every part of the loss is a row's own, so the
    blocks' mean is the batch's."""
    batches = jnp.asarray(batches, jnp.int32)
    steps, n, s = batches.shape
    if n % row_block:
        raise ValueError(f"{n} rows do not divide into blocks of {row_block}")
    blocks = n // row_block
    grad = jax.value_and_grad(
        lambda p, ids: loss(p, ids, cfg, matmul, query_block))

    first = None if again else params

    def step(state, batch, t):
        p, mu, nu = state

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
        return (jax.tree.map(jnp.add, p, upd), mu, nu), l

    with jax.default_matmul_precision(PRECISION):
        step = jax.jit(step, donate_argnums=0)
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        state = (params if again else jax.tree.map(jnp.array, params),
                 zeros(params), zeros(params))
        del params
        losses = []
        for t in range(steps):
            state, l = step(state, batches[t], jnp.float32(t + 1))
            losses.append(l)
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
