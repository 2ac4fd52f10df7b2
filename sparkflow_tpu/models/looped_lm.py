"""A looped causal LM: ONE stack of blocks run ``passes`` times with the same
weights, a head and an exit gate after every pass, and as loss the expected
cross-entropy under the exit distribution the gates make.

For a row of ``S`` ids (``T = passes``, ``L = num_layers``; ``N(.; g)`` is
RMSNorm with scale ``g``, computed in float32)::

    x = E[ids]
    for t = 1..T:                                   # the same L blocks each time
      for l = 1..L:
        y = N(x; ln1_scale)
        q, k, v = y Wq, y Wk, y Wv                  # num_heads of head_dim, no bias
        q, k = rope(q), rope(k)                     # rotate-half, whole head
        a = flash_attention(q, k, v, causal) Wo
        x = x + N(a; ln1_post_scale)                # sandwich: a norm after, too
        y = N(x; ln2_scale)
        m = (silu(y Wgate) * (y Wup)) Wdown
        x = x + N(m; ln2_post_scale)
      h_t = N(x; final_ln);  x = h_t                # the normed state goes on
      z_t = h_t W_head                              # float32 logits, untied
      lam_t = sigmoid(h_t . w_e + b_e)              # the exit gate, float32
    p_1 = lam_1;  p_t = lam_t prod_{j<t}(1 - lam_j);  p_T = prod_{j<T}(1 - lam_j)
    loss(row) = mean over the S-1 predicted positions i of
                sum_t p_t(i) CE(z_t(i), ids[i+1])  -  exit_entropy_weight * H(p(i))

``H`` is the entropy of a position's ``T``-way exit distribution. Gradients
flow through the ``p_t`` into the gate and the trunk; every block's weights get
the sum of the ``T`` passes' gradients.

What a step keeps and what it makes again (``remat=True``, the default): each
(pass, layer) application is a ``jax.checkpoint`` that keeps its input alone
(``[rows, S, hidden]`` in the compute type: ``T x L`` of them a step) and runs
the block's forward again in the backward pass, the attention kernel with it.
The ``T`` heads and cross-entropies go ``head_block`` positions of a row at a
time through :func:`~sparkflow_tpu.models.lm_ops.weighted_nll`, the function
the MoE families' heads go through, with ``p_t`` as the weight: it is an
argument of the checkpointed stretch, so its gradient, each position's
cross-entropy, reaches the gate. The passes are a ``lax.scan`` with the
weights closed over: the program holds one pass's ``L`` blocks, and the
backward pass adds every pass's gradients of the shared weights into one set
of sums.

The decode plane does not run this model (:attr:`decode_unsupported`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.attention import flash_attention
from .base import RegistryModel
from .lm_ops import dense, head_logits, heads, rms_norm, weighted_nll
from .registry import register_model


@register_model("looped_lm")
class LoopedLM(RegistryModel):
    """See the module's text. Fed ``input_ids [rows, S]`` with ``S <=
    max_len``. ``logits`` / ``pred`` are the LAST pass's; ``loop_logits
    [rows, passes, S, vocab]`` every pass's."""

    TENSORS = ("input_ids", "logits", "pred", "loop_logits")
    decode_unsupported = (
        "looped_lm trains only: a generated token runs the stack up to "
        "`passes` times, so the decode plane would need a cache row per "
        "(pass, layer) and a step whose depth the exit gate decides per "
        "token")

    def __init__(self, vocab_size: int, hidden: int = 2048,
                 num_layers: int = 6, num_heads: int = 16,
                 head_dim: int = 128, mlp_dim: int = 5632, passes: int = 4,
                 rope_theta: float = 1e6, rms_eps: float = 1e-6,
                 exit_entropy_weight: float = 0.1, max_len: int = 4096,
                 head_block: int = 2048, remat: bool = True,
                 compute_dtype=None):
        if passes < 1:
            raise ValueError(f"passes={passes}: the stack runs at least once")
        self.vocab_size, self.hidden = vocab_size, hidden
        self.num_layers, self.num_heads = num_layers, num_heads
        self.head_dim, self.mlp_dim, self.passes = head_dim, mlp_dim, passes
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.exit_entropy_weight = float(exit_entropy_weight)
        self.max_len, self.head_block, self.remat = max_len, head_block, remat
        super().__init__(compute_dtype)

    # -- specs ---------------------------------------------------------------

    def input_specs(self):
        return {"input_ids": ((None, self.max_len), "int32")}

    def param_specs(self):
        h, hd, m = self.hidden, self.num_heads * self.head_dim, self.mlp_dim
        n = "normal(0.02)"
        block = {
            "ln1_scale": ((h,), "ones"),
            "q_kernel": ((h, hd), n), "k_kernel": ((h, hd), n),
            "v_kernel": ((h, hd), n), "o_kernel": ((hd, h), n),
            "ln1_post_scale": ((h,), "ones"),
            "ln2_scale": ((h,), "ones"),
            "gate_kernel": ((h, m), n), "up_kernel": ((h, m), n),
            "down_kernel": ((m, h), n),
            "ln2_post_scale": ((h,), "ones"),
        }
        specs = {"embed": {"tok": ((self.vocab_size, h), n)}}
        for i in range(self.num_layers):
            specs[f"block_{i}"] = dict(block)
        specs["final_ln"] = {"scale": ((h,), "ones")}
        specs["lm_head"] = {"kernel": ((h, self.vocab_size), n)}
        specs["exit_gate"] = {"kernel": ((h, 1), n), "bias": ((1,), "zeros")}
        return specs

    # -- the stack -------------------------------------------------------------

    def _block(self, bp, x):
        """One layer on ``x [B, S, h]``: sandwich-norm attention, then a
        sandwich-norm SiLU-gated MLP. ``q`` and ``k`` reach the kernel's
        layout through ``lm_ops.heads`` (rotary positions alone: no per-head
        norm); the four hidden-width norms are ``lm_ops.rms_norm``."""
        b, s, _ = x.shape
        eps = self.rms_eps
        # ``attention`` groups the half's two parts: what is dense around
        # the kernel (``attn_proj``, the other families' name) and its call
        with jax.named_scope("attention"):
            with jax.named_scope("attn_proj"):
                y = rms_norm(x, bp["ln1_scale"], eps)
                q = heads(dense(y, bp["q_kernel"]), self.num_heads, None,
                          eps, self.rope_theta)
                k = heads(dense(y, bp["k_kernel"]), self.num_heads, None,
                          eps, self.rope_theta)
                v = dense(y, bp["v_kernel"]).reshape(b, s, self.num_heads, -1)
                v = jnp.transpose(v, (0, 2, 1, 3))
            with jax.named_scope("flash_attention"):
                att = flash_attention(q, k, v, causal=True)
            with jax.named_scope("attn_proj"):
                att = jnp.transpose(att, (0, 2, 1, 3)).reshape(b, s, -1)
                x = x + rms_norm(dense(att, bp["o_kernel"]),
                                 bp["ln1_post_scale"], eps)
        with jax.named_scope("mlp"):
            y = rms_norm(x, bp["ln2_scale"], eps)
            m = dense(jax.nn.silu(dense(y, bp["gate_kernel"]))
                      * dense(y, bp["up_kernel"]), bp["down_kernel"])
            return x + rms_norm(m, bp["ln2_post_scale"], eps)

    def _embed(self, params, ids):
        with jax.named_scope("embed"):
            return self.cast(jnp.take(params["embed"]["tok"], ids, axis=0))

    def _pass(self, params, x):
        """The ``L`` blocks once, before the final norm."""
        block = jax.checkpoint(self._block) if self.remat else self._block
        with jax.named_scope("loop_pass"):
            for i in range(self.num_layers):
                x = block(params[f"block_{i}"], x)
        return x

    def _final_norm(self, params, x):
        return rms_norm(x, params["final_ln"]["scale"], self.rms_eps)

    def _gate_logit(self, params, h):
        """The exit gate before its sigmoid, float32 ``[B, S]``."""
        with jax.named_scope("exit_gate"):
            g = params["exit_gate"]
            return (jnp.matmul(h.astype(jnp.float32), g["kernel"],
                               precision=jax.lax.Precision.HIGHEST)[..., 0]
                    + g["bias"][0])

    # -- forward and loss ------------------------------------------------------

    def _forward(self, params, feeds, train, rng):
        ids = feeds["input_ids"].astype(jnp.int32)

        def one_pass(x, _):
            x = self._pass(params, x)
            with jax.named_scope("loop_head"):
                x = self._final_norm(params, x)
                return x, head_logits(x, params["lm_head"]["kernel"])

        _, logits = jax.lax.scan(one_pass, self._embed(params, ids), None,
                                 length=self.passes)         # [T, B, S, V]
        return {"logits": logits[-1],
                "pred": jnp.argmax(logits[-1], axis=-1).astype(jnp.float32),
                "loop_logits": jnp.moveaxis(logits, 0, 1)}

    def _loss(self, params, feeds, train, rng):
        return self.loss_and_metrics(params, feeds, train, rng)[0]

    def loss_and_metrics(self, params, feeds, train=True, rng=None):
        """Each row's loss ``[B]`` and the step's counters, all means over
        the step's predicted positions: ``exit_mass [T]`` (the exit
        distribution), ``loop_loss [T]`` (every pass's cross-entropy),
        ``exit_entropy`` (the exit distribution's entropy)."""
        feeds = {k.split(":")[0]: v for k, v in feeds.items()}
        with jax.named_scope("batch"):
            ids = feeds["input_ids"].astype(jnp.int32)
        rows, s = ids.shape
        with jax.named_scope("loop_head"):
            # position S - 1 predicts nothing: its target is a filler of
            # weight 0
            tgt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
            live = jnp.broadcast_to(
                (jnp.arange(s) < s - 1).astype(jnp.float32), (rows, s))
        head = lambda hs: head_logits(hs, params["lm_head"]["kernel"])

        def one_pass(carry, t):
            x, stay = carry              # stay: log prod_{j<t}(1 - lam_j)
            x = self._pass(params, x)
            with jax.named_scope("loop_head"):
                x = self._final_norm(params, x)
                z = self._gate_logit(params, x)
                # the last pass takes what is left: its gate enters nothing
                log_p = stay + jnp.where(t == self.passes - 1, 0.0,
                                         jax.nn.log_sigmoid(z))
                weight = jnp.stack([jnp.exp(log_p) * live, live], axis=-1)
                # [B, 2]: sum_i p_t(i) CE_t(i) and sum_i CE_t(i)
                sums = weighted_nll(head, x, tgt, weight, self.head_block)
                return (x, stay + jax.nn.log_sigmoid(-z)), (log_p, sums)

        stay = jnp.zeros((rows, s), jnp.float32)
        _, (log_p, sums) = jax.lax.scan(
            one_pass, (self._embed(params, ids), stay),
            jnp.arange(self.passes))                          # [T, B, ...]
        with jax.named_scope("loop_head"):
            p = jnp.exp(log_p) * live
            entropy = -jnp.sum(p * log_p, axis=0)             # [B, S]
            loss = (jnp.sum(sums[..., 0], axis=0)
                    - self.exit_entropy_weight * jnp.sum(entropy, axis=-1)
                    ) / (s - 1)
        n = rows * (s - 1)
        with jax.named_scope("batch"):          # the counters over the rows
            return loss, dict(
                exit_mass=jnp.sum(p, axis=(1, 2)) / n,
                loop_loss=jnp.sum(sums[..., 1], axis=1) / n,
                exit_entropy=jnp.sum(entropy) / n)
