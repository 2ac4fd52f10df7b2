"""A causal LM whose block is not GPT-2's: RMSNorm, rotary positions, grouped
query heads with a per-head q/k norm, a learned selection of keys (an
indexer that scores every (query, key) pair and keeps each query's ``topk``)
and SiLU-gated experts that drop no token, of which this model may hold a
share. No bias anywhere, an untied head. The block is written once
(:class:`MoEDecoder`: everything of it but which keys a query sees, which
``block_diffusion_lm.py`` answers by a rule and this file by an indexer).

A layer, for a row of tokens (pre-norm, residual):

1. ``h = RMSNorm(x)``; ``q, k, v`` projections (``num_heads`` query heads
   over ``num_kv_heads`` key/value heads of ``head_dim``), RMSNorm over each
   head of ``q`` and ``k``, rotary positions over the whole head.
2. The indexer reads ``stop_gradient(h)``: ``qI`` (``indexer_heads`` of
   ``indexer_dim``), one key head ``kI``, weights ``w``; the scores and the
   selection are :func:`~sparkflow_tpu.ops.sparse_attention.index_select`'s.
3. Attention over the selected keys
   (:func:`~sparkflow_tpu.ops.sparse_attention.selected_attention`).
4. ``h' = RMSNorm(x)``; the router over all ``num_experts`` in float32, the
   token's top ``experts_per_token``; the experts ``experts_held`` add their
   part (:func:`~sparkflow_tpu.ops.grouped_matmul.dropless_experts`). What
   the experts not held would add is left out: under expert parallelism it
   is the other chips' part of the sum.

The loss of a row is its next-token cross-entropy over the vocabulary held
(``vocab_held``: the ids, embedding rows and head columns of one slice), plus
``indexer_loss_weight`` times each layer's indexer loss (the KL from the
attention's head-summed probabilities to the indexer's softmax over the
selection; it moves the indexer's three matrices and nothing else, and the
cross-entropy never moves them), plus ``router_aux_weight`` times each
layer's balance loss over all experts.

What a block's backward pass keeps and what it makes again (``remat=True``,
the default). Each block is a ``jax.checkpoint``: its backward pass runs the
block's forward again (projections, norms, rotary, router, the experts'
forward: their residuals are the large ones) but for the values the
checkpoint keeps by name (:data:`KEPT`): the selection, as bits; the
attention's output and logsumexp; the two row statistics of the indexer
loss's kernel. With them ``index_select``, ``sparse_attn_fwd`` and
``index_kl_fwd`` run once a (row, layer) and not twice; each value is the
very one the backward kernels read, so nothing in a result changes. The
indexer loss's target (``sparse_attn_probs``) is made again and is meant to
be: it is float32 ``[S, S]``, 268 MB a layer and row at 8192 tokens, against
2.25 ms to make it. The rows of a step go through the model one after
another, but every row's kept values live until that row's backward pass, so
a step holds ``rows x layers`` such sets. ``remat=False`` keeps everything.

The decode plane does not run this model: its cache would have to hold the
indexer's keys and its kernels select pages per query (ROADMAP).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import grouped_matmul as gm
from ..ops import sparse_attention as sa
from .base import RegistryModel, _Names
from .lm_ops import dense as _dense
from .lm_ops import head_logits, heads, rms_norm, rope, weighted_nll
from .registry import register_model


# What a block's checkpoint keeps by name for its backward pass (the module's
# text says why); ``ops/sparse_attention.py`` puts the names on the values.
# Bytes for a layer and a row of S tokens, Hq query heads of D:
# ``S^2 / 8`` (the selection as bits) ``+ 2 S Hq D`` (the output, bf16)
# ``+ 4 S Hq + 8 S`` (the logsumexp and the KL kernel's lse and mass, f32):
# 8.4 + 67.1 + 1.05 + 0.07 = 76.6 MB at S 8192, Hq 32, D 128, and 0.61 GB for
# the 2 rows x 4 layers a step of that size holds (the compiler counts 1.5 GB
# more scratch: XLA copies each value into and out of the rows' buffers).
# The selection as int8 would be ``S^2`` (67.1 MB, and 1.07 GB at S 32 768)
# to spare the cheapest of the three kernels: hence the bits.
# Not kept: ``selected_probs``' target, float32 ``[S, S]`` (268 MB a layer and
# row for 2.25 ms of ``sparse_attn_probs``; kept in a narrower type it would
# be another input to ``index_kl_bwd``, another result), and all XLA makes.
KEPT = jax.checkpoint_policies.save_only_these_names(
    sa.SELECTION, sa.ATTN_OUT, sa.ATTN_LSE, sa.KL_LSE, sa.KL_MASS)


class MoEDecoder(RegistryModel):
    """What the families of this file and of ``block_diffusion_lm.py``
    share, written once: the block's norms, projections, per-head q/k norm
    and rotary positions (:meth:`_qkv`; ``lm_ops.heads`` makes ``q`` and
    ``k``, ``lm_ops.rms_norm`` the hidden-width norms), its router and experts
    (:meth:`_experts`), the residual wiring (:meth:`_block`), the stack of
    checkpointed blocks (:meth:`_encode`), the head and a row's weighted
    cross-entropy a stretch at a time (:meth:`_weighted_nll`; both through
    ``lm_ops.py``, which ``looped_lm.py`` calls too). A family says
    which keys a query sees (:meth:`_attend`), where an index of the row is
    (:meth:`_positions`), what a block's checkpoint keeps (``KEPT``), how an
    id finds its embedding row (:meth:`_embed_index`) and what a row's loss
    is."""

    TENSORS = ("input_ids", "logits", "pred")
    KEPT = None                 # a ``jax.checkpoint`` policy; None keeps nothing

    def __init__(self, vocab_size: int, hidden: int, num_layers: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 num_experts: int, experts_per_token: int, expert_dim: int,
                 experts_held: Optional[Sequence[int]],
                 vocab_held: Optional[Sequence[int]], rope_theta: float,
                 rms_eps: float, norm_topk_prob: bool,
                 router_aux_weight: float, max_len: int, head_block: int,
                 dropout: float, remat: bool, compute_dtype):
        if dropout:
            raise ValueError(f"{self.model_name} has no dropout")
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not divide over "
                             f"{num_kv_heads} key/value heads")
        self.vocab_size, self.hidden = vocab_size, hidden
        self.num_layers, self.num_heads = num_layers, num_heads
        self.num_kv_heads, self.head_dim = num_kv_heads, head_dim
        self.num_experts, self.experts_per_token = num_experts, experts_per_token
        self.expert_dim = expert_dim
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.vocab_held = tuple(vocab_held or (0, vocab_size))
        for name, (lo, hi), whole in (
                ("experts_held", self.experts_held, num_experts),
                ("vocab_held", self.vocab_held, vocab_size)):
            if not 0 <= lo < hi <= whole:
                raise ValueError(f"{name}={lo, hi} is no range of {whole}")
        self.rope_theta, self.rms_eps = float(rope_theta), float(rms_eps)
        self.norm_topk_prob = norm_topk_prob
        self.router_aux_weight = router_aux_weight
        self.max_len, self.remat = max_len, remat
        self.head_block = head_block
        super().__init__(compute_dtype)
        self.graphdef = _Names(self.TENSORS)

    # -- specs ---------------------------------------------------------------

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def vocab_here(self) -> int:
        return self.vocab_held[1] - self.vocab_held[0]

    embed_rows = vocab_here      # a family may hold rows beyond the slice

    def input_specs(self):
        return {"input_ids": ((None, self.max_len), "int32")}

    def _attention_specs(self):
        """A family's own matrices of the attention layer, after ``W_o``."""
        return {}

    def _block_specs(self):
        h, d, m = self.hidden, self.head_dim, self.expert_dim
        n = "normal(0.02)"
        return {
            "ln1_scale": ((h,), "ones"),
            "q_kernel": ((h, self.num_heads * d), n),
            "k_kernel": ((h, self.num_kv_heads * d), n),
            "v_kernel": ((h, self.num_kv_heads * d), n),
            "q_norm": ((d,), "ones"), "k_norm": ((d,), "ones"),
            "o_kernel": ((self.num_heads * d, h), n),
            **self._attention_specs(),
            "ln2_scale": ((h,), "ones"),
            "router": ((h, self.num_experts), n),
            "experts_w1": ((self.held, h, m), n),
            "experts_w3": ((self.held, h, m), n),
            "experts_w2": ((self.held, m, h), n),
        }

    def param_specs(self):
        h = self.hidden
        specs = {"embed": {"tok": ((self.embed_rows, h), "normal(0.02)")}}
        for i in range(self.num_layers):
            specs[f"block_{i}"] = self._block_specs()
        specs["final_ln"] = {"scale": ((h,), "ones")}
        specs["lm_head"] = {"kernel": ((h, self.vocab_here), "normal(0.02)")}
        return specs

    # -- the block, once -----------------------------------------------------

    def _positions(self, s: int):
        """The position of each of a row's ``s`` indices; ``None``: its
        index."""
        return None

    def _qkv(self, bp, y):
        """``y = RMSNorm(x) [B, S, h]`` -> ``q [B, Hq, S, D]``, ``k, v [B,
        Hkv, S, D]``: the projections, RMSNorm over each head of ``q`` and
        ``k``, rotary positions over the whole head. ``q`` and ``k`` go from
        the product to the kernels' layout through
        :func:`~sparkflow_tpu.models.lm_ops.heads`, one pass each; ``v`` has
        neither norm nor rotation and is transposed."""
        b, s, _ = y.shape
        with jax.named_scope("attn_proj"):
            pos = self._positions(s)
            q = heads(_dense(y, bp["q_kernel"]), self.num_heads,
                      bp["q_norm"], self.rms_eps, self.rope_theta, pos)
            k = heads(_dense(y, bp["k_kernel"]), self.num_kv_heads,
                      bp["k_norm"], self.rms_eps, self.rope_theta, pos)
            v = _dense(y, bp["v_kernel"]).reshape(b, s, self.num_kv_heads, -1)
            return q, k, jnp.transpose(v, (0, 2, 1, 3))

    def _attend(self, bp, y):
        """The attention on ``y = RMSNorm(x) [B, S, h]``: its output by head
        ``[B, Hq, S, D]`` and the family's own entries of the block's
        ``aux``. The family's kernels go under parts of its own
        (``utils.tracing.STEP_PARTS``); :meth:`_qkv` brings ``attn_proj``."""
        raise NotImplementedError

    def _experts(self, bp, y):
        """The expert layer on ``y = RMSNorm(x) [B, S, h]``: the held
        experts' part of the layer's output, each row's balance loss ``[B]``
        and each held expert's load ``[held]``."""
        b, s, h = y.shape
        with jax.named_scope("router"):
            logits = jnp.matmul(y.reshape(b * s, h).astype(jnp.float32),
                                bp["router"],
                                precision=jax.lax.Precision.HIGHEST)
            probs, gates, experts = gm.route_top_k(
                logits, self.experts_per_token, self.norm_topk_prob)
            balance = jax.vmap(gm.balance_loss)(
                probs.reshape(b, s, -1), experts.reshape(b, s, -1))
        with jax.named_scope("experts"):
            out, load = gm.dropless_experts(
                y.reshape(b * s, h), gates, experts, bp["experts_w1"],
                bp["experts_w3"], bp["experts_w2"], self.experts_held[0])
        return out.reshape(b, s, h), balance, load

    def _block(self, bp, x):
        """One layer on ``x [B, S, h]`` -> ``(x, aux)``; ``aux`` holds each
        row's balance loss, the layer's expert load, the rows of its experts'
        buffer that are in use and what the family's attention adds."""
        b, s, _ = x.shape
        with jax.named_scope("attn_proj"):
            y = rms_norm(x, bp["ln1_scale"], self.rms_eps)
        att, aux = self._attend(bp, y)
        with jax.named_scope("attn_proj"):
            att = jnp.transpose(att, (0, 2, 1, 3)).reshape(b, s, -1)
            x = x + _dense(att, bp["o_kernel"])
        with jax.named_scope("router"):
            y = rms_norm(x, bp["ln2_scale"], self.rms_eps)
        out, balance, load = self._experts(bp, y)
        with jax.named_scope("router"):
            return x + out, dict(aux, balance=balance, expert_load=load,
                                 expert_rows_live=gm.rows_live(load))

    def _expert_counts(self, ids, live):
        """The counters every family returns of a step's rows ``ids [rows,
        S]``: the pairs routed in all, and of the experts' buffers the rows
        in use by layer (``live [rows, L]`` summed) and the rows in all."""
        rows, s = ids.shape
        k, held = self.experts_per_token, self.held
        return dict(
            pairs_routed=jnp.full((), rows * s * k, jnp.int32),
            expert_rows_live=jnp.sum(live, axis=0),
            expert_rows_bound=jnp.full(
                (), rows * gm.rows_bound(s, k, held), jnp.int32))

    def _head(self, params, x):
        """Final norm and the head over the vocabulary held: float32
        logits. The callers name the part (``lm_head``), which holds what
        they make of the logits too."""
        return head_logits(
            rms_norm(x, params["final_ln"]["scale"], self.rms_eps),
            params["lm_head"]["kernel"])

    def _embed_index(self, ids):
        """The embedding row of each id."""
        return ids - self.vocab_held[0]

    def _encode(self, params, ids):
        with jax.named_scope("embed"):
            x = self.cast(jnp.take(params["embed"]["tok"],
                                   self._embed_index(ids), axis=0))
        block = (jax.checkpoint(self._block, policy=self.KEPT) if self.remat
                 else self._block)
        aux = []
        for i in range(self.num_layers):
            x, a = block(params[f"block_{i}"], x)
            aux.append(a)
        with jax.named_scope("router"):     # the layers' counters, stacked
            return x, jax.tree.map(lambda *a: jnp.stack(a), *aux)

    def _weighted_nll(self, params, x, tgt, weight):
        """``sum_i weight[i] * cross-entropy(head(x[i]), tgt[i])`` of one
        row: ``x [S, h]`` (before the final norm), ``tgt [S]`` columns of the
        head; ``head_block`` positions at a time
        (:func:`~sparkflow_tpu.models.lm_ops.weighted_nll`)."""
        return weighted_nll(lambda xs: self._head(params, xs), x, tgt, weight,
                            self.head_block)

    def _loss(self, params, feeds, train, rng):
        return self.loss_and_metrics(params, feeds, train, rng)[0]


@register_model("sparse_moe_lm")
class SparseMoELM(MoEDecoder):
    """See the module's text. ``experts_held = (first, stop)`` and
    ``vocab_held = (first, stop)`` give the share this model holds; the
    default is everything."""

    KEPT = staticmethod(KEPT)    # a function: not to be bound as a method
    decode_unsupported = (
        "sparse_moe_lm trains only: the decode plane has no cache for the "
        "indexer's keys and no per-query selection in its paged kernels")

    def __init__(self, vocab_size: int, hidden: int = 2048,
                 num_layers: int = 4, num_heads: int = 32,
                 num_kv_heads: int = 4, head_dim: int = 128,
                 num_experts: int = 128, experts_per_token: int = 8,
                 expert_dim: int = 768,
                 experts_held: Optional[Sequence[int]] = None,
                 vocab_held: Optional[Sequence[int]] = None,
                 indexer_heads: int = 16, indexer_dim: int = 64,
                 indexer_topk: int = 2048, indexer_block: int = 256,
                 rope_theta: float = 1e7, rms_eps: float = 1e-6,
                 norm_topk_prob: bool = True,
                 router_aux_weight: float = 0.001,
                 indexer_loss_weight: float = 1.0, max_len: int = 8192,
                 head_block: int = 2048,
                 dropout: float = 0.0, remat: bool = True,
                 compute_dtype=None):
        self.indexer_heads, self.indexer_dim = indexer_heads, indexer_dim
        self.indexer_topk, self.indexer_block = indexer_topk, indexer_block
        self.indexer_loss_weight = indexer_loss_weight
        super().__init__(vocab_size, hidden, num_layers, num_heads,
                         num_kv_heads, head_dim, num_experts,
                         experts_per_token, expert_dim, experts_held,
                         vocab_held, rope_theta, rms_eps, norm_topk_prob,
                         router_aux_weight, max_len, head_block, dropout,
                         remat, compute_dtype)

    def _attention_specs(self):
        h, n = self.hidden, "normal(0.02)"
        return {
            "idx_q_kernel": ((h, self.indexer_heads * self.indexer_dim), n),
            "idx_k_kernel": ((h, self.indexer_dim), n),
            "idx_w_kernel": ((h, self.indexer_heads), n),
        }

    def _attend(self, bp, y):
        """Steps 1-3 on ``y = RMSNorm(x) [B, S, h]``: the attention's output
        by head, and in ``aux`` each row's indexer loss ``[B]`` and the keys
        a query selected (mean)."""
        b, s, _ = y.shape
        q, k, v = self._qkv(bp, y)

        with jax.named_scope("indexer"):
            ys = jax.lax.stop_gradient(y)
            # heads of 64 in the index kernels' own layout: ``rope``, not
            # ``heads``
            qi = rope(_dense(ys, bp["idx_q_kernel"]).reshape(
                b, s, self.indexer_heads, -1), self.rope_theta)
            ki = rope(_dense(ys, bp["idx_k_kernel"]), self.rope_theta)
            w = _dense(ys, bp["idx_w_kernel"])
            mask = sa.index_select(qi, ki, w, self.indexer_topk,
                                   self.indexer_block)
            if self.remat:      # kept as bits: everyone reads the bits' copy
                mask = sa.unpack_selection(checkpoint_name(
                    sa.pack_selection(mask), sa.SELECTION))
        with jax.named_scope("sparse_attention"):
            att, lse = sa.selected_attention(q, k, v, mask)
        with jax.named_scope("indexer"):
            target = sa.selected_probs(q, k, lse, mask)
            kl = sa.indexer_loss(qi, ki, w, mask, target, self.indexer_block)
            picked = jnp.mean(jnp.sum(mask.astype(jnp.float32), axis=-1))
        return att, dict(indexer=kl, selected_keys=picked)

    # -- forward and loss ----------------------------------------------------

    def _forward(self, params, feeds, train, rng):
        ids = feeds["input_ids"].astype(jnp.int32)
        x = self._encode(params, ids)[0]
        with jax.named_scope("lm_head"):
            logits = self._head(params, x)
            return {"logits": logits,
                    "pred": (jnp.argmax(logits, axis=-1)
                             + self.vocab_held[0]).astype(jnp.float32)}

    def _row_nll(self, params, x, ids):
        """Mean next-token cross-entropy of one row: ``x [S, h]`` (before the
        final norm), ``ids [S]``."""
        s = ids.shape[0]
        tgt = jnp.concatenate([ids[1:], ids[:1]]) - self.vocab_held[0]
        live = (jnp.arange(s) < s - 1).astype(jnp.float32)
        return self._weighted_nll(params, x, tgt, live) / (s - 1)

    def loss_and_metrics(self, params, feeds, train=True, rng=None):
        """Each row's loss ``[B]`` and the step's counters: per layer the
        pairs each held expert got (``expert_load [L, held]``) and the keys
        a query selected (``selected_keys [L]``), the rows of the experts'
        buffers the layer's kernels and row copies visited (``expert_rows_live
        [L]``, of ``expert_rows_bound``, the buffers' size), and the (token,
        expert) pairs the step routed in all (``pairs_routed``). Rows go through the
        model one after another: every part of the loss is a row's own, a
        row of 8k tokens fills the chip's matrix unit, and the buffers of a
        dropless layer are sized for the worst routing of the tokens they
        serve."""
        feeds = {k.split(":")[0]: v for k, v in feeds.items()}
        with jax.named_scope("batch"):
            ids = feeds["input_ids"].astype(jnp.int32)

        def row(r):
            x, aux = self._encode(params, r[None])
            with jax.named_scope("lm_head"):    # the row's loss, whole
                loss = (self._row_nll(params, x[0], r)
                        + self.indexer_loss_weight * jnp.sum(aux["indexer"])
                        + self.router_aux_weight * jnp.sum(aux["balance"]))
            return loss, (aux["expert_load"], aux["expert_rows_live"],
                          aux["selected_keys"])

        loss, (load, live, picked) = jax.lax.map(row, ids)
        with jax.named_scope("batch"):          # the counters over the rows
            return loss, dict(expert_load=jnp.sum(load, axis=0),
                              selected_keys=jnp.mean(picked, axis=0),
                              **self._expert_counts(ids, live))
