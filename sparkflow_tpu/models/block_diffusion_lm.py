"""A masked-diffusion LM trained block by block: the decoder of
``sparse_moe_lm.py`` (RMSNorm, rotary, grouped query heads with a per-head
q/k norm, dropless SiLU-gated experts of which a share may be held, an untied
head; :class:`~sparkflow_tpu.models.sparse_moe_lm.MoEDecoder` holds all of
that, written once) under another attention mask and another loss.

A training row is ``[c ; n]`` of ``2 L`` ids: ``c`` the ``L`` clean tokens,
``n`` their noised copy, ``n_i`` either ``c_i`` or ``mask_token_id``
(:func:`noise_rows` makes such rows from clean ones). With ``block_length``
``B``, an index ``p`` of the row is at position ``p mod L``, in block ``(p mod
L) // B``, *clean* below ``L`` and *noised* from there. What differs from
``sparse_moe_lm``:

1. *Which keys a query sees*: a rule of the two indices
   (:func:`~sparkflow_tpu.ops.block_attention.block_attention`): a clean
   query the clean keys of its own and earlier blocks, a noised query the
   clean keys of earlier blocks and the noised keys of its own. Nothing is
   learned about it and nothing causal is left inside a block.
2. *Where an index is*: both copies of a token share its rotary position.
3. *The row's loss*: the head runs on the noised half only; a block's loss is
   the mean, over its masked positions ``i``, of the cross-entropy of the
   logits at index ``L + i`` against ``c_i`` (the same position: no shift),
   over the vocabulary held; the row's loss is the mean over its ``L / B``
   blocks, plus ``router_aux_weight`` times each layer's balance loss over
   all experts and all ``2 L`` positions. A block's mean over ``k`` masked of
   ``B`` is the ``1 / t`` weight of absorbing-state diffusion under a linear
   schedule at ``t = k / B``: the model has no time input.
4. *The embedding* holds the vocabulary slice and one more row, the mask
   token's: an id outside every slice, an input on every chip, never a target.

A block's checkpoint keeps the attention's output and logsumexp by name, so
``block_attn_fwd`` runs once a (row, layer): 2 x 2L x Hq x D bytes and 4 x 2L
x Hq, 68.2 MB at ``L`` 4096, 32 heads of 128, and as with ``sparse_moe_lm``
every row of a step keeps its own until its backward pass.

The decode plane does not run this model: a generation step denoises a block
(several tokens a step, a cache written per block), which no engine there
does (ROADMAP).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import block_attention as ba
from .registry import register_model
from .sparse_moe_lm import MoEDecoder


def noise_rows(ids, block_length: int, mask_token_id: int, seed: int):
    """Clean rows ``ids [rows, L]`` -> training rows ``[rows, 2 L]`` (the
    clean copy, then the noised copy), for an input pipeline. Each block of
    ``block_length`` positions masks ``k`` of them, ``k`` uniform on ``1 ..
    block_length`` and the positions uniform among the ``C(block_length, k)``
    choices; a masked position holds ``mask_token_id``. The same ``seed``
    gives the same noise; numpy, on the host."""
    ids = np.asarray(ids)
    rows, length = ids.shape
    if length % block_length:
        raise ValueError(f"blocks of {block_length} do not divide a row of "
                         f"{length}")
    rng = np.random.default_rng(seed)
    shape = (rows, length // block_length, block_length)
    k = rng.integers(1, block_length + 1, shape[:2])
    # the k positions of smallest rank among a block's uniform draws
    rank = np.argsort(np.argsort(rng.random(shape), axis=-1), axis=-1)
    masked = (rank < k[..., None]).reshape(rows, length)
    noised = np.where(masked, mask_token_id, ids).astype(ids.dtype)
    return np.concatenate([ids, noised], axis=1)


@register_model("block_diffusion_lm")
class BlockDiffusionLM(MoEDecoder):
    """See the module's text. Fed ``input_ids [rows, 2 L]``; ``max_len`` is
    ``2 L``. ``mask_token_id`` is an id of ``vocab_size`` outside
    ``vocab_held`` (so a model that holds the whole vocabulary takes
    ``vocab_size`` itself, one past the last id). ``experts_held`` and
    ``vocab_held`` as in ``sparse_moe_lm``."""

    KEPT = staticmethod(jax.checkpoint_policies.save_only_these_names(
        ba.ATTN_OUT, ba.ATTN_LSE))
    decode_unsupported = (
        "block_diffusion_lm trains only: a generation step denoises a whole "
        "block under the block mask, and the decode plane has no step of "
        "several tokens nor a cache written per block")

    def __init__(self, vocab_size: int, mask_token_id: int,
                 block_length: int = 4, hidden: int = 2048,
                 num_layers: int = 4, num_heads: int = 32,
                 num_kv_heads: int = 4, head_dim: int = 128,
                 num_experts: int = 128, experts_per_token: int = 8,
                 expert_dim: int = 768,
                 experts_held: Optional[Sequence[int]] = None,
                 vocab_held: Optional[Sequence[int]] = None,
                 rope_theta: float = 1e6, rms_eps: float = 1e-6,
                 norm_topk_prob: bool = True,
                 router_aux_weight: float = 0.001, max_len: int = 8192,
                 remat: bool = True, compute_dtype=None):
        # the head's stretch and "no dropout" are the family's, not options
        super().__init__(vocab_size, hidden, num_layers, num_heads,
                         num_kv_heads, head_dim, num_experts,
                         experts_per_token, expert_dim, experts_held,
                         vocab_held, rope_theta, rms_eps, norm_topk_prob,
                         router_aux_weight, max_len, 2048, 0.0, remat,
                         compute_dtype)
        if (max_len % 2 or (max_len // 2) % block_length
                or block_length & (block_length - 1)):
            raise ValueError(f"max_len={max_len} is not twice a whole number "
                             f"of blocks of {block_length}, a power of two")
        lo, hi = self.vocab_held
        if lo <= mask_token_id < hi or not 0 <= mask_token_id <= vocab_size:
            raise ValueError(f"mask_token_id={mask_token_id} has to lie in "
                             f"the vocabulary and outside vocab_held={lo, hi}")
        self.mask_token_id, self.block_length = mask_token_id, block_length

    @property
    def embed_rows(self) -> int:
        return self.vocab_here + 1

    def _embed_index(self, ids):
        return jnp.where(ids == self.mask_token_id, self.vocab_here,
                         ids - self.vocab_held[0])

    def _positions(self, s: int):
        return ba.positions(s // 2)

    def _attend(self, bp, y):
        q, k, v = self._qkv(bp, y)
        with jax.named_scope("block_attention"):
            att, _ = ba.block_attention(q, k, v, y.shape[1] // 2,
                                        self.block_length)
        return att, {}

    # -- forward and loss ----------------------------------------------------

    def _forward(self, params, feeds, train, rng):
        """``logits [rows, L, vocab held]``: the noised half's, index ``L +
        i`` predicting token ``i``."""
        ids = feeds["input_ids"].astype(jnp.int32)
        x = self._encode(params, ids)[0]
        with jax.named_scope("lm_head"):
            logits = self._head(params, x[:, ids.shape[1] // 2:])
            return {"logits": logits,
                    "pred": (jnp.argmax(logits, axis=-1)
                             + self.vocab_held[0]).astype(jnp.float32)}

    def _row_loss(self, params, x, ids):
        """The masked-token loss of one row and how many positions carry it:
        ``x [2 L, h]`` (before the final norm), ``ids [2 L]``."""
        length, b = ids.shape[0] // 2, self.block_length
        masked = (ids[length:] == self.mask_token_id).astype(jnp.float32)
        per_block = jnp.sum(masked.reshape(-1, b), axis=-1, keepdims=True)
        weight = (masked.reshape(-1, b) / jnp.maximum(per_block, 1.0)
                  ).reshape(length) * (b / length)
        nll = self._weighted_nll(params, x[length:],
                                 ids[:length] - self.vocab_held[0], weight)
        return nll, jnp.sum(masked).astype(jnp.int32)

    def loss_and_metrics(self, params, feeds, train=True, rng=None):
        """Each row's loss ``[rows]`` and the step's counters: per layer the
        pairs each held expert got (``expert_load [layers, held]``), the
        (position, expert) pairs the step routed in all (``pairs_routed``,
        over all ``2 L`` positions of every row), the rows of the experts'
        buffers in use and in all (``expert_rows_live [layers]``,
        ``expert_rows_bound``) and the positions that carry loss
        (``masked_tokens``). Rows go through the model one after
        another, as in ``sparse_moe_lm``."""
        feeds = {k.split(":")[0]: v for k, v in feeds.items()}
        with jax.named_scope("batch"):
            ids = feeds["input_ids"].astype(jnp.int32)

        def row(r):
            x, aux = self._encode(params, r[None])
            with jax.named_scope("lm_head"):    # the row's loss, whole
                nll, masked = self._row_loss(params, x[0], r)
                loss = nll + self.router_aux_weight * jnp.sum(aux["balance"])
            return loss, (aux["expert_load"], aux["expert_rows_live"],
                          masked)

        loss, (load, live, masked) = jax.lax.map(row, ids)
        with jax.named_scope("batch"):          # the counters over the rows
            return loss, dict(expert_load=jnp.sum(load, axis=0),
                              masked_tokens=jnp.sum(masked),
                              **self._expert_counts(ids, live))
