"""all_to_all expert dispatch: the communicating form of expert parallelism.

``models/moe.py``'s capacity dispatch runs under GSPMD (the expert einsum's
sharding makes XLA insert the collective). This module is the explicit
shard_map form — the GShard pipeline (Lepikhin et al.; PAPERS.md pattern):

    route locally -> all_to_all token buffers over the ``ep`` axis ->
    each device runs ONLY its local experts -> all_to_all back -> combine

Every device holds a batch shard AND ``E/n`` experts of the bank; tokens
move to their expert's device over ICI and return. With ``E == n`` (one
expert per device — the common pod configuration) there is zero redundant
FLOP anywhere. Used inside ``shard_map`` (see
``parallel/ep.make_moe_shardmap_train_step``).

Routing is top-k (k=1 gives Switch semantics, k>1 the GShard renormalized
gates), with first choices claiming buffer capacity before any second
choice — the same priority rule as the GSPMD slot dispatch, so the two
forms compute identical outputs when capacity covers every choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp



def all_to_all_moe_ffn(x, router_w, experts_fc1, experts_b1, experts_fc2,
                       experts_b2, axis_name: str, num_experts: int,
                       capacity_factor: float = 1.25, token_mask=None,
                       top_k: int = 1, return_overflow: bool = False):
    """Top-k routed expert FFN with all_to_all dispatch.

    Args (device-local views inside shard_map over ``axis_name``):
      x            [B_local, S, H] token activations (batch sharded)
      router_w     [H, E] replicated router
      experts_fc1  [E_local, H, M] — THIS device's slice of the expert bank
      experts_b1   [E_local, M]
      experts_fc2  [E_local, M, H]
      experts_b2   [E_local, H]
      token_mask   optional [B_local, S]; masked tokens claim no capacity
      top_k        experts per token (1 = Switch; >1 = GShard renormalized)
      return_overflow  also return the fraction of live routed choices this
                       device DROPPED for lack of send-buffer capacity

    Returns ``(combined [B_local, S, H], aux_loss scalar)`` — plus the
    overflow fraction when requested. The aux loss is the Switch
    load-balance term computed from GLOBALLY psummed routing statistics
    (first-choice counts, router probabilities, live-token count) over
    ``axis_name``, so it is identical on every device and bit-matches the
    single-device computation over the full batch — mean-of-per-shard-aux
    would not (mean of products != product of means), and the mismatch,
    while tiny in the loss, becomes a full ±lr parameter delta once Adam
    normalizes the gradient.
    """
    try:
        n = jax.lax.axis_size(axis_name)
    except NameError as e:
        raise NameError(
            f"mesh axis {axis_name!r} is not bound: an ep_axis MoE model "
            f"must run inside shard_map over that axis — use "
            f"parallel.ep.make_moe_shardmap_train_step (or build the model "
            f"without ep_axis for the GSPMD dispatch)") from e
    b, s, h = x.shape
    nl = b * s                      # local tokens
    e = num_experts
    k = max(1, min(top_k, e))
    e_local = experts_fc1.shape[0]
    assert e_local * n == e, (e_local, n, e)
    # per (device -> peer) buffer capacity: routed choices THIS device may
    # send to one peer. cf * nl * k / n is the balanced share across the k
    # choices; generous by design.
    cap = max(1, int(-(-capacity_factor * nl * k // n)))

    xf = x.reshape(nl, h)
    logits = jnp.einsum("th,he->te", xf.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)                 # [Nl, E]
    top_vals, top_idx = jax.lax.top_k(probs, k)             # [Nl, k]
    top_idx = top_idx.astype(jnp.int32)
    if k == 1:
        gates = top_vals  # Switch semantics: gate = max prob
    else:
        # GShard top-k: gates renormalized over the chosen experts
        gates = top_vals / jnp.maximum(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    live = (token_mask.reshape(nl).astype(jnp.float32)
            if token_mask is not None else jnp.ones((nl,), jnp.float32))

    onehot1 = jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32) * live[:, None]
    # global routing statistics: psum the per-expert first-choice counts,
    # the per-expert probability mass, and the live-token count across the
    # axis BEFORE forming the load-balance product (see docstring)
    count1_g = jax.lax.psum(jnp.sum(onehot1, axis=0), axis_name)      # [E]
    pmass_g = jax.lax.psum(jnp.sum(probs * live[:, None], axis=0),
                           axis_name)                                  # [E]
    nlive_g = jnp.maximum(jax.lax.psum(jnp.sum(live), axis_name), 1.0)
    aux = e * jnp.sum((count1_g / nlive_g) * (pmass_g / nlive_g))

    # destination peer per (choice, token), positions via cumsum over the
    # choice-major stack: ALL first choices claim send-buffer slots before
    # any second choice (GShard priority, same as the GSPMD path)
    dest = top_idx // e_local                               # [Nl, k]
    dest_oh = (jax.nn.one_hot(dest, n, dtype=jnp.float32)
               * live[:, None, None])                       # [Nl, k, n]
    stacked = jnp.transpose(dest_oh, (1, 0, 2)).reshape(k * nl, n)
    pos_all = jnp.cumsum(stacked, axis=0) - 1.0             # [k*Nl, n]

    xf_pad = jnp.concatenate([xf, jnp.zeros((1, h), xf.dtype)], axis=0)
    # token_for_slot stores the FLAT choice-token id ci*nl + t (sentinel
    # k*nl); the flat id recovers both the token row and the choice's expert
    token_for_slot = jnp.full((n * cap + 1,), k * nl, dtype=jnp.int32)
    slots, kept_live = [], []
    for ci in range(k):
        oh = stacked[ci * nl:(ci + 1) * nl]                 # [Nl, n]
        pos = jnp.sum(pos_all[ci * nl:(ci + 1) * nl] * oh,
                      axis=-1).astype(jnp.int32)            # [Nl]
        kept = (pos < cap) & (live > 0)
        slot = jnp.where(kept, dest[:, ci] * cap + pos, n * cap)
        token_for_slot = token_for_slot.at[slot].set(
            ci * nl + jnp.arange(nl, dtype=jnp.int32))
        slots.append(slot)
        kept_live.append(kept)
    tfs = token_for_slot[:n * cap]
    tok_idx = jnp.where(tfs < k * nl, tfs % nl, nl)         # pad row on empty
    send_x = xf_pad[tok_idx].reshape(n, cap, h)
    # sidecar: which LOCAL expert on the destination + validity
    le_flat = (top_idx % e_local).T.reshape(k * nl)         # choice-major
    le_pad = jnp.concatenate([le_flat, jnp.zeros((1,), jnp.int32)])
    send_le = le_pad[jnp.minimum(tfs, k * nl)].reshape(n, cap)
    send_valid = (tfs < k * nl).astype(jnp.float32).reshape(n, cap)

    # the exchange: slab j of send goes to peer j; recv slab j came from j
    recv_x = jax.lax.all_to_all(send_x, axis_name, 0, 0, tiled=False)
    recv_le = jax.lax.all_to_all(send_le, axis_name, 0, 0, tiled=False)
    recv_valid = jax.lax.all_to_all(send_valid, axis_name, 0, 0, tiled=False)

    # local expert compute over the n*cap received tokens; one-hot combine
    # over E_local only (E_local == 1 on E == n meshes: no redundancy)
    rt = recv_x.reshape(n * cap, h)
    le_oh = (jax.nn.one_hot(recv_le.reshape(-1), e_local, dtype=jnp.float32)
             * recv_valid.reshape(-1)[:, None])             # [n*cap, E_local]
    hid = jnp.einsum("th,ehm->etm", rt, experts_fc1.astype(rt.dtype))
    hid = jax.nn.gelu(hid + experts_b1.astype(hid.dtype)[:, None, :])
    out = jnp.einsum("etm,emh->eth", hid, experts_fc2.astype(hid.dtype))
    out = out + experts_b2.astype(out.dtype)[:, None, :]
    out = jnp.einsum("eth,te->th", out, le_oh.astype(out.dtype))

    # send results home and combine into original token positions; each
    # token reads its k result slots back, weighted by its gates (overflow
    # slot row is zero: dropped choices contribute nothing)
    back = jax.lax.all_to_all(out.reshape(n, cap, h), axis_name, 0, 0,
                              tiled=False)
    back_pad = jnp.concatenate([back.reshape(n * cap, h),
                                jnp.zeros((1, h), back.dtype)], axis=0)
    y = sum(back_pad[slots[ci]] * gates[:, ci:ci + 1].astype(back.dtype)
            for ci in range(k))
    y = y.reshape(b, s, h).astype(x.dtype)
    if not return_overflow:
        return y, aux
    routed = jnp.maximum(jnp.sum(live) * k, 1.0)
    kept_n = sum(jnp.sum(jnp.where(kl, live, 0.0)) for kl in kept_live)
    return y, aux, 1.0 - kept_n / routed
