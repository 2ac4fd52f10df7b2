"""Pipeline parallelism: transformer blocks sharded into stages over ``pp``.

Each device on the ``pp`` mesh axis holds 1/P of the transformer blocks
(stacked and sharded on a leading stage axis), so model memory scales down
with pipeline depth. Activations travel stage-to-stage with ``ppermute`` over
the ICI ring; microbatches bound activation memory and gradients accumulate
across them. Differentiation flows through the collective (ppermute transposes
to the reverse permute), so this is a complete train step, not a forward-only
demo.

Three schedules share the layout and numerics:

- ``'gpipe'`` (default): the overlapped fill-drain schedule. Every tick, ALL
  stages compute concurrently — stage ``s`` works on microbatch ``t - s`` —
  so a step's serial span is ``M + P - 1`` stage-times instead of the
  sequential ``M * P`` (utilization ``M/(M+P-1)``; Huang et al., GPipe).
  Invalid (fill/drain) ticks compute on placeholder activations whose chains
  never reach a live loss term, so masking them keeps gradients exact.
  Autodiff reverses the schedule tick-by-tick (ppermute transposes to the
  reverse ring), giving the overlapped backward for free; per-tick
  ``jax.checkpoint`` keeps activation memory at stage boundaries.
- ``'1f1b'``: one-forward-one-backward (PipeDream-flush / Megatron
  non-interleaved). The schedule is SIMULATED in numpy at trace time
  (P, M are static) into per-tick op tables; the compiled step is a single
  ``lax.scan`` whose tick does the table's op — a hand-scheduled backward
  via ``jax.vjp`` per microbatch with stage-input recompute, NOT autodiff
  of the whole schedule. Peak activation memory is **P microbatch inputs**
  per stage (the 1F1B bound) vs the fill-drain schedule's ``M + P - 1``
  saved boundary activations; serial span is ``~2M + 2P - 3`` combined
  fwd+bwd stage-times (GPipe's combined span is the same asymptotically —
  1F1B's win is memory, not bubble).
- ``'sequential'``: the round-1 schedule (one stage live per tick), kept as
  the numerics cross-check baseline.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def split_stage_params(model, params, n_stages: int):
    """Repack transformer params into the pipeline layout:

    - ``stages``: every per-block leaf stacked to [n_stages, blocks_per_stage, ...]
      (shard the leading axis over 'pp')
    - ``shared``: embed / final_ln / head, replicated on every stage.
    """
    n_layers = model.num_layers
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} blocks not divisible by {n_stages} stages")
    per = n_layers // n_stages
    blocks = [params[f"block_{i}"] for i in range(n_layers)]
    stage_trees = []
    for s in range(n_stages):
        group = blocks[s * per:(s + 1) * per]
        stage_trees.append(jax.tree.map(lambda *xs: jnp.stack(xs), *group))
    stages = jax.tree.map(lambda *xs: jnp.stack(xs), *stage_trees)
    # copy shared leaves: the pp train step donates its params, and aliasing
    # the caller's arrays would delete them out from under the caller
    shared = jax.tree.map(jnp.array,
                          {k: v for k, v in params.items()
                           if not k.startswith("block_")})
    return {"stages": stages, "shared": shared}


def merge_stage_params(model, pp_params):
    """Inverse of :func:`split_stage_params` (e.g. for checkpoint export)."""
    n_layers = model.num_layers
    stages = pp_params["stages"]
    flat_example = jax.tree.leaves(stages)[0]
    n_stages, per = flat_example.shape[0], flat_example.shape[1]
    assert n_stages * per == n_layers
    out = dict(pp_params["shared"])
    for i in range(n_layers):
        s, b = divmod(i, per)
        out[f"block_{i}"] = jax.tree.map(lambda x: x[s, b], stages)
    return out


def pp_pspecs(pp_params):
    """PartitionSpecs: stage axis over 'pp', shared replicated."""
    stages = jax.tree.map(lambda x: P("pp"), pp_params["stages"])
    shared = jax.tree.map(lambda x: P(), pp_params["shared"])
    return {"stages": stages, "shared": shared}


def split_stage_pspecs(pp_axis: str, block_pspecs, shared_pspecs):
    """PartitionSpecs for the :func:`split_stage_params` layout that KEEP
    per-block leaf sharding: every stage leaf becomes
    ``P(pp_axis, None, *block_leaf_spec)`` — the leading stage axis shards
    over ``pp_axis``, the blocks-per-stage axis stays replicated, and the
    original per-block axes (e.g. megatron ``tp`` columns) ride behind. This
    is how the serving engine composes a 2D ``pp x tp`` mesh: depth shards
    via the stage stack, width via the block leaves. ``block_pspecs`` is the
    spec tree for ONE block; ``shared_pspecs`` passes through for the
    stage-replicated embed/final_ln leaves."""
    stages = jax.tree.map(lambda sp: P(pp_axis, None, *tuple(sp)),
                          block_pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    return {"stages": stages, "shared": shared_pspecs}


_OP_NONE, _OP_FWD, _OP_BWD = 0, 1, 2


def _simulate_1f1b(P: int, M: int):
    """Tick-by-tick 1F1B schedule tables (pure python; P, M static).

    Greedy rule per stage: backward when a cotangent is ready and either the
    in-flight limit ``P - s`` is hit or no forward is possible; otherwise
    forward when an activation is ready. Yields the classic warmup /
    steady-1F1B / cooldown shape. Returns:

    - ``ops[t, s]``   — executed op (NONE/FWD/BWD); the LAST stage's FWD is
      rewritten to NONE (its input is already stored by the arrival write,
      and its BWD tick recomputes forward through the head anyway)
    - ``mbs[t, s]``   — microbatch index of the op
    - ``arrf[t, s]``  — 1 when a forward activation arrives at stage s this
      tick (stage s-1 ran FWD at t-1); ``arrm[t, s]`` its microbatch.

    Invariants (asserted): per-stage live-slot window never exceeds P and
    in-window microbatches stay distinct mod P — so one ``[P, ...]`` ring
    buffer keyed ``m % P`` is both the arrival queue and the bwd input store.
    Cotangents always arrive exactly on their consumption tick (bwd has
    priority), so they need no buffer at all.
    """
    ops, mbs = [], []
    fwd_done = [0] * P
    bwd_done = [0] * P
    act_ready = [dict() for _ in range(P)]
    cot_ready = [dict() for _ in range(P)]
    for m in range(M):
        act_ready[0][m] = 0
    t = 0
    while any(b < M for b in bwd_done):
        if t > 4 * (M + P) + 16:
            raise AssertionError("1f1b schedule failed to converge")
        row_op, row_mb = [_OP_NONE] * P, [0] * P
        for s in range(P):
            in_flight = fwd_done[s] - bwd_done[s]
            m_b, m_f = bwd_done[s], fwd_done[s]
            can_bwd = m_b < M and cot_ready[s].get(m_b, 1 << 30) <= t
            can_fwd = m_f < M and act_ready[s].get(m_f, 1 << 30) <= t
            if can_bwd and (in_flight >= P - s or not can_fwd):
                row_op[s], row_mb[s] = _OP_BWD, m_b
            elif can_fwd and in_flight < P - s:
                row_op[s], row_mb[s] = _OP_FWD, m_f
        for s in range(P):
            if row_op[s] == _OP_FWD:
                m = row_mb[s]
                fwd_done[s] += 1
                if s + 1 < P:
                    act_ready[s + 1][m] = t + 1
                else:
                    cot_ready[s][m] = t + 1
            elif row_op[s] == _OP_BWD:
                m = row_mb[s]
                bwd_done[s] += 1
                if s - 1 >= 0:
                    cot_ready[s - 1][m] = t + 1
        ops.append(row_op)
        mbs.append(row_mb)
        t += 1
    ops = np.array(ops, np.int32)
    mbs = np.array(mbs, np.int32)
    T = ops.shape[0]
    arrf = np.zeros((T, P), np.int32)
    arrm = np.zeros((T, P), np.int32)
    for tt in range(1, T):
        for s in range(1, P):
            if ops[tt - 1, s - 1] == _OP_FWD:
                arrf[tt, s] = 1
                arrm[tt, s] = mbs[tt - 1, s - 1]
    # check the ring-buffer invariants (see docstring)
    for s in range(1, P):
        live = set()
        for tt in range(T):
            if arrf[tt, s]:
                live.add(int(arrm[tt, s]))
            if ops[tt, s] == _OP_BWD:
                live.discard(int(mbs[tt, s]))
            if len(live) > 1:
                ms = sorted(live)
                assert len(live) <= P and ms[-1] - ms[0] < P, (s, tt, ms)
    # last stage executes nothing at its FWD ticks (timing only — see doc)
    ops_exec = ops.copy()
    ops_exec[:, P - 1] = np.where(ops_exec[:, P - 1] == _OP_FWD, _OP_NONE,
                                  ops_exec[:, P - 1])
    return ops_exec, mbs, arrf, arrm


def make_pp_train_step(model, optimizer, mesh: Mesh, n_microbatches: int = 1,
                       pp_axis: str = "pp", schedule: str = "gpipe",
                       dp_axis: str = "dp", task: str = "classifier",
                       _raw: bool = False):
    """Pipeline-parallel train step for the transformer families.

    Signature: ``step(pp_params, opt_state, ids, y, rng) ->
    (pp_params, opt_state, loss)`` — params in :func:`split_stage_params`
    layout sharded over 'pp'. ``task``:

    - ``'classifier'`` — ``y`` is one-hot labels [B, C]; mean-pool + CE head.
    - ``'lm'``        — causal next-token NLL; ``y`` is the attention mask
      [B, S] (token weights for the loss; blocks run causal).

    When the mesh ALSO has a ``dp_axis``, the batch shards over it and each
    data-parallel replica runs the pipeline on its shard (stage grads pmean
    over dp; composition of pp x dp). ``schedule`` is ``'gpipe'``
    (overlapped, ``M + P - 1`` serial stage-times) or ``'sequential'``
    (``M * P``, the numerics baseline). The returned callable exposes
    ``schedule_ticks``: the number of serial stage-computations in its
    forward sweep.
    """
    if schedule not in ("gpipe", "1f1b", "sequential"):
        raise ValueError(f"unknown pp schedule {schedule!r}")
    if schedule == "1f1b" and mesh.shape[pp_axis] < 2:
        # the last-stage arrival-store optimization leaves a 1-stage table
        # with no forward ops at all — a degenerate pipeline anyway
        raise ValueError("schedule='1f1b' needs a pp axis of size >= 2")
    if task not in ("classifier", "lm"):
        raise ValueError(f"unknown pp task {task!r}")
    has_dp = dp_axis in mesh.axis_names and mesh.shape[dp_axis] > 1
    causal = task == "lm"
    n_stages = mesh.shape[pp_axis]
    per = model.num_layers // n_stages
    M = n_microbatches
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_apply(stage_blocks, x, rng):
        """Apply this device's ``per`` blocks (stacked leading axis)."""

        def body(carry, block):
            x, rng = carry
            x, rng = model._block(block, x, None, causal, True, rng)
            return (x, rng), None

        (x, rng), _ = jax.lax.scan(body, (x, rng), stage_blocks)
        return x

    from ..models.transformer import _dense, _layer_norm

    def embed_micro(shared, ids, m_idx, mb):
        """Embed microbatch ``m_idx`` (clamped: fill/drain ticks reuse a real
        slice, their chains are masked out of the loss)."""
        mi = jnp.clip(m_idx, 0, M - 1)
        idsm = jax.lax.dynamic_slice_in_dim(ids, mi * mb, mb, axis=0)
        x = jnp.take(shared["embed"]["tok"], idsm, axis=0)
        x = x + shared["embed"]["pos"][:ids.shape[1]][None, :, :]
        return model.cast(x)

    def _mb_slice(a, m_idx, mb):
        return jax.lax.dynamic_slice_in_dim(
            a, jnp.clip(m_idx, 0, M - 1) * mb, mb, axis=0)

    def head_loss(shared, x, ids, y, m_idx, mb):
        """Mean loss of microbatch ``m_idx`` from final-stage activations."""
        x = _layer_norm(x, shared["final_ln"]["scale"], shared["final_ln"]["bias"])
        if task == "lm":
            idsm = _mb_slice(ids, m_idx, mb).astype(jnp.int32)
            w = _mb_slice(y, m_idx, mb)[:, 1:].astype(jnp.float32)
            logits = jnp.matmul(x.astype(jnp.float32),
                                shared["embed"]["tok"].T.astype(jnp.float32))
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            nll = -jnp.take_along_axis(logp, idsm[:, 1:, None], axis=-1)[..., 0]
            per_ex = (jnp.sum(nll * w, axis=-1)
                      / jnp.maximum(jnp.sum(w, axis=-1), 1e-6))
            return jnp.mean(per_ex)
        ym = _mb_slice(y, m_idx, mb)
        pooled = jnp.mean(x, axis=1).astype(jnp.float32)
        logits = _dense(pooled, shared["head"]["kernel"], shared["head"]["bias"])
        # softmax_xent accepts one-hot [mb, C] or index [mb]/[mb, 1] labels
        # (the estimator's scalar labelCol path) — a raw ym*log_softmax sum
        # would silently broadcast index labels into a meaningless loss
        from ..models.base import softmax_xent
        return jnp.mean(softmax_xent(logits, ym))

    # ---- gpipe: every stage computes every tick, on microbatch (t - s) ----

    def gpipe_loss(pp_params, ids, y, rng):
        s = jax.lax.axis_index(pp_axis)
        shared = pp_params["shared"]
        my_blocks = jax.tree.map(lambda a: a[0], pp_params["stages"])
        ids = ids.astype(jnp.int32)
        b, seq = ids.shape
        mb = b // M
        T = M + n_stages - 1  # fill-drain span

        ckpt_stage = jax.checkpoint(stage_apply)

        def tick(carry, t):
            x_in, loss_acc = carry
            m_here = t - s  # logical microbatch this stage holds at tick t
            # stage 0 ingests a fresh microbatch; later stages use the ring
            inj = embed_micro(shared, ids, t, mb)
            inp = jnp.where(s == 0, inj, x_in)
            out = ckpt_stage(my_blocks, inp,
                             jax.random.fold_in(rng, t * n_stages + s))
            # the final stage finishes microbatch m_here this tick
            lval = head_loss(shared, out, ids, y, m_here, mb)
            live = (s == n_stages - 1) & (m_here >= 0) & (m_here < M)
            loss_acc = loss_acc + jnp.where(live, lval, 0.0)
            x_next = jax.lax.ppermute(out, pp_axis, ring)
            return (x_next, loss_acc), None

        x0 = jnp.zeros((mb, seq, model.hidden),
                       model.compute_dtype or jnp.float32)
        (_, loss_acc), _ = jax.lax.scan(tick, (x0, jnp.zeros(())),
                                        jnp.arange(T))
        # LOCAL contribution (nonzero on the last stage only). Deliberately
        # NOT psum'd here: differentiating through a psum inside shard_map
        # transposes it as psum — every device would receive the SUM of all
        # devices' cotangent seeds and grads would inflate by P. The caller
        # psums the forward value for reporting only.
        return loss_acc / M

    # ---- 1f1b: table-driven one-forward-one-backward (see module doc) -----

    if schedule == "1f1b":
        _ops_np, _mbs_np, _arrf_np, _arrm_np = _simulate_1f1b(n_stages, M)
        _T_1f1b = _ops_np.shape[0]
        ring_back = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def f1b_grads_and_loss(pp_params, ids, y, rng):
        """Hand-scheduled 1F1B step body (inside shard_map). Returns LOCAL
        (grads, loss_sum) — the caller does the pp/dp reductions."""
        s = jax.lax.axis_index(pp_axis)
        shared = pp_params["shared"]
        my_blocks = jax.tree.map(lambda a: a[0], pp_params["stages"])
        ids = ids.astype(jnp.int32)
        b, seq = ids.shape
        mb = b // M
        dt = model.compute_dtype or jnp.float32
        zeros_act = jnp.zeros((mb, seq, model.hidden), dt)
        zero_dgr = jax.tree.map(jnp.zeros_like, pp_params)
        OPS, MBS = jnp.asarray(_ops_np), jnp.asarray(_mbs_np)
        ARRF, ARRM = jnp.asarray(_arrf_np), jnp.asarray(_arrm_np)

        def _rng_for(m):
            # fwd send and bwd recompute fold identically -> same dropout
            return jax.random.fold_in(rng, m * n_stages + s)

        def tick(carry, t):
            xbuf, send_f, send_b, gacc, lacc = carry
            x_arr = jax.lax.ppermute(send_f, pp_axis, ring)
            g_arr = jax.lax.ppermute(send_b, pp_axis, ring_back)
            op = OPS[t][s]
            m = MBS[t][s]
            # arrival: stash the incoming activation in its ring slot (the
            # same buffer the bwd recompute reads — invariants in
            # _simulate_1f1b guarantee no live slot is ever clobbered)
            slot_in = ARRM[t][s] % n_stages
            xbuf = jax.lax.cond(
                ARRF[t][s] == 1,
                lambda xb: jax.lax.dynamic_update_index_in_dim(
                    xb, x_arr.astype(dt), slot_in, 0),
                lambda xb: xb, xbuf)

            def none_br(_):
                return zeros_act, zeros_act, zero_dgr, jnp.zeros(())

            def fwd_br(_):
                x0 = embed_micro(shared, ids, m, mb)
                xs = jax.lax.dynamic_index_in_dim(xbuf, m % n_stages, 0,
                                                  keepdims=False)
                x_in = jnp.where(s == 0, x0, xs)
                out = stage_apply(my_blocks, x_in, _rng_for(m))
                return out, zeros_act, zero_dgr, jnp.zeros(())

            def bwd_br(_):
                xs = jax.lax.dynamic_index_in_dim(xbuf, m % n_stages, 0,
                                                  keepdims=False)
                rngm = _rng_for(m)

                def last_br(_):
                    def f(blocks, sh, x):
                        return head_loss(sh, stage_apply(blocks, x, rngm),
                                         ids, y, m, mb)
                    lval, vjp = jax.vjp(f, my_blocks, shared, xs)
                    db, dsh, dx = vjp(jnp.ones(()))
                    return db, dsh, dx.astype(dt), lval

                def first_br(_):
                    def f(blocks, sh):
                        return stage_apply(
                            blocks, embed_micro(sh, ids, m, mb), rngm)
                    out, vjp = jax.vjp(f, my_blocks, shared)
                    db, dsh = vjp(g_arr.astype(out.dtype))
                    return db, dsh, zeros_act, jnp.zeros(())

                def mid_br(_):
                    def f(blocks, x):
                        return stage_apply(blocks, x, rngm)
                    out, vjp = jax.vjp(f, my_blocks, xs)
                    db, dx = vjp(g_arr.astype(out.dtype))
                    dsh = jax.tree.map(jnp.zeros_like, shared)
                    return db, dsh, dx.astype(dt), jnp.zeros(())

                db, dsh, dx, lval = jax.lax.cond(
                    s == n_stages - 1, last_br,
                    lambda o: jax.lax.cond(s == 0, first_br, mid_br, o),
                    None)
                dgr = {"stages": jax.tree.map(lambda g: g[None], db),
                       "shared": dsh}
                return zeros_act, dx, dgr, lval

            send_f_new, send_b_new, dgr, dl = jax.lax.switch(
                op, [none_br, fwd_br, bwd_br], None)
            gacc = jax.tree.map(jnp.add, gacc, dgr)
            return (xbuf, send_f_new, send_b_new, gacc, dl + lacc), None

        xbuf0 = jnp.zeros((n_stages, mb, seq, model.hidden), dt)
        carry0 = (xbuf0, zeros_act, zeros_act, zero_dgr, jnp.zeros(()))
        (_, _, _, grads, loss_sum), _ = jax.lax.scan(
            tick, carry0, jnp.arange(_T_1f1b))
        grads = jax.tree.map(lambda g: g / M, grads)
        return grads, loss_sum / M

    # ---- sequential: one stage live per tick (round-1 baseline) -----------

    def forward_one(pp_params, ids, y, rng):
        s = jax.lax.axis_index(pp_axis)
        shared = pp_params["shared"]
        my_blocks = jax.tree.map(lambda a: a[0], pp_params["stages"])

        ids = ids.astype(jnp.int32)
        b, seq = ids.shape
        x = jnp.take(shared["embed"]["tok"], ids, axis=0)
        x = x + shared["embed"]["pos"][:seq][None, :, :]
        x = model.cast(x)

        def tick(t, x):
            def run(x):
                return stage_apply(my_blocks, x, jax.random.fold_in(rng, t))
            x = jax.lax.cond(s == t, run, lambda x: x, x)
            return jax.lax.ppermute(x, pp_axis, ring)

        x = jax.lax.fori_loop(0, n_stages, tick, x)
        # after n_stages ticks the fully-processed activation is back on
        # stage 0; head_loss (which applies the final layer norm) with
        # m_idx=0 and mb=rows reuses the task-specific head — the caller
        # already sliced this microbatch
        lval = head_loss(shared, x, ids, y, 0, ids.shape[0])
        # only stage 0 holds the real result: the LOCAL masked contribution
        # (no psum here — see gpipe_loss on why psum-in-the-loss inflates
        # gradients by P under shard_map autodiff)
        return jnp.where(s == 0, lval, 0.0)

    param_specs = {"stages": P(pp_axis), "shared": P()}  # pytree prefixes
    data_spec = P(dp_axis) if has_dp else P()

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, data_spec, data_spec, P()),
             out_specs=(param_specs, P()),
             check_vma=False)
    def grad_fn(pp_params, ids, y, rng):
        if ids.shape[0] % M or ids.shape[0] < M:
            raise ValueError(
                f"batch {ids.shape[0]} must be a positive multiple of "
                f"n_microbatches={M}")
        if has_dp:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(dp_axis))
        if schedule == "gpipe":
            loss, grads = jax.value_and_grad(gpipe_loss, argnums=0)(
                pp_params, ids, y, rng)
            loss = jax.lax.psum(loss, pp_axis)  # reporting only
        elif schedule == "1f1b":
            grads, loss = f1b_grads_and_loss(pp_params, ids, y, rng)
            loss = jax.lax.psum(loss, pp_axis)  # nonzero on last stage only
        else:
            # per-microbatch value_and_grad accumulation: only one
            # microbatch's activations are ever live during backward
            mb = ids.shape[0] // M

            def micro(i, carry):
                grads_acc, loss_acc = carry
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * mb, mb, 0)
                l, g = jax.value_and_grad(forward_one)(
                    pp_params, sl(ids), sl(y), jax.random.fold_in(rng, i))
                return jax.tree.map(jnp.add, grads_acc, g), loss_acc + l

            zero = jax.tree.map(jnp.zeros_like, pp_params)
            grads, loss = jax.lax.fori_loop(0, M, micro, (zero, jnp.zeros(())))
            grads = jax.tree.map(lambda x: x / M, grads)
            loss = jax.lax.psum(loss, pp_axis) / M  # reporting only
        # shared params got gradient contributions on every stage: reduce;
        # stage params are exclusively pp-local (grads already correct per
        # stage) but with data parallelism every dp replica contributed
        grads["shared"] = jax.tree.map(
            lambda gg: jax.lax.psum(gg, pp_axis), grads["shared"])
        if has_dp:
            grads = jax.tree.map(lambda gg: jax.lax.pmean(gg, dp_axis), grads)
            loss = jax.lax.pmean(loss, dp_axis)
        return grads, loss

    def step(pp_params, opt_state, ids, y, rng):
        grads, loss = grad_fn(pp_params, ids, y, rng)
        # the optax update runs under GSPMD: sharded stage leaves update
        # locally, replicated shared leaves update identically everywhere
        updates, opt_state = optimizer.update(grads, opt_state, pp_params)
        pp_params = optax.apply_updates(pp_params, updates)
        return pp_params, opt_state, loss

    # _raw hands back the traceable step for callers embedding it in their
    # own compiled program (the trainer's epoch scan); default is jitted.
    out = step if _raw else jax.jit(step, donate_argnums=(0, 1))
    # serial forward span in stage-times: the schedule's defining number
    # (for 1f1b the table length counts COMBINED fwd+bwd compute slots)
    out.schedule_ticks = (M + n_stages - 1 if schedule == "gpipe"
                          else _T_1f1b if schedule == "1f1b"
                          else M * n_stages)
    return out
