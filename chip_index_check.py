"""The indexer's kernels on the chip, alone, at the shapes of
``train-keye-vl2-ep8-seq8k`` (one row of 8192 tokens, 16 index heads of 64,
the top 2048 keys, 256 queries a grid step)::

    python chip_index_check.py [--parent <checkout>]
    JAX_PLATFORMS=cpu python chip_index_check.py --rehearse

One JSON line each:

- ``select``: the kernel ``index_select``'s selection against
  ``select_block``'s on the same scores, bit for bit. The scores are the
  kernel's own tile function's, written out by a small kernel here.
- ``loss``: ``indexer_loss``'s value and gradients against
  ``indexer_loss_reference``'s, which is given 1024 queries at a time (all
  scores of a row at once are 4.3 GB, three times over backward), in
  bfloat16 as the model feeds them and in float32.
- ``times``: each kernel's wall time alone, in ms, and with ``--parent`` the
  XLA path of that checkout's ``ops/sparse_attention.py`` beside it.
"""

import argparse
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from sparkflow_tpu.ops import sparse_attention as sa

CELL = dict(seq=8192, heads=16, dim=64, topk=2048, block=256, chunk=1024)
REHEARSE = dict(seq=256, heads=2, dim=8, topk=40, block=64, chunk=64)


def inputs(shape, dtype, seed=0):
    r = np.random.default_rng(seed)
    s, nh, d = shape["seq"], shape["heads"], shape["dim"]
    return (jnp.asarray(r.normal(size=(1, s, nh, d)), dtype),
            jnp.asarray(r.normal(size=(1, s, d)), dtype),
            jnp.asarray(r.normal(size=(1, s, nh)), dtype))


def kernel_scores(qi, ki, w, block_q, block_k, interpret):
    """``[B, S, S]`` float32: every tile as ``_index_tile`` makes it."""
    q, k, ww = sa._index_layout(qi, ki, w)
    b, nh, s, d = q.shape

    def body(q_ref, k_ref, w_ref, o_ref):
        o_ref[0] = sa._index_tile(q_ref, k_ref[0], w_ref)

    return pl.pallas_call(
        body, grid=(b, s // block_q, s // block_k),
        in_specs=[pl.BlockSpec((1, nh, block_q, d),
                               lambda bi, i, j: (bi, 0, i, 0)),
                  pl.BlockSpec((1, block_k, d), lambda bi, i, j: (bi, j, 0)),
                  pl.BlockSpec((1, nh, block_q, 1),
                               lambda bi, i, j: (bi, 0, i, 0))],
        out_specs=pl.BlockSpec((1, block_q, block_k),
                               lambda bi, i, j: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        interpret=interpret)(q, k, ww)


def check_select(shape, dtype, interpret):
    qi, ki, w = inputs(shape, dtype)
    s, topk, block = shape["seq"], shape["topk"], shape["block"]
    got = jax.jit(lambda *a: sa.index_select(*a, topk, block))(qi, ki, w)
    scores = jax.jit(lambda *a: kernel_scores(
        *a, block, sa._block(s), interpret))(qi, ki, w)[0]
    firsts = jnp.arange(0, s, block, dtype=jnp.int32)
    want = jax.jit(lambda sc: jax.lax.map(
        lambda a: sa.select_block(a[0], a[1], topk),
        (sc.reshape(s // block, block, s), firsts)))(scores).reshape(s, s)
    got = np.asarray(got[0]) != 0
    kept = got.sum(-1)
    return dict(line="select", dtype=jnp.dtype(dtype).name, seq=s, topk=topk,
                differing=int((got != np.asarray(want)).sum()),
                kept_mean=float(kept.mean()), kept_max=int(kept.max()),
                kept_as_counted=bool((kept == np.minimum(
                    np.arange(s) + 1, topk)).all()),
                scores_exactly_zero=int((np.asarray(scores) == 0).sum()))


def check_loss(shape, dtype, precision):
    qi, ki, w = inputs(shape, dtype, seed=1)
    s, topk, block, chunk = (shape[k] for k in ("seq", "topk", "block",
                                                "chunk"))
    mask = jax.jit(lambda *a: sa.index_select(*a, topk, block))(qi, ki, w)
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(1, s, s)),
                         jnp.float32)
    target = jax.nn.softmax(jnp.where(mask != 0, logits, sa.NEG_INF), axis=-1)
    target = jnp.where(mask != 0, target, 0.0)
    value, grads = jax.jit(jax.value_and_grad(
        lambda *a: sa.indexer_loss(*a, mask, target, block)[0],
        argnums=(0, 1, 2)))(qi, ki, w)

    @jax.jit
    def part(q, k, ww, m, p):
        with jax.default_matmul_precision(precision):
            return jax.value_and_grad(
                lambda q, k, ww: sa.indexer_loss_reference(
                    q, k, ww, m, p)[0] * (chunk / s), argnums=(0, 1, 2))(
                        q, k, ww)

    want, dq, dw, dk = 0.0, [], [], 0.0
    for c in range(0, s, chunk):
        rows = slice(c, c + chunk)
        v, (a, b, d) = part(qi[:, rows], ki, w[:, rows], mask[:, rows],
                            target[:, rows])
        want, dk = want + float(v), dk + b.astype(jnp.float32)
        dq.append(a)
        dw.append(d)
    wants = (jnp.concatenate(dq, 1), dk, jnp.concatenate(dw, 1))
    out = dict(line="loss", dtype=jnp.dtype(dtype).name,
               reference_precision=precision, value=float(value),
               reference=want, value_gap=abs(float(value) - want) / abs(want))
    for name, g, r in zip(("dqi", "dki", "dw"), grads, wants):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        out[name] = dict(largest_gap=float(np.abs(g - r).max()),
                         largest=float(np.abs(r).max()))
    return out


def wall_ms(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def times(shape, dtype, parent):
    qi, ki, w = inputs(shape, dtype, seed=3)
    s, topk, block = shape["seq"], shape["topk"], shape["block"]
    out = dict(line="times", dtype=jnp.dtype(dtype).name)

    def programs(mod):
        select = jax.jit(lambda *a: mod.index_select(*a, topk, block))
        mask = select(qi, ki, w)
        target = mask.astype(jnp.float32) / jnp.sum(mask, -1, keepdims=True)
        loss = lambda *a: mod.indexer_loss(*a, mask, target, block)[0]
        return select, jax.jit(loss), jax.jit(jax.grad(loss,
                                                       argnums=(0, 1, 2)))

    select, loss, grad = programs(sa)
    out["index_select_ms"] = wall_ms(select, qi, ki, w)
    # every tile of the row, the half above the diagonal too, written out
    out["all_score_tiles_ms"] = wall_ms(jax.jit(lambda *a: kernel_scores(
        *a, block, sa._block(s), jax.default_backend() != "tpu")), qi, ki, w)
    out["indexer_loss_ms"] = wall_ms(loss, qi, ki, w)
    out["indexer_loss_and_grad_ms"] = wall_ms(grad, qi, ki, w)
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_sparse_attention", os.path.join(
                parent, "sparkflow_tpu", "ops", "sparse_attention.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        select, loss, grad = programs(mod)
        out["parent_index_select_ms"] = wall_ms(select, qi, ki, w)
        out["parent_indexer_loss_ms"] = wall_ms(loss, qi, ki, w)
        out["parent_indexer_loss_and_grad_ms"] = wall_ms(grad, qi, ki, w)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parent", default="")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.rehearse):
        raise SystemExit("no TPU: run through the chip tool, or --rehearse")
    shape = REHEARSE if args.rehearse else CELL
    dev = jax.devices()[0]
    print(json.dumps(dict(line="device", platform=dev.platform,
                          kind=dev.device_kind, shape=shape)), flush=True)
    ok = True
    for dtype in (jnp.bfloat16, jnp.float32):
        sel = check_select(shape, dtype, not on_chip)
        print(json.dumps(sel), flush=True)
        ok = ok and sel["differing"] == 0 and sel["kept_as_counted"]
    # the model feeds bfloat16. With float32 operands the kernels' products
    # are the MXU's default ones: against a reference at "highest" the relu
    # of a dot near 0 falls the other way now and then
    for dtype, precision in ((jnp.bfloat16, "default"),
                             (jnp.float32, "default"),
                             (jnp.float32, "highest")):
        print(json.dumps(check_loss(shape, dtype, precision)), flush=True)
    print(json.dumps(times(shape, jnp.bfloat16, args.parent)), flush=True)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
