"""The experts' row movements on the chip, alone, at the shapes of the two MoE
cells (a row of 8192 positions of width 2048 in bfloat16, 8 experts a token,
16 held: a buffer of 69 632 rows)::

    python chip_rows_check.py [--parent <checkout>]
    JAX_PLATFORMS=cpu python chip_rows_check.py --rehearse

One JSON line a routing (about a fifth of the buffer's rows in use, all of
them, one tile an expert):

- ``equal``: the kernels ``expert_rows_in`` and ``expert_rows_out`` against
  ``_take`` (the plain gather they replaced) with the buffer poisoned past
  ``tiles_used``: the rows from tokens bit for bit where defined, the tokens
  from rows to a rounding of the type (the plain form sums a token's pairs
  in another order), the gates' gradient to float32's.
- ``ms``: each of the five movements' wall time, the kernel's and the plain
  form's, and with ``--parent`` that checkout's whole layer
  (``dropless_experts`` forward and backward) beside this one's.
"""

import argparse
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from sparkflow_tpu.ops import grouped_matmul as gm

CELL = dict(tokens=8192, width=2048, expert_width=768, experts=128, k=8,
            held=16, tile=gm.TILE)
REHEARSE = dict(tokens=64, width=32, expert_width=16, experts=16, k=2,
                held=4, tile=8)


def routing(shape, share, seed=0):
    """``experts [N, k]`` with about ``share`` of the pairs on the held
    experts (``None``: one pair each, so one tile an expert)."""
    r = np.random.default_rng(seed)
    n, k, e, held = (shape[a] for a in ("tokens", "k", "experts", "held"))
    if share is None:
        experts = held + r.integers(0, e - held, size=(n, k))
        experts[:held, 0] = np.arange(held)
        return jnp.asarray(experts, jnp.int32)
    scores = r.random((n, e))
    scores[:, :held] += (share >= 1.0) * 2.0 + (share - held / e)
    return jnp.asarray(np.argsort(-scores, axis=1)[:, :k], jnp.int32)


def wall_ms(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t0) / n, 3)


def movements(shape, experts, dtype):
    """The five movements as ``{name: (kernel form, plain form, args)}``."""
    n, h, tile = shape["tokens"], shape["width"], shape["tile"]
    lay = gm.group_rows(experts, 0, shape["held"], tile)
    rows = lay.token_of_row.shape[0]
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(n, h)), dtype)
    gates = jnp.asarray(r.random((n, shape["k"])), jnp.float32)
    live = gm.live_rows(rows, lay.tiles_used, tile)
    # a buffer nobody wrote past the tiles in use: NaN there
    out = jnp.where(live, jnp.asarray(r.normal(size=(rows, h)), dtype),
                    jnp.nan)
    clean = jnp.where(live, out, 0)
    where = (lay.token_of_row, lay.row_of_pair, lay.tiles_used, tile)
    gate_of_row = gm._gate_of_row(gates, lay.row_of_pair, rows)

    def plain_combine(o, g):
        return jnp.sum(gm._take(o, lay.row_of_pair).astype(jnp.float32)
                       * g[..., None], axis=1).astype(o.dtype)

    return lay, live, {
        "dispatch": (lambda a: gm.dispatch(a, *where),
                     lambda a: gm._take(a, lay.token_of_row), (x,)),
        "dispatch_bwd": (
            lambda g: jax.vjp(lambda a: gm.dispatch(a, *where), x)[1](g)[0],
            lambda g: jnp.sum(gm._take(g, lay.row_of_pair).astype(
                jnp.float32), axis=1).astype(g.dtype), (out,)),
        "combine": (lambda o, g: gm.combine(o, g, *where),
                    plain_combine, (out, gates)),
        "combine_bwd": (
            lambda o, g, dy: jax.vjp(
                lambda a, b: gm.combine(a, b, *where), o, g)[1](dy),
            lambda o, g, dy: (
                (gm._take(dy, lay.token_of_row).astype(jnp.float32)
                 * gate_of_row).astype(o.dtype),
                jnp.sum(gm._take(o, lay.row_of_pair).astype(jnp.float32)
                        * dy[:, None, :].astype(jnp.float32), axis=-1)),
            (out, gates, x)),
    }, clean


def check(shape, share, dtype, parent):
    experts = routing(shape, share)
    lay, live, moves, clean = movements(shape, experts, dtype)
    line = {"share": share, "tiles_used": int(lay.tiles_used[0]),
            "tiles": int(lay.tile_expert.shape[0]), "equal": {}, "ms": {}}
    for name, (kernel, plain, args) in moves.items():
        kernel, plain = jax.jit(kernel), jax.jit(plain)
        got = kernel(*args)
        # the plain form reads what the kernels must not: give it zeros there
        want = plain(*[clean if a.shape == clean.shape else a for a in args])
        gaps = []
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if g.shape == clean.shape:
                g, w = jnp.where(live, g, 0), jnp.where(live, w, 0)
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            gaps.append(float(np.max(np.abs(g - w)
                                     / np.maximum(np.abs(w), 1.0))))
        line["equal"][name] = gaps
        line["ms"][name] = [wall_ms(kernel, *args), wall_ms(plain, *args)]
    line["ms"]["layer"] = [layer_ms(gm, shape, experts, dtype)]
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_grouped_matmul", os.path.join(
                parent, "sparkflow_tpu", "ops", "grouped_matmul.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        line["ms"]["layer"].append(layer_ms(mod, shape, experts, dtype))
    print(json.dumps(line), flush=True)


def layer_ms(mod, shape, experts, dtype):
    """``dropless_experts`` of ``mod``, forward and backward."""
    r = np.random.default_rng(3)
    n, h, m, held = (shape[a] for a in ("tokens", "width", "expert_width",
                                        "held"))
    x = jnp.asarray(r.normal(size=(n, h)), dtype)
    gates = jnp.asarray(r.random((n, shape["k"])), jnp.float32)
    w = [jnp.asarray(r.normal(size=s) * 0.02, dtype)
         for s in ((held, h, m), (held, h, m), (held, m, h))]

    def loss(x, gates, *w):
        return jnp.sum(mod.dropless_experts(
            x, gates, experts, *w, 0, tile=shape["tile"])[0].astype(
                jnp.float32))

    return wall_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))),
                   x, gates, *w)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parent")
    args = ap.parse_args()
    shape = REHEARSE if args.rehearse else CELL
    dtype = jnp.float32 if args.rehearse else jnp.bfloat16
    print(json.dumps({"device": jax.devices()[0].device_kind, "shape": shape,
                      "dtype": jnp.dtype(dtype).name}), flush=True)
    for share in (0.17, 1.0, None):
        check(shape, share, dtype, args.parent)


if __name__ == "__main__":
    main()
