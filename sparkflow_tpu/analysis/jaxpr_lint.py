"""Graph-level lint (GC-J1xx): abstract-trace a program, report what will
hurt on hardware — before it burns TPU hours.

Everything here runs on :func:`jax.make_jaxpr` / :func:`jax.eval_shape`
machinery: the model function is traced with ``ShapeDtypeStruct`` inputs,
so no FLOP executes, no buffer is allocated, and no compile happens — a
full lint of the repo's model presets against every registry optimizer is
sub-second on CPU. The analysis is Parallax-style "ahead of execution":
placement and dtype mistakes are graph properties, visible in the jaxpr
without running it.

Rules
-----
GC-J101  implicit-reshard   a ``sharding_constraint`` eqn pins a tensor to
                            a different PartitionSpec than its declared
                            input spec — GSPMD will insert a resharding
                            collective on every step.
GC-J102  large-replicated   an input leaf above ``large_bytes`` declared
                            replicated (``P()``) on a >1-device mesh.
GC-J103  f64-promotion      re-tracing under x64 turns a float32 program
                            partially float64: a Python/numpy double made
                            it into the graph. Such programs are one
                            ``jax_enable_x64`` flip away from running at
                            half speed and double memory.
GC-J104  weak-type-output   a top-level output is weakly typed — a bare
                            scalar literal dominates it, so its dtype is
                            decided by the caller, not the model.
GC-J105  missed-donation    a large input whose avals all reappear in the
                            outputs is not donated; XLA must keep input
                            and output buffers live simultaneously.
GC-J106  sharding-config-   the collectives actually present in a train
         mismatch           step's jaxpr contradict its declared
                            ``ShardingConfig``: a ``zero_stage>=1`` config
                            whose step never ``reduce_scatter``s is paying
                            full-size gradient all-reduces (the sharded
                            update silently degraded); a ``zero_stage=0``
                            config whose step runs scatter machinery is
                            mislabeled and will checkpoint/restore with
                            the wrong layout assumptions. The same rule
                            covers the decode plane
                            (:func:`lint_decode_step`): an engine that
                            declares ``tp_axis``/``ep_axis`` must show a
                            ``psum`` over that axis in its decode-step
                            jaxpr (the rejoin after the O-projection / MoE
                            combine — without it each shard keeps partial
                            activations and the logits are garbage), and a
                            TP-less engine must show none (a collective
                            the config doesn't declare means the program
                            and its memory/latency model disagree).
GC-J107  collective-        a collective (psum/all_gather/psum_scatter/...)
         divergence         sits inside the branches of a ``lax.cond`` or
                            the body/condition of a ``lax.while_loop``.
                            Collectives are rendezvous points: every device
                            on the axis must reach the same collective the
                            same number of times. A data-dependent
                            predicate that evaluates differently across
                            devices sends some of them into the collective
                            and some around it — the ones inside wait
                            forever and the mesh hangs (no error, no
                            timeout). ``lax.scan`` and unrolled loops are
                            fine (trip counts are static); a predicate that
                            is *provably* uniform across the mesh (computed
                            from fully-replicated values) is a legitimate
                            suppression — pass ``ignore=("GC-J107",)`` at
                            that call site.
GC-J108  full-pool-dequant  a ``convert_element_type`` whose operand is the
                            WHOLE quantized KV page pool (int8/fp8 operand,
                            wide-float target, page-pool rank with the
                            pool's ``num_pages`` in its shape). Dequant
                            must run on the gathered pages (a few per
                            slot), never the pool: a full-pool convert
                            materializes a transient fp copy of the entire
                            cache, silently forfeiting the memory the
                            quantization bought — and it scales with pool
                            size, not batch, so it is invisible at toy
                            shapes and an OOM at serving shapes. Detected
                            in :func:`lint_decode_collectives` /
                            :func:`lint_decode_step` when the caller
                            supplies ``kv_pool_pages``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import core as jex_core
from jax.sharding import PartitionSpec as P

from .findings import Finding

__all__ = ["lint_fn", "lint_train_step", "lint_sharding_config",
           "lint_collective_divergence", "lint_decode_collectives",
           "lint_decode_step", "lint_dp_train_step", "repo_self_check"]

#: collective primitives whose presence/absence encodes the zero stage
_SCATTER_PRIMS = frozenset({"reduce_scatter"})
_REDUCE_PRIMS = frozenset({"psum", "reduce_scatter", "all_reduce"})

#: every primitive that is a cross-device rendezvous (GC-J107). "psum2" is
#: what lax.psum traces to inside shard_map on current JAX; "pbroadcast" is
#: deliberately absent — it is shard_map's varying->replicated *type* cast,
#: not communication, and appears inside branches as plumbing.
_RENDEZVOUS_PRIMS = frozenset({
    "psum", "psum2", "all_reduce", "reduce_scatter", "psum_scatter",
    "all_gather", "all_gather_invariant", "all_to_all", "ppermute",
    "pmax", "pmin", "pmean"})

#: control-flow primitives whose predicate/trip count is data-dependent
_DATA_DEP_CONTROL = frozenset({"cond", "while"})

#: below this, replication / double-buffering is noise, not a finding
DEFAULT_LARGE_BYTES = 1 << 20


def _norm_spec(spec) -> Tuple:
    """PartitionSpec/NamedSharding -> canonical tuple (trailing Nones
    stripped, so P('dp') == P('dp', None))."""
    if spec is None:
        return ()
    if hasattr(spec, "spec"):  # NamedSharding
        spec = spec.spec
    parts = tuple(spec)
    while parts and parts[-1] is None:
        parts = parts[:-1]
    return parts


def _sub_jaxprs(value) -> Iterable:
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _iter_eqns(jaxpr) -> Iterable:
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _aval_bytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * np.dtype(aval.dtype).itemsize


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _flat_specs(arg, spec) -> List[Optional[Tuple]]:
    """Per-leaf normalized specs for one argument pytree. ``spec`` may be
    None (unknown), one PartitionSpec (broadcast), or a matching pytree."""
    n = len(jax.tree.leaves(arg))
    if spec is None:
        return [None] * n
    if isinstance(spec, P) or hasattr(spec, "spec"):
        return [_norm_spec(spec)] * n
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, P))
    if len(leaves) != n:
        raise ValueError(
            f"in_specs entry has {len(leaves)} leaves for an argument "
            f"with {n}; pass one PartitionSpec or a matching pytree")
    return [_norm_spec(s) for s in leaves]


def _struct_like(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), np.dtype(x.dtype))
    return x


def lint_fn(fn: Callable, args: Sequence, *,
            in_specs: Optional[Sequence] = None,
            mesh=None,
            donate_argnums: Sequence[int] = (),
            name: Optional[str] = None,
            large_bytes: int = DEFAULT_LARGE_BYTES,
            check_x64: bool = True,
            ignore: Sequence[str] = ()) -> List[Finding]:
    """Lint one traceable function.

    Parameters
    ----------
    fn, args : the callable and its positional arguments — pytrees of
        arrays / ``ShapeDtypeStruct``. Traced abstractly; never executed.
    in_specs : per-argument declared placements (aligned with ``args``);
        each entry is None (unknown), a single ``PartitionSpec``, or a
        pytree of specs. Enables GC-J101/GC-J102.
    mesh : the mesh the specs refer to; replication findings only fire on
        a >1-device mesh.
    donate_argnums : argument indices the caller's jit donates — consumed
        by the GC-J105 check, exactly jit's convention.
    check_x64 : re-trace under ``jax.enable_x64`` for the
        GC-J103 promotion check (skipped automatically if any input is
        already 64-bit).
    """
    ignore = set(ignore)
    label = name or getattr(fn, "__name__", "fn")
    args = tuple(jax.tree.map(_struct_like, a) for a in args)
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr = closed.jaxpr
    findings: List[Finding] = []

    flat_leaves: List[Tuple[int, str, Any]] = []  # (argnum, path, leaf)
    for i, a in enumerate(args):
        for path, leaf in _leaf_paths(a):
            flat_leaves.append((i, path, leaf))
    flat_specs: List[Optional[Tuple]] = []
    for i, a in enumerate(args):
        spec = in_specs[i] if in_specs is not None else None
        flat_specs.extend(_flat_specs(a, spec))

    # GC-J101: sharding constraints that disagree with declared placement
    if "GC-J101" not in ignore and in_specs is not None:
        var_spec: Dict[Any, Tuple] = {}
        for var, spec in zip(jaxpr.invars, flat_specs):
            if spec is not None:
                var_spec[var] = spec
        for eqn in jaxpr.eqns:  # top-level only: invar identity is lost
            if eqn.primitive.name != "sharding_constraint":  # in sub-jaxprs
                continue
            operand = eqn.invars[0]
            new = _norm_spec(eqn.params.get("sharding"))
            old = var_spec.get(operand)
            if old is not None and old != new:
                findings.append(Finding(
                    "GC-J101",
                    f"{label}: tensor {operand.aval.str_short()} declared "
                    f"P{old} is constrained to P{new} — GSPMD reshards it "
                    f"(a collective) every call; align the constraint or "
                    f"the input sharding",
                    source="jaxpr_lint",
                    detail={"declared": old, "constrained": new}))
            for outvar in eqn.outvars:
                var_spec[outvar] = new

    # GC-J102: large replicated inputs on a real mesh
    if ("GC-J102" not in ignore and in_specs is not None
            and mesh is not None and getattr(mesh, "size", 1) > 1):
        for (argnum, path, leaf), spec in zip(flat_leaves, flat_specs):
            if spec != () or spec is None:
                continue
            nbytes = _aval_bytes(leaf)
            if nbytes >= large_bytes:
                findings.append(Finding(
                    "GC-J102",
                    f"{label}: input arg{argnum}{path} "
                    f"({tuple(leaf.shape)} {np.dtype(leaf.dtype).name}, "
                    f"{nbytes >> 20} MiB) is replicated over {mesh.size} "
                    f"devices — shard it or accept {mesh.size}x the HBM",
                    source="jaxpr_lint",
                    detail={"bytes": nbytes, "arg": argnum, "path": path}))

    # GC-J103: float64 appearing under x64 in an f32 program
    input_f64 = any(np.dtype(leaf.dtype) in (np.float64, np.complex128)
                    for _, _, leaf in flat_leaves)
    if "GC-J103" not in ignore and check_x64 and not input_f64:
        try:
            with jax.enable_x64(True):
                closed64 = jax.make_jaxpr(fn)(*args)
        except Exception:
            closed64 = None  # fn untraceable under x64: nothing to report
        if closed64 is not None:
            hits: List[str] = []
            for eqn in _iter_eqns(closed64.jaxpr):
                for var in eqn.outvars:
                    aval = getattr(var, "aval", None)
                    if aval is not None and getattr(aval, "dtype", None) is not None \
                            and np.dtype(aval.dtype) == np.float64:
                        hits.append(f"{eqn.primitive.name} -> "
                                    f"{aval.str_short()}")
                        break
            if hits:
                shown = "; ".join(hits[:3])
                more = f" (+{len(hits) - 3} more)" if len(hits) > 3 else ""
                findings.append(Finding(
                    "GC-J103",
                    f"{label}: float32 inputs produce float64 under x64 "
                    f"tracing — a Python/numpy double is on the hot path: "
                    f"{shown}{more}. Pin literals with jnp/np.float32",
                    source="jaxpr_lint", detail={"count": len(hits)}))

    # GC-J104: weakly-typed top-level outputs
    if "GC-J104" not in ignore:
        for idx, aval in enumerate(closed.out_avals):
            if getattr(aval, "weak_type", False):
                findings.append(Finding(
                    "GC-J104",
                    f"{label}: output {idx} ({aval.str_short()}) is weakly "
                    f"typed — a bare Python scalar dominates it and its "
                    f"final dtype depends on the caller; anchor it with an "
                    f"explicit dtype",
                    source="jaxpr_lint", detail={"output": idx}))

    # GC-J105: donation opportunities
    if "GC-J105" not in ignore:
        donate = set(donate_argnums)
        out_avals = [(tuple(a.shape), np.dtype(a.dtype))
                     for a in closed.out_avals]
        for i, a in enumerate(args):
            if i in donate:
                continue
            leaves = jax.tree.leaves(a)
            if not leaves:
                continue
            total = sum(_aval_bytes(l) for l in leaves)
            if total < large_bytes:
                continue
            need = [(tuple(l.shape), np.dtype(l.dtype)) for l in leaves]
            pool = list(out_avals)
            if all(_take(pool, item) for item in need):
                findings.append(Finding(
                    "GC-J105",
                    f"{label}: arg {i} ({total >> 20} MiB) matches the "
                    f"outputs aval-for-aval but is not donated — add "
                    f"donate_argnums=({i},) to reuse its buffers in place",
                    source="jaxpr_lint", detail={"arg": i, "bytes": total}))

    # GC-J107: collectives under data-dependent control flow (SPMD hang)
    if "GC-J107" not in ignore:
        findings.extend(_divergence_findings(jaxpr, label))
    return findings


def _take(pool: List, item) -> bool:
    try:
        pool.remove(item)
        return True
    except ValueError:
        return False


# ---------------------------------------------------------------------------
# GC-J107: collectives under data-dependent control flow
# ---------------------------------------------------------------------------


def _divergence_findings(jaxpr, label: str) -> List[Finding]:
    """One GC-J107 finding per cond/while eqn with a rendezvous collective
    anywhere beneath it (nested control flow reports at every level — each
    predicate on the way down is a place devices can disagree)."""
    findings: List[Finding] = []
    for eqn in _iter_eqns(jaxpr):
        kind = eqn.primitive.name
        if kind not in _DATA_DEP_CONTROL:
            continue
        hits = set()
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                for inner in _iter_eqns(sub):
                    if inner.primitive.name in _RENDEZVOUS_PRIMS:
                        hits.add(inner.primitive.name)
        if not hits:
            continue
        where = ("a lax.cond branch" if kind == "cond"
                 else "the body/condition of a lax.while_loop")
        findings.append(Finding(
            "GC-J107",
            f"{label}: {', '.join(sorted(hits))} inside {where} — a "
            f"collective is a rendezvous, and a predicate that differs "
            f"across devices sends some into it and some around it: the "
            f"mesh hangs. Hoist the collective out of the branch, or if "
            f"the predicate is provably uniform across the mesh, suppress "
            f"with ignore=('GC-J107',)",
            source="jaxpr_lint",
            detail={"control": kind, "collectives": sorted(hits)}))
    return findings


def lint_collective_divergence(fn: Callable, args: Sequence, *,
                               mesh=None, in_specs=None, out_specs=None,
                               name: Optional[str] = None,
                               ignore: Sequence[str] = ()) -> List[Finding]:
    """GC-J107 over one traceable function. With ``mesh``/``in_specs`` the
    function is traced under the same shard_map wrapper the caller compiles
    (axis-bound collectives only trace inside one)."""
    if "GC-J107" in set(ignore):
        return []
    label = name or getattr(fn, "__name__", "fn")
    args = tuple(jax.tree.map(_struct_like, a) for a in args)
    if mesh is not None and in_specs is not None:
        fn = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    closed = jax.make_jaxpr(fn)(*args)
    return _divergence_findings(closed.jaxpr, label)


# ---------------------------------------------------------------------------
# model-level entry points
# ---------------------------------------------------------------------------


def _model_structs(model, names: Sequence[str], batch: int):
    specs = model.input_specs()
    structs = []
    for n in names:
        key = n.split(":")[0]
        if key not in specs:
            raise KeyError(f"{key!r} is not a model input; inputs: "
                           f"{sorted(specs)}")
        shape, dtype = specs[key]
        shape = tuple(batch if d is None else int(d) for d in shape)
        structs.append(jax.ShapeDtypeStruct(shape, np.dtype(dtype)))
    return structs


def lint_train_step(model, input_name, label_name=None, optimizer="adam",
                    *, batch: int = 8, mesh=None,
                    params_spec=None, data_spec=None,
                    donate_state: bool = True,
                    ignore: Sequence[str] = (),
                    large_bytes: int = DEFAULT_LARGE_BYTES,
                    name: Optional[str] = None) -> List[Finding]:
    """Lint one optimizer step of ``model`` exactly as the trainer builds
    it (:func:`sparkflow_tpu.core.make_train_step`'s raw body): masked loss,
    optimizer update, parameter apply. ``optimizer`` is a registry name or
    an optax transformation. ``donate_state=True`` mirrors core's
    ``donate_argnums=(0, 1)`` — set False to re-check donation advice."""
    import optax

    from ..core import make_loss_fn, _step_body
    from ..optimizers import build_optimizer

    if isinstance(optimizer, str):
        opt_label, optimizer = optimizer, build_optimizer(optimizer, 0.01)
    else:
        opt_label = type(optimizer).__name__
    loss_fn = make_loss_fn(model, input_name, label_name)
    step = _step_body(loss_fn, optimizer)

    multi = isinstance(input_name, (list, tuple))
    names = list(input_name) if multi else [input_name]
    x_structs = _model_structs(model, names, batch)
    x = tuple(x_structs) if multi else x_structs[0]
    if label_name is not None:
        y = _model_structs(model, [label_name], batch)[0]
    else:
        y = jax.ShapeDtypeStruct((batch, 1), np.float32)  # ignored dummy
    mask = jax.ShapeDtypeStruct((batch,), np.float32)
    rng = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, rng)
    opt_state = jax.eval_shape(optimizer.init, params)

    in_specs = None
    if params_spec is not None or data_spec is not None:
        rows = data_spec if data_spec is not None else P()
        in_specs = (params_spec, params_spec,
                    rows, rows, rows, P())
    return lint_fn(
        step, (params, opt_state, x, y, mask, rng),
        in_specs=in_specs, mesh=mesh,
        donate_argnums=(0, 1) if donate_state else (),
        name=name or f"train_step[{getattr(model, 'name', type(model).__name__)}"
                     f"/{opt_label}]",
        large_bytes=large_bytes, ignore=ignore)


def lint_apply(model, input_name, output_name, *, batch: int = 8,
               mesh=None, params_spec=None, data_spec=None,
               ignore: Sequence[str] = (),
               large_bytes: int = DEFAULT_LARGE_BYTES,
               name: Optional[str] = None) -> List[Finding]:
    """Lint the inference path: ``apply(params, x) -> output_name``."""
    multi = isinstance(input_name, (list, tuple))
    names = list(input_name) if multi else [input_name]
    in_keys = [n.split(":")[0] for n in names]

    def predict(params, x):
        feeds = dict(zip(in_keys, tuple(x) if multi else (x,)))
        return model.apply(params, feeds, [output_name],
                           train=False)[output_name]

    x_structs = _model_structs(model, names, batch)
    x = tuple(x_structs) if multi else x_structs[0]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    in_specs = None
    if params_spec is not None or data_spec is not None:
        in_specs = (params_spec, data_spec)
    return lint_fn(predict, (params, x), in_specs=in_specs, mesh=mesh,
                   name=name or f"apply[{type(model).__name__}"
                                f"/{output_name}]",
                   large_bytes=large_bytes, ignore=ignore)


# ---------------------------------------------------------------------------
# GC-J106: declared ShardingConfig vs observed collectives
# ---------------------------------------------------------------------------


def lint_sharding_config(fn: Callable, args: Sequence, sharding, *,
                         name: Optional[str] = None,
                         ignore: Sequence[str] = ()) -> List[Finding]:
    """Check a train step's OBSERVED collectives against its declared
    :class:`~sparkflow_tpu.sharding.ShardingConfig` (GC-J106).

    The zero stage is a graph property: a stage>=1 step MUST contain a
    ``reduce_scatter`` (the gradient merge that makes the state shards
    sufficient), and a stage-0 step must NOT — tracing the step abstractly
    and walking every sub-jaxpr (shard_map bodies included) reads it off
    without executing a FLOP. A mismatch means the declared config and the
    compiled program disagree: memory budgets, checkpoint layouts and byte
    counts derived from the config are all wrong for what actually runs.
    """
    from ..sharding import as_sharding_config

    if "GC-J106" in set(ignore):
        return []
    cfg = as_sharding_config(sharding)
    label = name or getattr(fn, "__name__", "fn")
    args = tuple(jax.tree.map(_struct_like, a) for a in args)
    closed = jax.make_jaxpr(fn)(*args)
    prims = {eqn.primitive.name for eqn in _iter_eqns(closed.jaxpr)}
    scatters = sorted(prims & _SCATTER_PRIMS)
    reduces = sorted(prims & _REDUCE_PRIMS)
    findings: List[Finding] = []
    if cfg.zero_stage >= 1 and not scatters:
        detail = {"declared": cfg.describe(), "observed": reduces}
        if reduces:
            findings.append(Finding(
                "GC-J106",
                f"{label}: declared zero_stage={cfg.zero_stage} but the "
                f"step's gradient merge is {reduces} with NO reduce_scatter "
                f"— every device still receives the FULL gradient, so the "
                f"sharded optimizer state saves nothing at update time; "
                f"the step was built without the sharded update (check "
                f"that the config reached the step builder)",
                source="jaxpr_lint", detail=detail))
        else:
            findings.append(Finding(
                "GC-J106",
                f"{label}: declared zero_stage={cfg.zero_stage} but the "
                f"step contains no cross-device reduction at all — each "
                f"device trains an independent model copy on its shard "
                f"(divergent replicas, not data parallelism)",
                source="jaxpr_lint", detail=detail))
    elif cfg.zero_stage == 0 and scatters:
        findings.append(Finding(
            "GC-J106",
            f"{label}: declared zero_stage=0 (replicated update) but the "
            f"step runs {scatters} — the update IS sharded, and anything "
            f"trusting the declared config (checkpoint layout conversion, "
            f"memory budgets) is wrong for this program",
            source="jaxpr_lint",
            detail={"declared": cfg.describe(), "observed": scatters}))
    return findings


#: storage dtypes a quantized KV pool can hold (GC-J108 operand gate)
_QUANT_POOL_DTYPES = ("int8", "float8")


def _full_pool_dequant_findings(jaxpr, label: str,
                                kv_pool_pages: int) -> List[Finding]:
    """GC-J108: flag convert_element_type eqns that widen a whole quantized
    page pool to float. The page-gather shrinks the pages axis to a few
    pages per slot, so a wide convert still carrying ``kv_pool_pages`` in a
    rank>=4 operand can only be the un-gathered pool."""
    findings: List[Finding] = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        aval = eqn.invars[0].aval
        src = np.dtype(aval.dtype).name
        if not src.startswith(_QUANT_POOL_DTYPES):
            continue
        new = np.dtype(eqn.params.get("new_dtype"))
        if not (np.issubdtype(new, np.floating) and new.itemsize >= 2):
            continue
        shape = tuple(getattr(aval, "shape", ()))
        if len(shape) < 4 or kv_pool_pages not in shape:
            continue
        findings.append(Finding(
            "GC-J108",
            f"{label}: convert_element_type({src} -> {new.name}) over a "
            f"{shape} operand — the whole quantized KV pool "
            f"(num_pages={kv_pool_pages}) is being dequantized before the "
            f"page gather. This materializes a full-precision transient "
            f"copy of the entire cache (scales with pool size, not batch), "
            f"forfeiting the memory quantization bought; gather the pages "
            f"first and dequantize the gathered rows",
            source="jaxpr_lint",
            detail={"operand_shape": list(shape), "operand_dtype": src,
                    "new_dtype": new.name,
                    "kv_pool_pages": kv_pool_pages}))
    return findings


def lint_decode_collectives(fn: Callable, args: Sequence, *,
                            mesh=None, in_specs=None, out_specs=None,
                            tp_axis: Optional[str] = None,
                            ep_axis: Optional[str] = None,
                            pp_axis: Optional[str] = None,
                            kv_pool_pages: Optional[int] = None,
                            name: Optional[str] = None,
                            ignore: Sequence[str] = ()) -> List[Finding]:
    """GC-J106 + GC-J107 (+ GC-J108 when ``kv_pool_pages`` is given) over
    one decode-plane executable body.

    ``fn`` is the per-shard step function; with ``mesh``/``in_specs`` given
    it is traced under the same shard_map wrapper the engine compiles
    (axis-bound psums only trace inside one). The check is direction-exact:

    - a declared ``tp_axis``/``ep_axis`` must appear among the axes of the
      step's reduction collectives — that psum IS the rejoin after the
      O-projection / MoE combine, and a step without it ships per-shard
      partial activations into the logits;
    - a declared ``pp_axis`` must appear among the axes of the step's
      ``ppermute`` handoffs — the ring permute IS the stage-to-stage
      activation transfer, and a depth-sharded step without it means every
      stage decodes its local layers in isolation; the pp axis also joins
      the declared reduce axes (the staged step broadcasts the last stage's
      sampled token with a select-psum);
    - an axis NOT declared must not appear — an undeclared collective means
      the compiled program and the config everyone budgets from disagree.

    With ``kv_pool_pages`` given (a quantized-pool engine's total page
    count), the same jaxpr is additionally scanned for GC-J108
    ``full-pool-dequant``: any wide-float ``convert_element_type`` whose
    operand is the whole quantized pool.
    """
    ignore = set(ignore)
    check_j108 = kv_pool_pages is not None and "GC-J108" not in ignore
    if {"GC-J106", "GC-J107"} <= ignore and not check_j108:
        return []
    label = name or getattr(fn, "__name__", "decode_step")
    args = tuple(jax.tree.map(_struct_like, a) for a in args)
    if mesh is not None and in_specs is not None:
        fn = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    closed = jax.make_jaxpr(fn)(*args)
    divergence: List[Finding] = []
    if "GC-J107" not in ignore:
        divergence = _divergence_findings(closed.jaxpr, label)
    if check_j108:
        divergence = divergence + _full_pool_dequant_findings(
            closed.jaxpr, label, int(kv_pool_pages))
    if "GC-J106" in ignore:
        return divergence
    observed: set = set()
    permuted: set = set()
    for eqn in _iter_eqns(closed.jaxpr):
        is_reduce = eqn.primitive.name in _REDUCE_PRIMS
        if not is_reduce and eqn.primitive.name != "ppermute":
            continue
        axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        (observed if is_reduce else permuted).update(
            a for a in axes if isinstance(a, str))
    findings: List[Finding] = []
    detail = {"observed_axes": sorted(observed),
              "observed_ppermute_axes": sorted(permuted),
              "declared": {"tp_axis": tp_axis, "ep_axis": ep_axis,
                           "pp_axis": pp_axis}}
    for role, axis in (("tp_axis", tp_axis), ("ep_axis", ep_axis)):
        if axis is not None and axis not in observed:
            what = ("O-projection/MLP rejoin" if role == "tp_axis"
                    else "expert-combine rejoin")
            findings.append(Finding(
                "GC-J106",
                f"{label}: declared {role}={axis!r} but the decode step "
                f"contains no psum over it — the {what} is missing, so "
                f"every shard keeps its partial activations and the "
                f"served logits are garbage (check the axis reached the "
                f"model's decode_step)",
                source="jaxpr_lint", detail=detail))
    if pp_axis is not None and pp_axis not in permuted:
        findings.append(Finding(
            "GC-J106",
            f"{label}: declared pp_axis={pp_axis!r} but the decode step "
            f"contains no ppermute over it — the stage-to-stage activation "
            f"handoff is missing, so each stage runs only its local layers "
            f"and the served logits never saw the full depth (check the "
            f"axis reached the staged step builder)",
            source="jaxpr_lint", detail=detail))
    extra_perm = permuted - ({pp_axis} if pp_axis is not None else set())
    if extra_perm:
        findings.append(Finding(
            "GC-J106",
            f"{label}: the decode step runs ppermute over "
            f"{sorted(extra_perm)} without a declared pp_axis — the "
            f"program is depth-sharded but the config everyone budgets "
            f"from says it is not",
            source="jaxpr_lint", detail=detail))
    # pp joins the declared reduce axes: the staged step's exit broadcast
    # (select-psum of the last stage's token/logits) is over pp_axis
    declared = {a for a in (tp_axis, ep_axis, pp_axis) if a is not None}
    extra = observed - declared
    if extra:
        findings.append(Finding(
            "GC-J106",
            f"{label}: the decode step runs reduction collectives over "
            f"{sorted(extra)} that the engine's config does not declare — "
            f"per-token latency and per-device memory derived from the "
            f"config are wrong for this program",
            source="jaxpr_lint", detail=detail))
    return findings + divergence


def lint_decode_step(engine, *, name: Optional[str] = None,
                     ignore: Sequence[str] = ()) -> List[Finding]:
    """GC-J106 for a live :class:`~sparkflow_tpu.serving.decode.DecodeEngine`:
    trace its steady-state decode step exactly as warmup compiles it (same
    shard_map wrapper and specs when model-parallel) and check the observed
    collectives against the tp/ep/pp axes the engine declares (a pp engine
    must show the ppermute stage handoff). A quantized-pool engine
    (``kv_quant=``) is additionally scanned for GC-J108 full-pool-dequant.
    Zero findings is the repo gate; both planted-defect directions live in
    ``tests/test_decode.py`` / ``tests/test_analysis.py``."""
    import jax.numpy as jnp
    B, maxp = engine.num_slots, engine.max_pages_per_slot
    i32 = jnp.int32
    args = (engine._param_struct(), engine._pool_struct(),
            engine._pool_struct(),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B,), i32),
            jax.ShapeDtypeStruct((B, maxp), i32),
            jax.ShapeDtypeStruct((B, 2), jnp.uint32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
            jax.ShapeDtypeStruct((B,), i32))
    mesh = in_specs = out_specs = None
    if getattr(engine, "_sharded", False):
        psp, pls, R = engine._param_specs, engine._pool_spec, P()
        mesh = engine.mesh
        in_specs = (psp, pls, pls, R, R, R, R, R, R)
        out_specs = (R, pls, pls, R)
    return lint_decode_collectives(
        engine._decode_fn, args, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, tp_axis=engine._tp_axis,
        ep_axis=engine._ep_axis, pp_axis=engine._pp_axis,
        kv_pool_pages=(engine.kv.num_pages
                       if getattr(engine, "_quantized", False) else None),
        name=name or (f"decode_step[tp={engine._tp},ep={engine._ep},"
                      f"pp={engine._pp}]"),
        ignore=ignore)


def lint_dp_train_step(model, optimizer="adam", *, mesh, sharding,
                       input_name="x:0", label_name="y:0", batch: int = 8,
                       ignore: Sequence[str] = (),
                       name: Optional[str] = None) -> List[Finding]:
    """GC-J106 over the unified dp step exactly as the trainer builds it:
    constructs :func:`~sparkflow_tpu.parallel.dp.make_dp_train_step`'s raw
    stepper for ``sharding`` and lints its jaxpr against the same config.
    The repo gate traces every zero stage this way; a planted mismatch
    (declared stage N, built stage M) is the test fixture."""
    from ..optimizers import build_optimizer
    from ..optimizers_sharded import sharded_update, shard_zero3_params
    from ..parallel.dp import make_dp_train_step
    from ..sharding import as_sharding_config

    cfg = as_sharding_config(sharding)
    if isinstance(optimizer, str):
        opt_label, optimizer = optimizer, build_optimizer(optimizer, 0.01)
    else:
        opt_label = type(optimizer).__name__
    step = make_dp_train_step(model, optimizer, mesh, input_name, label_name,
                              sharding=cfg, _raw=True)
    multi = isinstance(input_name, (list, tuple))
    names = list(input_name) if multi else [input_name]
    x_structs = _model_structs(model, names, batch)
    x = tuple(x_structs) if multi else x_structs[0]
    if label_name is not None:
        y = _model_structs(model, [label_name], batch)[0]
    else:
        y = jax.ShapeDtypeStruct((batch, 1), np.float32)
    mask = jax.ShapeDtypeStruct((batch,), np.float32)
    rng = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = cfg.dp_size(mesh)
    if cfg.zero_stage >= 1:
        opt_state = jax.eval_shape(
            sharded_update(optimizer, n, cfg.data_axis).init, params)
        if cfg.zero_stage >= 3:
            params = jax.eval_shape(lambda p: shard_zero3_params(p, n),
                                    params)
    else:
        opt_state = jax.eval_shape(optimizer.init, params)
    return lint_sharding_config(
        step, (params, opt_state, x, y, mask, rng), cfg,
        name=name or f"dp_train_step[{getattr(model, 'name', type(model).__name__)}"
                     f"/{opt_label}/zero{cfg.zero_stage}]",
        ignore=ignore)


# ---------------------------------------------------------------------------
# repo self-check: the presets x the optimizer registry
# ---------------------------------------------------------------------------


def repo_self_check(ignore: Sequence[str] = ()) -> List[Finding]:
    """Trace-lint the repo's own model presets and optimizer registry —
    the hot paths every example and test trains. Any finding here is a
    repo bug; ``tests/test_analysis.py`` pins this to zero."""
    from ..models import model_from_json, presets
    from ..optimizers import AVAILABLE_OPTIMIZERS

    findings: List[Finding] = []
    mlp = model_from_json(presets.mlp(16, 4, hidden=(8,)))
    # every registry optimizer across the mlp step: this is where Python
    # scalar literals (lr, eps, decay math) would promote dtypes
    for opt in AVAILABLE_OPTIMIZERS:
        findings.extend(lint_train_step(
            mlp, "x:0", "y:0", opt, batch=4, ignore=ignore,
            name=f"train_step[mlp/{opt}]"))
    cnn = model_from_json(presets.cnn(side=12, channels=1, num_classes=4))
    findings.extend(lint_train_step(cnn, "x:0", "y:0", "adam", batch=4,
                                    ignore=ignore,
                                    name="train_step[cnn/adam]"))
    ae = model_from_json(presets.autoencoder(input_dim=12, widths=(8, 4, 8)))
    findings.extend(lint_train_step(ae, "x:0", None, "adam", batch=4,
                                    ignore=ignore,
                                    name="train_step[autoencoder/adam]"))
    findings.extend(lint_apply(mlp, "x:0", "out:0", batch=4, ignore=ignore,
                               name="apply[mlp/out]"))
    return findings
