"""``utils.tracing.STEP_PARTS``: one list of a train step's parts. Every
``dot_general``, ``pallas_call``, gather, scatter and reduction that a model of
the four decoder families and ``core._step_body`` write into a step lies under
exactly one part's ``jax.named_scope``, forward and backward; no part lies
inside another; and no file of the program spells a scope the list does not
know. A step is built as ``Trainer`` builds it, traced to a jaxpr at toy sizes
and every equation's name stack is walked, into the bodies of ``scan``,
``while``, ``checkpoint``, ``custom_vjp`` and ``pjit``."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core
from jax._src import source_info_util

from sparkflow_tpu import core
from sparkflow_tpu.models import build_registry_spec
from sparkflow_tpu.utils.tracing import STEP_GROUPS, STEP_PARTS, STEP_SUBPARTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MOE = dict(hidden=32, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
           num_experts=8, experts_per_token=2, expert_dim=16,
           experts_held=[0, 4], rope_theta=1e4)
FAMILIES = {
    "transformer_lm": (dict(vocab_size=48, hidden=16, num_layers=2,
                            num_heads=2, mlp_dim=32, max_len=128), 128, 48),
    "sparse_moe_lm": (dict(MOE, vocab_size=48, indexer_heads=2, indexer_dim=8,
                           indexer_topk=8, indexer_block=16, max_len=32),
                      32, 48),
    "block_diffusion_lm": (dict(MOE, vocab_size=96, vocab_held=[0, 48],
                                mask_token_id=90, block_length=4, max_len=64),
                           64, 48),
    "looped_lm": (dict(vocab_size=96, hidden=32, num_layers=2, num_heads=2,
                       head_dim=16, mlp_dim=64, passes=3, rope_theta=1e4,
                       max_len=128, head_block=64), 128, 96),
}
# the parts each family's step has, all of them forward and backward but
# ``batch`` (the counters have no gradient) and ``optimizer``
PARTS_OF = {
    "transformer_lm": {"embed", "attn_proj", "flash_attention", "mlp",
                       "lm_head"},
    "sparse_moe_lm": {"embed", "attn_proj", "sparse_attention", "indexer",
                      "router", "experts", "lm_head"},
    "block_diffusion_lm": {"embed", "attn_proj", "block_attention", "router",
                           "experts", "lm_head"},
    "looped_lm": {"embed", "attn_proj", "flash_attention", "mlp",
                  "loop_head"},
}


def checked(primitive: str) -> bool:
    """The kinds of equation that do a step's work: matrix products, kernels,
    gathers and scatters, reductions."""
    return (primitive in ("dot_general", "pallas_call", "argmax", "argmin",
                          "cumsum", "cumlogsumexp", "cummax", "cumprod")
            or primitive.startswith(("gather", "scatter"))
            or (primitive.startswith("reduce_")
                and primitive != "reduce_precision"))


def equations(jaxpr, outer=((), ())):
    """``(scope names, transform names, equation)`` of every equation of
    ``jaxpr`` and of the jaxprs its equations hold (not of a kernel's body),
    the names of the equations around it first."""
    for eqn in jaxpr.eqns:
        stack = eqn.source_info.name_stack.stack
        scopes = outer[0] + tuple(
            e.name for e in stack if isinstance(e, source_info_util.Scope))
        transforms = outer[1] + tuple(
            e.name for e in stack
            if isinstance(e, source_info_util.Transform))
        yield scopes, transforms, eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax_core.jaxprs_in_params(eqn.params):
                yield from equations(sub, (scopes, transforms))


def violations(jaxpr) -> list:
    """The working equations of ``jaxpr`` that lie under no part or under
    two, as text. None is skipped: the allow-list is empty. What may lie
    outside every part is no working equation (``checked``): JAX's sums of
    cotangents over rows and passes (``add_any``), broadcasts, the loops'
    slices."""
    out = []
    for scopes, _, eqn in equations(jaxpr):
        prim = eqn.primitive.name
        parts = sorted({n for n in scopes if n in STEP_PARTS})
        if not checked(prim) or len(parts) == 1:
            continue
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        wrote = frame.function_name.split(".")[-1] if frame else ""
        out.append(f"{prim} in {wrote}: under {parts or 'no part'} "
                   f"({'/'.join(scopes)})")
    return out


def step_jaxpr(family):
    """One step of the family's toy model as ``Trainer`` builds it (its
    model, its optimizer, the loss with the model's counters through
    ``core._step_body``), on two rows of ids fed as floats."""
    from sparkflow_tpu.trainer import Trainer

    kw, length, vocab = FAMILIES[family]
    trainer = Trainer(build_registry_spec(family, **kw), "input_ids", None,
                      optimizer="adam", learning_rate=3e-4,
                      mini_batch_size=2, iters=1, seed=1)
    loss_fn = core.make_loss_fn(trainer.model, trainer.input_name,
                                trainer.label_name, with_metrics=True)
    params = trainer.model.init(jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, vocab, (2, length))
    x = jnp.asarray(ids, jnp.float32)            # Trainer.fit's feed type
    return jax.make_jaxpr(core._step_body(loss_fn, trainer.optimizer))(
        params, trainer.optimizer.init(params), x, None,
        jnp.ones(x.shape[0]), jax.random.PRNGKey(1)).jaxpr


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def step(request):
    return request.param, step_jaxpr(request.param)


def test_every_working_equation_lies_under_exactly_one_part(step):
    assert violations(step[1]) == []


def test_the_parts_of_a_family_are_there_forward_and_backward(step):
    family, jaxpr = step
    working, every = set(), set()
    for scopes, transforms, eqn in equations(jaxpr):
        here = {(n, "transpose" in transforms) for n in scopes
                if n in STEP_PARTS}
        every |= here
        if checked(eqn.primitive.name):
            working |= here
    both = {(p, back) for p in PARTS_OF[family] for back in (False, True)}
    assert both <= working
    # Adam's update is elementwise: no working equation, and no gradient
    assert {p for p, _ in every} == PARTS_OF[family] | {"batch", "optimizer"}
    assert ("optimizer", True) not in every


@pytest.mark.parametrize("kernel", ["head_rotary_fwd", "head_rotary_bwd"])
def test_the_heads_kernels_lie_under_attn_proj_and_no_second_part(step,
                                                                  kernel):
    """``q`` and ``k`` on their way to the attention kernels
    (``ops/head_rotary.py``) are ``attn_proj``'s in the three families that
    rotate their heads: the forward kernel in the forward pass and, made
    again, inside the backward; its transpose in the backward alone. The
    GPT-2 family has learned positions and neither kernel."""
    family, jaxpr = step
    found = [(scopes, "transpose" in transforms)
             for scopes, transforms, eqn in equations(jaxpr)
             if eqn.primitive.name == "pallas_call"
             and eqn.params["name"] == kernel]
    if family == "transformer_lm":
        assert found == []
        return
    assert found and all(
        [n for n in scopes if n in STEP_PARTS] == ["attn_proj"]
        for scopes, _ in found), found
    backward = {back for _, back in found}
    assert backward == ({True} if kernel.endswith("bwd") else {False, True})


def test_a_subpart_lies_inside_its_part_and_nowhere_else(step):
    for scopes, _, eqn in equations(step[1]):
        for name in set(scopes) & set(STEP_SUBPARTS):
            assert STEP_SUBPARTS[name] in scopes, (name, scopes)


# -- the rule itself, on programs that break it -------------------------------


def _scoped(body):
    def step(x):
        with jax.named_scope("loss"):
            return jax.value_and_grad(body)(x)
    return jax.make_jaxpr(step)(jnp.ones((4, 4))).jaxpr


def test_a_part_inside_another_part_breaks_the_rule():
    def body(x):
        with jax.named_scope("attn_proj"):
            with jax.named_scope("mlp"):
                return jnp.sum(x @ x)

    found = violations(_scoped(body))
    assert found and all("['attn_proj', 'mlp']" in v for v in found)
    assert any(v.startswith("dot_general") for v in found)


def test_a_product_outside_every_part_breaks_the_rule():
    def body(x):
        with jax.named_scope("mlp"):
            y = x @ x
        return jnp.sum(y @ x)            # under ``loss`` and nothing else

    found = violations(_scoped(body))
    assert found and all("no part" in v for v in found)
    # the product under ``mlp`` and its two transposes are in order
    assert sum(v.startswith("dot_general") for v in found) == 3


def test_a_group_around_parts_breaks_nothing():
    def body(x):
        with jax.named_scope("attention"):
            with jax.named_scope("attn_proj"):
                y = x @ x
            with jax.named_scope("flash_attention"):
                return jnp.sum(y @ x)

    assert violations(_scoped(body)) == []


@pytest.mark.parametrize("primitive, is_checked", [
    ("dot_general", True), ("pallas_call", True), ("gather", True),
    ("scatter-add", True), ("scatter_add", True), ("reduce_sum", True),
    ("reduce_max", True), ("argmax", True), ("cumsum", True),
    ("reduce_precision", False), ("add_any", False), ("mul", False),
    ("dynamic_slice", False), ("broadcast_in_dim", False)])
def test_what_counts_as_a_working_equation(primitive, is_checked):
    assert checked(primitive) is is_checked


# -- the list and the program's literals --------------------------------------


def spelled_scopes() -> dict:
    """``{scope: file}`` of every ``jax.named_scope("...")`` literal under
    ``sparkflow_tpu/models``, in ``core.py`` and in ``ops/grouped_matmul.py``
    and of every ``jax.named_scope(`` that is no literal (``None: file``)."""
    files = [os.path.join("models", n) for n in sorted(os.listdir(
        os.path.join(ROOT, "sparkflow_tpu", "models"))) if n.endswith(".py")]
    spelled = {}
    for name in files + ["core.py", os.path.join("ops", "grouped_matmul.py")]:
        with open(os.path.join(ROOT, "sparkflow_tpu", name)) as f:
            text = f.read()
        for arg in re.findall(r"jax\.named_scope\(\s*([^)]*)\)", text):
            literal = re.fullmatch(r"[\"']([^\"']+)[\"']", arg.strip())
            spelled.setdefault(literal.group(1) if literal else None, name)
    return spelled


def test_every_scope_the_program_spells_is_a_name_of_the_list():
    known = set(STEP_PARTS) | set(STEP_GROUPS) | set(STEP_SUBPARTS)
    spelled = spelled_scopes()
    assert None not in spelled, "a scope's name has to be a literal"
    assert {s: f for s, f in spelled.items() if s not in known} == {}
    assert known <= set(spelled)         # and every name of the list is in use


def test_the_list_names_nothing_twice_and_a_subparts_part_is_a_part():
    names = list(STEP_PARTS) + list(STEP_GROUPS) + list(STEP_SUBPARTS)
    assert len(names) == len(set(names))
    assert set(STEP_SUBPARTS.values()) <= set(STEP_PARTS)
    assert isinstance(STEP_PARTS, tuple) and isinstance(STEP_GROUPS, tuple)
    # a reader splits an operation's path at ``/``, ``(`` and ``)``
    assert all(re.fullmatch(r"[a-z_]+", n) for n in names)


def test_the_program_reads_the_list_nowhere():
    """The scopes are literals: importing the package or building a step
    imports and walks nothing for them."""
    reads = re.compile(r"\bSTEP_(PARTS|GROUPS|SUBPARTS)\b")
    for folder, _, files in os.walk(os.path.join(ROOT, "sparkflow_tpu")):
        for name in files:
            if name.endswith(".py") and name != "tracing.py":
                with open(os.path.join(folder, name)) as f:
                    code = re.sub(r"``[^`]*``", "", f.read())   # not the docs
                assert not reads.search(code), name
