"""The main path's kernels, compiled for a TPU v5e that is described, not
attached (the TPU compiler is installed; nothing runs).

Interpret mode accepts kernels the chip's compiler refuses — a batched dot
with no free left dimension, a block that breaks the (8, 128) tiling, a
working set past VMEM — so each kernel entry point is lowered here at real
widths with ``interpret=False`` and must hold a ``tpu_custom_call``. The
paged kernels' gate (``PAGED_BLOCK_LIMIT``) is held from both sides: every
layout family below it compiles, and the compiler refuses what lies past it.

One file, and the topology is described inside a fixture: only one process
may load the TPU library, and pytest-xdist workers each import every file.
"""

import base64
import os
import re
import struct

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from sparkflow_tpu.ops import attention as A


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def _custom_calls(text):
    """Instruction names of the compiled text's pallas kernels."""
    return re.findall(r"^\s*(?:ROOT\s+)?%?(\S+) = .*\bcustom-call\(.*"
                      r"custom_call_target=\"tpu_custom_call\"", text, re.M)


def _flash_structs(sh, b, h, s, d, dtype, mask):
    q = jax.ShapeDtypeStruct((b, h, s, d), dtype, sharding=sh)
    m = jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=sh)
    return (q, q, q) + ((m,) if mask else ())


# GPT-2 small's training shape (B8 H12 S1024 D64, bf16), the BERT-ish f32
# shape that once failed the (8, 128) tile check on the row statistics, and
# the longest context the kernel is offered (one row of 32k)
FLASH_SHAPES = [(8, 12, 1024, 64, jnp.bfloat16), (4, 12, 512, 64, jnp.float32),
                (1, 8, 32768, 64, jnp.bfloat16)]


@pytest.mark.parametrize("mask", [False, True], ids=["nomask", "kv_mask"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("b,h,s,d,dtype", FLASH_SHAPES,
                         ids=["gpt2-small", "bert-512-f32", "long-32k"])
def test_flash_lowers_on_tpu(one_chip, b, h, s, d, dtype, causal, mask):
    """Forward, and forward+backward, through the pallas kernels."""
    structs = _flash_structs(one_chip, b, h, s, d, dtype, mask)

    def fwd(q, k, v, *m):
        return A.flash_attention(q, k, v, causal=causal, interpret=False,
                                 kv_mask=m[0] if m else None)

    def fwd_bwd(q, k, v, *m):
        return jax.grad(lambda q_, k_, v_: fwd(q_, k_, v_, *m)
                        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    assert "tpu_custom_call" in _compile(fwd, *structs)
    assert A.last_attention_path() == "pallas"
    # the forward again, and the fused backward kernel or, where a head's dQ
    # is past its VMEM budget (the 32k row), the dq and dk/dv kernels
    fused = A._bwd_is_fused(s, d, dtype)
    assert fused == (s < 32768)
    assert (_compile(fwd_bwd, *structs).count("tpu_custom_call")
            >= (2 if fused else 3))


# one backward pass of the two dense cells: train-gpt2m (4 rows of 16 heads of
# 64 over 1024 keys) and train-ouro-seq4k (2 rows of 16 heads of 128 over 4096)
@pytest.mark.parametrize("b,h,s,d", [(4, 16, 1024, 64), (2, 16, 4096, 128)],
                         ids=["train-gpt2m", "train-ouro-seq4k"])
def test_fused_flash_backward_lowers_at_the_cells_shapes(one_chip, b, h, s, d):
    """``flash_bwd_dqkv`` compiles for the chip at the cells' shapes (the
    head's whole dQ in VMEM beside the 512 x 512 tiles), under a name that
    holds ``flash_bwd_`` for the benchmark's reader, and takes the place of
    both ``flash_bwd_dq`` and ``flash_bwd_dkv``."""
    def fwd_bwd(q, k, v):
        return jax.grad(lambda *qkv: A.flash_attention(
            *qkv, causal=True, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    with A.record_attention_paths() as paths:
        text = _compile(fwd_bwd, *_flash_structs(one_chip, b, h, s, d,
                                                 jnp.bfloat16, False))
    assert paths == ["flash_attention:pallas", "flash_attention_bwd:fused"]
    calls = _custom_calls(text)
    assert len(calls) == 2 and any("flash_fwd" in c for c in calls), calls
    assert any("flash_bwd_dqkv" in c for c in calls), calls


def _paged_structs(sh, h, d, page, pool_dtype, num_q, q_dtype=jnp.bfloat16,
                   slots=4, max_pages=8, num_pages=16):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    q = s((slots, h, d) if num_q is None else (slots, h, num_q, d), q_dtype)
    pool = s((num_pages, page, h, d), pool_dtype)
    out = [q, pool, pool, s((slots, max_pages), jnp.int32),
           s((slots,), jnp.int32)]
    if jnp.dtype(pool_dtype).itemsize == 1:      # int8 / fp8: scaled pool
        scales = s((num_pages, h), jnp.float32)
        out += [scales, scales]
    return out


def _paged_fn(num_q):
    kernel = A.paged_attention if num_q is None else A.paged_attention_verify

    def fn(q, k, v, table, n, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return kernel(q, k, v, table, n, interpret=False, **kw)

    return fn


# (heads, head_dim, page): every GPT-2 size (12/16/20/25 heads of 64),
# Cerebras-GPT 1.3B (16 x 128), the layout the old gate admitted (8k x 128),
# a tp-split remainder (3 heads), the toy layouts of the smokes, and the
# largest blocks the gate admits (both pad to exactly PAGED_BLOCK_LIMIT)
PAGED_LAYOUTS = [(12, 64, 16), (16, 64, 16), (20, 64, 16), (25, 64, 16),
                 (16, 128, 16), (32, 128, 16), (3, 64, 16), (4, 8, 8),
                 (12, 64, 128), (32, 128, 64)]
POOL_DTYPES = [jnp.bfloat16, jnp.float32, jnp.int8, jnp.float8_e4m3fn]
# None = the single-token decode kernel; 5 = spec_k 4 as it comes (no S % 8
# rule), 8 = the old gate's width, 1 = the degenerate verify
NUM_Q = [None, 1, 5, 8]


@pytest.mark.parametrize("num_q", NUM_Q,
                         ids=lambda n: "decode" if n is None else f"verify{n}")
@pytest.mark.parametrize("pool_dtype", POOL_DTYPES,
                         ids=lambda d: jnp.dtype(d).name)
def test_paged_kernels_lower_on_tpu(one_chip, pool_dtype, num_q):
    """Every layout family the gate admits compiles to the Mosaic kernel."""
    for h, d, page in PAGED_LAYOUTS:
        assert A._paged_block_rule(page, h, d) is None, (h, d, page)
        text = _compile(_paged_fn(num_q),
                        *_paged_structs(one_chip, h, d, page, pool_dtype,
                                        num_q))
        assert "tpu_custom_call" in text, (h, d, page)
        assert A.last_attention_path() == "pallas", (h, d, page)


@pytest.mark.parametrize("num_q", [None, 5],
                         ids=["decode", "verify5"])
def test_paged_gate_is_the_compilers(one_chip, num_q):
    """Past ``PAGED_BLOCK_LIMIT`` the gate sends the layout to the reference
    (no custom call in the program), and it is not being timid: handed the
    same layout with the gate lifted, the compiler runs out of VMEM."""
    h, d, page = 64, 128, 256            # 8x the limit
    assert A._paged_block_rule(page, h, d) is not None
    structs = _paged_structs(one_chip, h, d, page, jnp.bfloat16, num_q)
    assert "tpu_custom_call" not in _compile(_paged_fn(num_q), *structs)
    assert A.last_attention_path() == "reference"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(A, "PAGED_BLOCK_LIMIT", 1 << 30)
        with pytest.raises(Exception, match="(?i)vmem|RESOURCE_EXHAUSTED"):
            _compile(_paged_fn(num_q), *structs)


# -- the kernels' names -------------------------------------------------------


@pytest.fixture(scope="module")
def kernel_texts(one_chip):
    """Compiled text of flash forward+backward (GPT-2 small's shape, whose
    backward is the fused kernel, and a 32k row, whose backward is the dq and
    dkv kernels) and of both paged kernels."""
    def fwd_bwd(q, k, v):
        return jax.grad(lambda *qkv: A.flash_attention(
            *qkv, causal=True, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    flash, long = (_compile(fwd_bwd, *_flash_structs(one_chip, *shape,
                                                     jnp.bfloat16, False))
                   for shape in ((8, 12, 1024, 64), (1, 8, 32768, 64)))
    paged = [_compile(_paged_fn(num_q), *_paged_structs(
        one_chip, 12, 64, 16, jnp.bfloat16, num_q)) for num_q in (None, 5)]
    return {"flash_fwd": flash, "flash_bwd_dqkv": flash,
            "flash_bwd_dq_": long, "flash_bwd_dkv": long,
            "paged_decode": paged[0], "paged_verify": paged[1]}


@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dqkv",
                                  "flash_bwd_dq_", "flash_bwd_dkv",
                                  "paged_decode", "paged_verify"])
def test_kernels_keep_their_names(kernel_texts, name):
    """Each ``pallas_call`` carries a ``name=``, and the compiler keeps it
    inside the custom call's instruction name, wrapped in the transforms
    around it (``%jvp_flash_fwd_.1``): a trace's reader matches by
    *contains* (``chipbench/trace_reads.py``). Unnamed, the flash kernels
    were ``jvp__.N`` / ``transpose_jvp___.N``. (``flash_bwd_dq_``, with the
    wrapper's underscore, is the dq kernel and not ``flash_bwd_dqkv``.)"""
    calls = _custom_calls(kernel_texts[name])
    assert calls and all(re.search(r"flash_|paged_", c) for c in calls), calls
    assert any(name in c for c in calls), calls


# -- the selected-key attention and the grouped expert product ----------------


@pytest.fixture(scope="module")
def sparse_texts(one_chip):
    """Compiled text of the kernels ``sparse_moe_lm`` runs, at the widths of
    ``chipbench/configs/keye-vl2-30b-a3b-ep8.json``: one row of 8192 tokens,
    32 query heads over 4 KV heads of 128 (the attention's backward is the
    one kernel there; the dq and dkv pair at a row of 32 768, past the fused
    kernel's VMEM budget); 16 experts of 2048 x 768 over the worst case's
    rows (69 632), and the tokens' movement into and out of those rows."""
    from sparkflow_tpu.ops import grouped_matmul as G
    from sparkflow_tpu.ops import sparse_attention as S

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    s = 8192
    q = sd((1, 32, s, 128), jnp.bfloat16)
    kv = sd((1, 4, s, 128), jnp.bfloat16)
    mask = sd((1, s, s), jnp.int8)

    def attend(q, k, v, mask):
        def loss(q, k, v):
            out, lse = S.selected_attention(q, k, v, mask, interpret=False)
            return out.astype(jnp.float32).sum(), lse
        (_, lse), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return grads, S.selected_probs(q, k, lse, mask, interpret=False)

    with A.record_attention_paths() as paths:
        attention = _compile(attend, q, kv, kv, mask)
        long = 4 * s
        pair = _compile(
            lambda q, k, v, mask: jax.grad(lambda q, k, v: S.selected_attention(
                q, k, v, mask, interpret=False)[0].astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v),
            sd((1, 32, long, 128), jnp.bfloat16),
            sd((1, 4, long, 128), jnp.bfloat16),
            sd((1, 4, long, 128), jnp.bfloat16), sd((1, long, long), jnp.int8))
    assert paths == ["sparse_attention_fwd:512x1024",
                     "sparse_attention_bwd:fused",
                     "sparse_attention_fwd:512x1024",
                     "sparse_attention_bwd:split"]
    rows = G.rows_bound(s, 8, 16)
    x = sd((rows, 2048), jnp.bfloat16)
    w = sd((16, 2048, 768), jnp.bfloat16)
    tiles = sd((rows // G.TILE,), jnp.int32)
    used = sd((1,), jnp.int32)

    def experts(x, w, tiles, used):
        return jax.grad(lambda x, w: G.grouped_matmul(
            x, w, tiles, used, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1))(x, w)

    product = _compile(experts, x, w, tiles, used)
    tokens = sd((s, 2048), jnp.bfloat16)
    gates = sd((s, 8), jnp.float32)
    token_of_row = sd((rows,), jnp.int32)
    row_of_pair = sd((s, 8), jnp.int32)

    def move(tokens, x, gates, token_of_row, row_of_pair, used):
        """Every form of the two row kernels: the plain copy and its
        transpose (``dispatch``), the gate-weighted sum and its transpose
        with the gates' gradient (``combine``)."""
        where = (token_of_row, row_of_pair, used, G.TILE, False)

        def loss(tokens, x, gates):
            return (G.dispatch(tokens, *where).astype(jnp.float32).sum()
                    + G.combine(x, gates, *where).astype(jnp.float32).sum())

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(tokens, x, gates)

    movement = _compile(move, tokens, x, gates, token_of_row, row_of_pair,
                        used)
    qi = sd((1, s, 16, 64), jnp.bfloat16)
    ki = sd((1, s, 64), jnp.bfloat16)
    wi = sd((1, s, 16), jnp.bfloat16)
    target = sd((1, s, s), jnp.float32)

    def index(qi, ki, wi, target):
        """The model's calls, with the kernels compiled and not interpreted
        (``index_select`` and ``indexer_loss`` ask the backend, a CPU
        here)."""
        layout = S._index_layout(qi, ki, wi)
        sel = S._select(*layout, 2048, 256, 512, False)
        grads = jax.grad(lambda *a: S._index_kl(
            *a, sel, target, 256, 512, False).sum(), argnums=(0, 1, 2))(
                *layout)
        return sel, grads

    indexer = _compile(index, qi, ki, wi, target)
    return {"sparse_attn_fwd": attention, "sparse_attn_bwd_dqkv": attention,
            "sparse_attn_bwd_dq_": pair, "sparse_attn_bwd_dkv": pair,
            "sparse_attn_probs": attention,
            "expert_gmm": product, "expert_tgmm": product,
            "expert_rows_in": movement, "expert_rows_out": movement,
            "index_select": indexer, "index_kl_fwd": indexer,
            "index_kl_bwd_dq": indexer, "index_kl_bwd_dk": indexer}


@pytest.mark.parametrize("name", ["sparse_attn_fwd", "sparse_attn_bwd_dqkv",
                                  "sparse_attn_bwd_dq_",
                                  "sparse_attn_bwd_dkv", "sparse_attn_probs",
                                  "expert_gmm", "expert_tgmm",
                                  "expert_rows_in", "expert_rows_out",
                                  "index_select",
                                  "index_kl_fwd", "index_kl_bwd_dq",
                                  "index_kl_bwd_dk"])
def test_sparse_kernels_lower_on_tpu_under_their_names(sparse_texts, name):
    """Each kernel of ``ops/sparse_attention.py`` and ``ops/grouped_matmul.py``
    compiles for the chip at the configuration's widths, and its ``name=`` is
    inside the custom call's instruction name, where the benchmark's readers
    look for it (``chipbench/trace_reads.py``). (``sparse_attn_bwd_dq_``,
    with the wrapper's underscore, is the dq kernel and not
    ``sparse_attn_bwd_dqkv``.)"""
    calls = _custom_calls(sparse_texts[name])
    assert any(name in c for c in calls), calls


# -- the block-diffusion mask's attention ----------------------------------------


@pytest.fixture(scope="module")
def block_texts(one_chip):
    """Compiled text of the kernels ``block_diffusion_lm`` runs, at the
    widths of ``chipbench/configs/sdar-30b-a3b-ep8.json``: one row of 4096
    tokens as 8192 positions, blocks of 4, 32 query heads over 4 KV heads of
    128, forward and the one backward kernel; the dq and dkv pair at a row of
    32 768 positions, past the fused kernel's VMEM budget."""
    from sparkflow_tpu.ops import block_attention as B

    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)

    def attend(q, k, v):
        return jax.grad(lambda q, k, v: B.block_attention(
            q, k, v, q.shape[2] // 2, 4, interpret=False)[0].astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    with A.record_attention_paths() as paths:
        cell, long = (_compile(attend, sd((1, 32, s, 128)),
                               sd((1, 4, s, 128)), sd((1, 4, s, 128)))
                      for s in (8192, 32768))
    assert paths == ["block_attention_fwd:512x1024",
                     "block_attention_bwd:fused",
                     "block_attention_fwd:512x1024",
                     "block_attention_bwd:split"]
    return {"block_attn_fwd": cell, "block_attn_bwd_dqkv": cell,
            "block_attn_bwd_dq_": long, "block_attn_bwd_dkv": long}


@pytest.mark.parametrize("name", ["block_attn_fwd", "block_attn_bwd_dqkv",
                                  "block_attn_bwd_dq_", "block_attn_bwd_dkv"])
def test_block_attention_lowers_on_tpu_under_its_names(block_texts, name):
    """Each kernel of ``ops/block_attention.py`` compiles for the chip at the
    configuration's widths (the mask made in the kernel from the indices, the
    grid over the schedule's tiles), and its ``name=`` is inside the custom
    call's instruction name, where the benchmark's readers look for it. No
    name holds ``sparse_attn`` or ``flash``, which other readers match."""
    calls = _custom_calls(block_texts[name])
    assert any(name in c for c in calls), calls
    assert not any("sparse_attn" in c or "flash" in c for c in calls), calls


@pytest.mark.parametrize("family", ["sparse", "block"])
def test_the_cells_side_of_the_fused_backwards_budget_holds_no_pair(
        sparse_texts, block_texts, family):
    """At both MoE cells' shapes the backward is the one kernel: neither
    kernel of the pair is in the compiled program."""
    text = dict(sparse=sparse_texts["sparse_attn_fwd"],
                block=block_texts["block_attn_fwd"])[family]
    calls = _custom_calls(text)
    assert any(f"{family}_attn_bwd_dqkv" in c for c in calls), calls
    assert not any(f"{family}_attn_bwd_dq_" in c
                   or f"{family}_attn_bwd_dkv" in c for c in calls), calls


def _kernel_windows(text, name):
    """Does the Mosaic module of the custom call ``name`` in a compiled text
    hold the int64 array ``shape`` (a block's ``window_bounds``, the grid's
    ``iteration_bounds``)? The module travels as base64 of MLIR bytecode,
    which keeps such an array as its little-endian bytes."""
    line = next(l for l in text.splitlines()
                if any(name in c for c in _custom_calls(l)))
    body = base64.b64decode(re.search(r'"body":"([^"]+)"', line).group(1))
    return lambda *shape: struct.pack(f"<{len(shape)}q", *shape) in body


@pytest.mark.parametrize("family", ["sparse", "block"])
def test_the_forwards_walk_1024_keys_a_visit_at_the_cells_widths(
        sparse_texts, block_texts, family):
    """With the default tiles both MoE cells' forward kernels compile for
    the chip with a key block of ``[1024, 128]`` under a query block of the
    group's eight ``[512, 128]`` (keye's grid 16 x 8 key tiles a KV head,
    sdar's the 48 visits of its schedule; the selection's tile int8 ``[512,
    1024]``), while the one backward kernel keeps 512 keys."""
    text = dict(sparse=sparse_texts, block=block_texts)[family][
        f"{family}_attn_fwd"]
    fwd = _kernel_windows(text, f"{family}_attn_fwd")
    bwd = _kernel_windows(text, f"{family}_attn_bwd_dqkv")
    assert fwd(1, 8, 512, 128) and fwd(1, 1024, 128) and not fwd(1, 512, 128)
    assert bwd(1, 8, 512, 128) and bwd(1, 512, 128) and not bwd(1, 1024, 128)
    if family == "sparse":
        assert fwd(4, 16, 8) and fwd(1, 512, 1024)
        assert bwd(4, 16, 16) and bwd(1, 512, 512)
    else:
        assert fwd(4, 48) and bwd(4, 80)


@pytest.mark.parametrize("family", ["sparse", "block"])
def test_the_forwards_at_512_x_1024_stand_well_inside_the_vmem_limit(
        one_chip, family, monkeypatch):
    """The v5e's compiler takes ``sparse_attn_fwd`` at the cells' shapes and
    512 x 1 024 with 36.1 MB of VMEM and ``block_attn_fwd`` with 35.3 (the
    least limits it accepts; ``ops/sparse_attention.py`` has them beside
    ``_VMEM_LIMIT``, 96 MiB): both lower under 40 MiB, under their names, and
    neither under 32."""
    from sparkflow_tpu.ops import block_attention as B
    from sparkflow_tpu.ops import sparse_attention as S

    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    s = 8192
    args = [sd(1, 32, s, 128), sd(1, 4, s, 128), sd(1, 4, s, 128)]
    if family == "sparse":
        args.append(jax.ShapeDtypeStruct((1, s, s), jnp.int8,
                                         sharding=one_chip))
        fn = lambda q, k, v, m: S.selected_attention(q, k, v, m,
                                                     interpret=False)
    else:
        fn = lambda q, k, v: B.block_attention(q, k, v, s // 2, 4,
                                               interpret=False)
    assert S._VMEM_LIMIT == 96 * 1024 * 1024
    monkeypatch.setattr(S, "_VMEM_LIMIT", 40 * 1024 * 1024)
    with A.record_attention_paths() as paths:
        calls = _custom_calls(_compile(fn, *args))
    assert paths == [f"{family}_attention_fwd:512x1024"]
    assert len(calls) == 1 and f"{family}_attn_fwd" in calls[0], calls
    monkeypatch.setattr(S, "_VMEM_LIMIT", 32 * 1024 * 1024)
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(lambda *a: fn(*a), *args)


def test_another_block_length_and_head_layout_lower_on_tpu(one_chip):
    """Blocks of 16 in a half row of three tiles, 8 query heads over one KV
    head: another shift, another group."""
    from sparkflow_tpu.ops import block_attention as B

    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    text = _compile(lambda q, k, v: B.block_attention(
        q, k, v, 1536, 16, interpret=False)[0], sd((1, 8, 3072, 128)),
        sd((1, 1, 3072, 128)), sd((1, 1, 3072, 128)))
    assert "block_attn_fwd" in text


# -- q and k on their way to the attention kernels ------------------------------


@pytest.fixture
def on_a_tpu(monkeypatch):
    """``ops/head_rotary.py`` has no ``interpret`` argument: it asks the
    backend, a CPU here, so the test answers for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _outputs(text):
    """``(instruction, the text of its result's shape)`` of the compiled
    text's entry computation: what the program writes to memory (a fusion's
    inner instructions write nothing)."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    return re.findall(r"^\s*(?:ROOT )?%?(\S+) = (.*?)\s[\w\-]+\(", entry,
                      re.M)


def _float32_rows(text, positions, elements):
    """The float32 arrays the program writes that have a dimension of
    ``positions`` and ``elements`` elements or more."""
    found = []
    for name, shape in _outputs(text):
        for dims in re.findall(r"f32\[([\d,]+)\]", shape):
            dims = [int(n) for n in dims.split(",")]
            if positions in dims and np.prod(dims) >= elements:
                found.append((name, dims))
    return found


# the cells' projections: keye's and sdar's q and k (a row of 8192 positions,
# 32 and 4 heads of 128), ouro's q and k (two rows of 4096, 16 heads)
HEADS = [(1, 8192, 32), (1, 8192, 4), (2, 4096, 16)]


@pytest.mark.parametrize("norm", [True, False], ids=["norm", "no_norm"])
@pytest.mark.parametrize("b, s, h", HEADS,
                         ids=["moe-q", "moe-k", "train-ouro-seq4k"])
def test_head_rotary_lowers_on_tpu_under_its_names(one_chip, on_a_tpu, b, s,
                                                   h, norm):
    """``head_rotary_fwd`` and ``head_rotary_bwd`` compile for the chip at
    the cells' shapes, with the per-head norm and without, and each
    ``name=`` is inside its custom call's instruction name, where the
    benchmark's reader looks for ``head_rotary_``."""
    from sparkflow_tpu.models import lm_ops

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)

    def fwd_bwd(x, scale):
        return jax.grad(lambda x, scale: jnp.sum(jnp.square(lm_ops.heads(
            x, h, scale if norm else None, 1e-6, 1e6).astype(jnp.float32))),
            argnums=(0, 1) if norm else 0)(x, scale)

    with A.record_attention_paths() as paths:
        calls = _custom_calls(_compile(fwd_bwd, sd((b, s, h * 128),
                                                   jnp.bfloat16),
                                       sd((128,), jnp.float32)))
    assert paths == ["head_rotary:pallas"]
    assert len(calls) == 2 and any("head_rotary_fwd" in c for c in calls)
    assert any("head_rotary_bwd" in c for c in calls), calls


def test_the_moe_blocks_q_and_k_are_written_once_after_their_product(
        one_chip, on_a_tpu):
    """The compiled forward of ``MoEDecoder._qkv`` at the MoE cells' widths
    (a row of 8192, 32 query heads over 4 KV heads of 128): two kernels, and
    no float32 array over a row's positions as large as ``k`` (``S x 4 x
    128``; ``q`` is eight of them). Written as ``rope(rms_norm(...))`` and a
    transpose, XLA wrote the normed ``q`` and its rotate-half as float32
    ``[1, 8192, 32, 128]`` each before the bfloat16 result and its copy."""
    from sparkflow_tpu.models.sparse_moe_lm import SparseMoELM

    model = SparseMoELM(vocab_size=1024, num_layers=1, num_experts=16,
                        compute_dtype="bfloat16")
    bp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, (shape, _) in model._block_specs().items()}
    y = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16, sharding=one_chip)
    text = _compile(model._qkv, bp, y)
    assert sum("head_rotary_fwd" in c for c in _custom_calls(text)) == 2
    assert _float32_rows(text, 8192, 8192 * 4 * 128) == []


def test_ouros_block_writes_no_float32_heads(one_chip, on_a_tpu):
    """The compiled forward of ``LoopedLM._block`` at ouro's widths (two rows
    of 4096, 16 heads of 128, hidden 2048): ``q`` and ``k`` through the
    kernel, and no float32 array over the rows' positions as large as one of
    them (both products wrote float32 ``[2, 4096, 2048]`` for the rotation,
    and the rotate-halves were float32 arrays too)."""
    from sparkflow_tpu.models.looped_lm import LoopedLM

    model = LoopedLM(vocab_size=1024, compute_dtype="bfloat16")
    bp = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
          for name, (shape, _) in model.param_specs()["block_0"].items()}
    x = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    text = _compile(model._block, bp, x)
    calls = _custom_calls(text)
    assert sum("head_rotary_fwd" in c for c in calls) == 2, calls
    assert any("flash_fwd" in c for c in calls), calls
    assert _float32_rows(text, 4096, 2 * 4096 * 16 * 128) == []
