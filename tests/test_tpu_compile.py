"""The main path's Pallas kernels, compiled for a described TPU v5e.

Interpret-mode parity (test_flash_decode.py, test_paged.py, test_ops.py)
checks a kernel's math; it says nothing about whether the chip's compiler
takes the kernel — two decode kernels passed every interpret-mode test
for ten PRs and were refused by Mosaic at their first block spec. The
TPU compiler is installed here and compiles for a chip that is described
and not attached (``jax.experimental.topologies``), so each case below
lowers one kernel — or one whole decode step — at GPT-2 125M widths
(12 heads x 64, B=8, cache 1024, page 128) and asserts that the compiled
program contains the Mosaic call. Nothing runs: this is not a chip run
and proves no result and no speed.

The code under test asks :func:`mpi_acx_tpu.backend.on_tpu` whether to
compile or interpret; the fixture patches that one name.
"""

import dataclasses
import functools
import hashlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from mpi_acx_tpu import backend
from mpi_acx_tpu.models import kvpage
from mpi_acx_tpu.models import transformer as tfm
from mpi_acx_tpu.ops import attention, flags, flash_decode

B, H, D, MAX_LEN, PAGE = 8, 12, 64, 1024, 128
N_PAGES = B * MAX_LEN // PAGE + B


@pytest.fixture(scope="module")
def v5e():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a TPU v5e here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compile_for_chip(monkeypatch):
    """Take the TPU branch everywhere, and keep the persistent compile
    cache out of it: an executable compiled for an absent chip is
    written but cannot be read back, and the next compile warns."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_s = jax.ShapeDtypeStruct


def _qkv(S):
    return [_s((B, S, H, D), jnp.bfloat16)] * 3


def _decode_args(kind, paged):
    """(q, k, v, [table,] pos) shapes; K/V in cache / pool layout."""
    lead, T = (N_PAGES, PAGE) if paged else (B, MAX_LEN)
    if kind == "int8":
        kv = (_s((lead, H, D, T), jnp.int8), _s((lead, H, 1, T), jnp.float32))
    else:
        kv = _s((lead, H, D, T), jnp.bfloat16)
    args = [_s((B, 1, H, D), jnp.bfloat16), kv, kv]
    if paged:
        args.append(_s((B, MAX_LEN // PAGE), jnp.int32))
    return args + [_s((B,), jnp.int32)]


def _fixed(q, k, v, pos):
    return flash_decode.flash_decode_attend(q, k, v, pos, MAX_LEN, 1)


def _paged(q, k, v, table, pos):
    return flash_decode.paged_flash_decode_attend(q, k, v, table, pos,
                                                  PAGE, 1)


_FLAGS = _s((64,), jnp.int32)
_IDX = _s((), jnp.int32)
_IDXS = _s((4,), jnp.int32)


def _model():
    """GPT-2 125M at full width, bf16 weights; depth cut to one layer
    (the layer stack is one scanned body, so depth adds nothing a
    compile can refuse). Shapes only."""
    cfg = dataclasses.replace(tfm.gpt2_small(), n_layers=1)
    params = tfm.cast_params(tfm.init_params(jax.random.key(0), cfg))
    return cfg, jax.tree.map(lambda a: _s(a.shape, a.dtype), params)


def _decode_step():
    """transformer.decode_step at its DEFAULT config: auto -> the
    Pallas decode kernel at max_len >= 1024 on TPU."""
    cfg, params = _model()
    cache = jax.eval_shape(lambda: tfm.init_kv_cache(cfg, B, MAX_LEN))
    cache["pos"] = _s((B,), jnp.int32)
    return (lambda p, c, t: tfm.decode_step(p, cfg, c, t),
            [params, cache, _s((B,), jnp.int32)])


def _paged_step():
    """kvpage.paged_decode_step at its default config and page size,
    int8 pool — serve_paged_greedy(kv_int8=True)'s step."""
    cfg, params = _model()
    state = jax.eval_shape(
        lambda: kvpage.init_page_pool(cfg, N_PAGES - B, PAGE, B,
                                      kv_int8=True))
    state["table"] = _s((B, MAX_LEN // PAGE), jnp.int32)
    state["pos"] = _s((B,), jnp.int32)
    return (lambda p, s, t: kvpage.paged_decode_step(p, cfg, s, t, PAGE),
            [params, state, _s((B,), jnp.int32)])


CASES = {
    "flash_resident_1024": lambda: (attention.flash_attention, _qkv(1024)),
    "flash_resident_4096": lambda: (attention.flash_attention, _qkv(4096)),
    "flash_streaming_16384": lambda: (
        attention.flash_attention, [_s((1, 16384, H, D), jnp.bfloat16)] * 3),
    "flash_lse_1024": lambda: (attention.flash_attention_lse, _qkv(1024)),
    "decode_fixed_bf16": lambda: (_fixed, _decode_args("bf16", False)),
    "decode_fixed_int8": lambda: (_fixed, _decode_args("int8", False)),
    "decode_paged_bf16": lambda: (_paged, _decode_args("bf16", True)),
    "decode_paged_int8": lambda: (_paged, _decode_args("int8", True)),
    "decode_paged_xl_bf16": lambda: _xl_attend("bf16"),
    "decode_paged_xl_int8": lambda: _xl_attend("int8"),
    "flags_pready": lambda: (flags.pready, [_FLAGS, _IDX]),
    "flags_pready_many": lambda: (flags.pready_many, [_FLAGS, _IDXS]),
    "flags_parrived": lambda: (flags.parrived, [_FLAGS, _IDX]),
    "flags_parrived_all": lambda: (flags.parrived_all, [_FLAGS, _IDXS]),
    "flags_produce_and_pready": lambda: (
        lambda x, f, i: flags.produce_and_pready(lambda t: t * t, x, f, i),
        [_s((8, 128), jnp.float32), _FLAGS, _IDX]),
    "decode_step_default": _decode_step,
    "paged_decode_step_default_int8": _paged_step,
}


def _place(spec, sharding):
    """A tree of shapes -> the same shapes on the described chip."""
    return jax.tree.map(
        lambda x: _s(x.shape, x.dtype, sharding=sharding), spec)


@pytest.mark.parametrize("name", list(CASES))
def test_compiles_for_v5e(name, v5e):
    fn, args = CASES[name]()
    compiled = jax.jit(fn).lower(*_place(args, v5e)).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: compiled, but with no Mosaic kernel in the program"


# The attention backward at the training cell's geometry
# ([8, 1024, 16, 64] bf16: GPT-2 medium, 4 micro-batches of 8 rows) and
# beyond: the gradient's program holds the backward Mosaic call under
# its own name and nothing of the blockwise path it replaced.

def _grad_lse(causal):
    def loss(q, k, v):
        o, lse = attention.flash_attention_lse(q, k, v, causal=causal)
        return (o.astype(jnp.float32) ** 2).sum() + lse.sum()
    return jax.grad(loss, argnums=(0, 1, 2))


def _grad_plain(q, k, v):
    return jax.grad(lambda *a: (attention.flash_attention(*a).astype(
        jnp.float32) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)


def _bhsd(b, s, h, dtype=jnp.bfloat16):
    return _s((b, s, h, D), dtype)


BWD_CASES = {
    "lse_cell_1024": (_grad_lse(True), [_bhsd(8, 1024, 16)] * 3),
    "plain_cell_1024": (_grad_plain, [_bhsd(8, 1024, 16)] * 3),
    "lse_4096": (_grad_lse(True), [_bhsd(8, 4096, H)] * 3),
    "lse_full_sq_ne_sk": (_grad_lse(False), [_bhsd(8, 1024, 16)]
                          + [_bhsd(8, 2048, 16)] * 2),
    "lse_f32_1024": (_grad_lse(True),
                     [_bhsd(2, 1024, 4, jnp.float32)] * 3),
}


@pytest.mark.parametrize("name", list(BWD_CASES))
def test_flash_backward_is_one_mosaic_call(name, v5e):
    fn, args = BWD_CASES[name]
    text = jax.jit(fn).lower(*_place(args, v5e)).compile().as_text()
    shapes = dict(re.findall(r"(%[\w.\-]+) = \(?(\w+\[[\d,]*\])", text))
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and " custom-call(" in l]
    bwd = [l for l in calls if l.strip().startswith("%attn_bwd")]
    fwd = [l for l in calls if "flash_attention" in l.split(" = ")[0]]
    assert len(bwd) == 1 and len(fwd) == 1, [l[:80] for l in calls]
    # the forward reader of the benchmark counts ``%flash_attention``
    assert "flash_attention" not in bwd[0].split(" = ")[0]
    operands = re.findall(r"%[\w.\-]+", bwd[0].split("custom-call(")[1]
                          .split(")")[0])
    assert len(operands) == 6
    for op in operands:            # a lane-1 operand is padded to 128
        assert not shapes[op].endswith(",1]"), (op, shapes[op])
    # nothing of the blockwise path: no loop over q blocks, no block of
    # probabilities in HBM
    assert not re.search(r"\swhile\(", text)
    assert not re.search(r"\[\d+,\d+,512,512\]", text)


@pytest.mark.parametrize("attend,args", [
    (lambda q, k, v, pos: flash_decode.flash_decode_attend(
        q, k, v, pos, 200, 1),
     [_s((B, 1, H, D), jnp.bfloat16)] + [_s((B, H, D, 200), jnp.bfloat16)] * 2
     + [_s((B,), jnp.int32)]),
    (lambda q, k, v, table, pos: flash_decode.paged_flash_decode_attend(
        q, k, v, table, pos, 32, 1),
     [_s((B, 1, H, D), jnp.bfloat16)]
     + [_s((N_PAGES, H, D, 32), jnp.bfloat16)] * 2
     + [_s((B, 8), jnp.int32), _s((B,), jnp.int32)]),
], ids=["fixed_max_len_200", "paged_page_32"])
def test_explicit_kernel_raises_on_untileable_length(attend, args, v5e):
    """decode_flash=True on a length Mosaic cannot tile is an error, not
    a quiet hand-over to the dense reference (the auto policy keeps its
    own guard and never gets here)."""
    with pytest.raises(ValueError, match="128"):
        jax.jit(attend).lower(*_place(args, v5e))


# --------------------------------------------------------------------------
# The paged decode step at the benchmark's serving geometry (GPT-2 XL:
# 25 heads x 64, 32 slots, page 128, 8 table columns), depth, pages and
# vocabulary cut: the page-write kernel alone, then a whole chunk of
# steps, which must hold both Mosaic calls and move no pool.

XL_B, XL_H, XL_L, XL_PAGES = 32, 25, 2, 208 + 32       # + parking pages


def _xl_pools(kind, n_layers=XL_L):
    pool = (n_layers, XL_PAGES, XL_H, D, PAGE)
    if kind == "int8":
        scales = _s(pool[:3] + (1, PAGE), jnp.float32)
        return (_s(pool, jnp.int8),) * 2 + (scales,) * 2
    return (_s(pool, jnp.bfloat16),) * 2


def _xl_attend(kind):
    """The live-page walk alone at the serving cells' geometry: the
    whole pools (+ the f32 scale pools when int8) left in HBM, a traced
    layer index, pages copied out by the kernel."""
    pools = _xl_pools(kind)
    kv = (pools[0], pools[2]) if kind == "int8" else pools[0]
    return (lambda q, k, v, table, pos, layer:
            flash_decode.paged_flash_decode_attend(q, k, v, table, pos,
                                                   PAGE, 1, layer=layer),
            [_s((XL_B, 1, XL_H, D), jnp.bfloat16), kv, kv,
             _s((XL_B, MAX_LEN // PAGE), jnp.int32), _s((XL_B,), jnp.int32),
             _s((), jnp.int32)])


# slots, K/V heads, query heads a K/V head, head width, table columns,
# layers with pages, pages: the three serving cells' walks
WALK_GEOMETRIES = {
    "xl": (XL_B, XL_H, 1, D, MAX_LEN // PAGE, 48, XL_PAGES),
    "lfm2": (64, 8, 4, 64, MAX_LEN // PAGE, 2, 1024 + 64),
    "jamba": (128, 1, 20, 128, 1280 // PAGE, 2, 1280 + 128),
}


@pytest.mark.parametrize("staged", [True, False], ids=["stage", "no-stage"])
@pytest.mark.parametrize("cell", list(WALK_GEOMETRIES))
def test_paged_walk_with_left_compiles_for_v5e(cell, staged, v5e):
    """The live-page walk told which slots are dead (``left``: two more
    prefetched vectors, a ``pl.when`` around the walk, index maps that
    read one of them) at each serving cell's geometry, behind a chunk's
    stage and without one: Mosaic takes it, as ONE call with the result
    the benchmark's readers match."""
    nb, hkv, n_rep, d, cols, layers, pages = WALK_GEOMETRIES[cell]
    pool = _s((layers, pages, hkv, d, PAGE), jnp.bfloat16)
    stage = _s((layers, nb, XL_CHUNK, hkv, 2 * d), jnp.bfloat16)

    def attend(q, k, v, table, pos, layer, left, stage, step):
        return flash_decode.paged_flash_decode_attend(
            q, k, v, table, pos, PAGE, n_rep, layer=layer, left=left,
            stage=((stage,), step) if staged else None)

    args = [_s((nb, 1, hkv * n_rep, d), jnp.bfloat16), pool, pool,
            _s((nb, cols), jnp.int32), _s((nb,), jnp.int32),
            _s((), jnp.int32), _s((nb,), jnp.int32), stage,
            _s((), jnp.int32)]
    text = jax.jit(attend).lower(*_place(args, v5e)).compile().as_text()
    calls = re.findall(r"%paged_flash_decode_attend[.0-9]* = "
                       r"([a-z0-9]+\[[0-9,]*\]).* custom-call\(", text)
    assert calls == [f"bf16[{nb},{hkv},{n_rep},{d}]"], calls


def _compiled_write(write, kind, v5e):
    """(compiled text, pool shape) of a page write on donated pools:
    (pools, fresh [B, 1, H, *], layer, write_page [B], off [B])."""
    pools = _xl_pools(kind)
    fresh = tuple(_s((XL_B, 1) + p.shape[2:4], p.dtype) for p in pools)
    args = [pools, fresh, _s((), jnp.int32), _s((XL_B,), jnp.int32),
            _s((XL_B,), jnp.int32)]
    text = jax.jit(write, donate_argnums=(0,)).lower(
        *_place(args, v5e)).compile().as_text()
    return text, pools[0].shape


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_page_write_kernel_compiles_for_v5e(kind, v5e):
    """flash_decode.paged_kv_write alone: the 5-D page blocks addressed
    from prefetched scalars, the fresh blocks with slots on lanes, the
    f32 scale pages, and the pools aliased to the results."""
    text, pool = _compiled_write(flash_decode.paged_kv_write, kind, v5e)
    assert "%paged_kv_write" in text and "tpu_custom_call" in text
    assert not _pool_movers(text, pool)


def _pool_movers(text, pool_shape):
    """Instructions of a compiled program that mention an array the
    size of one layer's pool or of the whole pool (K/V or scale pages)
    and are anything but plumbing or a Mosaic call: the copies, slices,
    updates and fusions that would move a pool."""
    L, P, H, _, T = pool_shape
    sized = re.compile(rf"\[(?:{L},)?{P},{H},(?:{D}|1),{T}\]")
    plumbing = {"parameter", "get-tuple-element", "tuple", "while",
                "bitcast", "custom-call"}
    found = []
    for line in text.splitlines():
        op = re.search(r"\s([a-z][a-z0-9-]*)\(", line)
        if (" = " in line and sized.search(line) and op
                and op.group(1) not in plumbing):
            found.append(line.strip()[:160])
    return found


def test_pool_movers_guard_sees_a_moved_pool(v5e):
    """The guard itself: the dense write on the same pools compiles to
    the slice / scatter / update of a layer that it must report."""
    text, pool = _compiled_write(flash_decode.paged_kv_write_dense, "bf16",
                                 v5e)
    assert _pool_movers(text, pool)


_XL_CFG = tfm.TransformerConfig(vocab=512, d_model=XL_H * D, n_heads=XL_H,
                                n_layers=1, d_ff=4 * XL_H * D,
                                max_seq=MAX_LEN)


XL_CHUNK = 32


def _xl_chunk(kind, n_layers=XL_L):
    """(paged_decode_chunk, args, keywords): the process's one chunk
    program as ``make_paged_step_fn`` binds it at the cell's geometry
    and default config, the serving chunk of 32 steps, the state with
    ``left`` as a serve loop hands it over."""
    cfg = _XL_CFG
    params = jax.tree.map(
        lambda a: _s(a.shape, a.dtype),
        tfm.cast_params(tfm.init_params(jax.random.key(0), cfg)))
    params["layers"] = jax.tree.map(
        lambda a: _s((n_layers,) + a.shape[1:], a.dtype), params["layers"])
    pools = _xl_pools(kind, n_layers)
    state = dict(zip(("k", "v", "ks", "vs"), pools),
                 table=_s((XL_B, MAX_LEN // PAGE), jnp.int32),
                 pos=_s((XL_B,), jnp.int32), left=_s((XL_B,), jnp.int32))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), XL_B))
    step = kvpage.make_paged_step_fn(params, cfg, tfm, XL_CHUNK, PAGE)
    assert step.func is kvpage.paged_decode_chunk
    return (step.func, [*step.args, state, _s((XL_B,), jnp.int32), keys],
            step.keywords)


def test_page_write_kernel_bytes_do_not_depend_on_the_caller(v5e):
    """The write kernel as it is serialized into the step program (its
    ``backend_config``, which the persistent compilation cache hashes)
    is the same whoever asks for the program. Mosaic bodies carry ten
    frames of the Python traceback they were traced under; traced
    afresh per caller, the write made the benchmark's warm-up burst and
    its window two cache entries for one program, and a compile inside
    the window (PERF.md, PR 25). The attend is not held to this here:
    in the serve loop its ten frames end inside the package, in this
    shallow test they would not."""
    def lowered():
        def one_step(params, state, tok):  # a new function, as a caller's
            return kvpage.paged_decode_step(params, _XL_CFG, state, tok, PAGE)
        _, (params, state, tok, _), _ = _xl_chunk("bf16")
        return jax.jit(one_step, donate_argnums=(1,)).lower(
            *_place([params, state, tok], v5e)).compiler_ir("stablehlo")

    def one_caller():
        return lowered()

    def another_caller():
        return (lowered(),)[0]

    def write_config(module):
        """The write's custom call, locations of the outer program off."""
        return [re.search(r'backend_config = "(.*?)"[,}]', line).group(1)
                for line in module.operation.get_asm().splitlines()
                if 'kernel_name = "paged_kv_write"' in line]

    a, b = write_config(one_caller()), write_config(another_caller())
    assert len(a) == 1 and a == b


def test_the_process_level_chunk_program_is_one_whoever_calls(v5e):
    """``kvpage.paged_decode_chunk`` itself, the jitted function that
    every serve call of a process shares (PERF.md, PR 28), bound by
    ``make_paged_step_fn`` as each serve call binds it and lowered for
    the described v5e from two call paths. Traced under the first, it
    is the same program to the last byte under the second, both Mosaic
    bodies and the tracebacks inside them included (a fresh trace from
    this shallow a caller would carry the caller's frames in the
    attend's): a call from the benchmark's window cannot make another
    cache entry, and a compile, than its warm-up call did."""
    def lowered():
        chunk, args, kw = _xl_chunk("bf16")
        return chunk.lower(*_place(args, v5e), **kw).compiler_ir("stablehlo")

    def one_caller():
        return lowered()

    def another_caller():
        return (lowered(),)[0]

    def kernels(module):
        """kernel name -> its custom call's serialized body."""
        found = {}
        for line in module.operation.get_asm().splitlines():
            name = re.search(r'kernel_name = "(\w+)"', line)
            if name:
                found[name.group(1)] = re.search(
                    r'backend_config = "(.*?)"[,}]', line).group(1)
        return found

    jax.clear_caches()
    traced = kvpage.programs_traced()
    first = kernels(one_caller())
    assert sorted(first) == ["paged_flash_decode_attend", "paged_kv_write"]
    assert kernels(another_caller()) == first
    assert kvpage.programs_traced() == traced + 1


def _loop_depths(jaxpr, depth=0, found=None):
    """kernel name -> how many ``scan`` / ``while`` bodies each of its
    pallas_calls sits in."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(depth)
        looped = eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _loop_depths(sub, depth + looped, found)
    return found


def _unfilled_stage(text, pools, n_slots, v_dim=None):
    """Lines of a compiled chunk that make its stage WITHOUT filling it.

    XLA's TPU compiler turns a zero fill into an ``AllocateBuffer``
    (uninitialised memory) where it sees a loop overwrite every element
    of the buffer, and it does not count a Pallas call's reads inside
    that loop: the walk's fold reads the rows of the stage that the
    chunk has not reached yet behind a weight of exactly 0.0, and 0.0
    times whatever the memory held before is NaN where that is not
    finite. PR 40's latent stage, updated ONE sublane row a step, lost
    its zeros that way (chip: every token 0 at the smoke's size); it is
    now rewritten a layer at a time (``flash_decode.stage_put``), which
    reads it, and keeps them."""
    made = jax.eval_shape(lambda: flash_decode.new_kv_stage(
        list(pools), n_slots, XL_CHUNK, v_dim))
    shapes = ["[" + ",".join(map(str, a.shape)) + "]" for a in made]
    return [l.strip()[:160] for l in text.splitlines()
            if "AllocateBuffer" in l
            and any(s in l.split(" custom-call(")[0] for s in shapes)]


# What PERF.md states for the cell's stage: 48 layers x 32 slots x 32
# tokens x 25 heads x (V then K: 2 x 64 = the 128 lanes), bf16, as
# counted; as the chip lays it out the 25 heads pad to 32 rows.
XL_STAGE_BYTES = 48 * 32 * 32 * 25 * 128 * 2
XL_STAGE_BYTES_LAID_OUT = XL_STAGE_BYTES // 25 * 32


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_paged_decode_chunk_moves_no_pool(kind, v5e):
    """``paged_decode_chunk`` (state donated) at the cell's geometry,
    default config and serving chunk: both Mosaic calls are in the
    program, once each, and no instruction copies, slices or updates an
    array the size of a layer's pool or of the pool. The one-token
    write once did exactly that: 93% of a decode step on the chip
    (PERF.md, PR 25), invisible off it. The attend is ONE custom call
    with the single result ``bf16[32,25,1,64]`` (what the benchmark's
    roofline reader matches), and its grid is the slots alone: no step
    a (slot, page) pair, live or dead; with ``left`` in the state it is
    told which slots are dead, by two vectors made once a step. The
    write is the chunk's FLUSH:
    ``chunk`` tokens a slot over a grid of (slot, the two pages they
    can land in), in the scan over layers BEHIND the scan over steps
    (one loop deep, where the attend is two deep), so a page is moved
    once a chunk. The stage is updated in place: nothing copies it, and
    it weighs what PERF.md says."""
    n_layers = 48       # whole: a stage of two layers fits fast memory
    chunk, args, kw = _xl_chunk(kind, n_layers)
    pools = _xl_pools(kind, n_layers)
    compiled = chunk.lower(*_place(args, v5e), **kw).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([a-z_]+)[.0-9]* = (.+?) custom-call\(.*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(name for name, _ in calls) == [
        "paged_flash_decode_attend", "paged_kv_write"]
    result = dict(calls)["paged_flash_decode_attend"]
    assert result.startswith(f"bf16[{XL_B},{XL_H},1,{D}]{{"), result
    assert not _pool_movers(text, pools[0].shape), \
        "\n".join(_pool_movers(text, pools[0].shape))
    assert not _unfilled_stage(text, pools, XL_B)
    jaxpr = jax.make_jaxpr(lambda *a: chunk.__wrapped__(*a, **kw))(
        *args).jaxpr
    grids = _pallas_grids(jaxpr)
    assert grids["paged_flash_decode_attend"] == [(XL_B,)]
    assert grids["paged_kv_write"] == [(XL_B, 2)]
    assert _loop_depths(jaxpr) == {"paged_flash_decode_attend": [2],
                                   "paged_kv_write": [1]}
    # the stage: [L, B, chunk, H, 2 D] (the scales [L, B, H, 1, chunk],
    # as in a page), written in place, one token's tiles a layer a step,
    # and read by the attend. In the computation that holds the attend
    # (the layer's body) nothing else makes a stage: no copy, no
    # transpose; the flush may relayout it once a chunk.
    stage = (rf"\[{n_layers},{XL_B},(?:{XL_CHUNK},{XL_H},{2 * D}|"
             rf"{XL_H},1,{XL_CHUNK})\]")
    bodies = re.split(r"\n(?=%|ENTRY )", text)
    body, = [b for b in bodies if "%paged_flash_decode_attend" in b
             and "tpu_custom_call" in b]
    # which slots are dead (``flash_decode._live_slots``) depends on the
    # step alone: worked out once a step, not in front of each call
    assert "_live_slots" in text and "_live_slots" not in body
    updates = {b.split(" ", 1)[0] for b in bodies          # fused, in place
               if re.search(r"\n\s*ROOT [^\n]*? dynamic-update-slice\(", b)}
    made = [l.strip() for l in body.splitlines()
            if re.search(rf" = [a-z0-9]+{stage}", l)
            and not re.search(r"\s(?:parameter|get-tuple-element|tuple|"
                              r"bitcast|dynamic-update-slice)\(", l)]
    assert made and all(
        re.search(r"calls=(%[\w.]+)", l).group(1) in updates for l in made), \
        "\n".join(l[:160] for l in made)
    if kind == "bf16":
        staged = jax.eval_shape(lambda: flash_decode.new_kv_stage(
            pools, XL_B, XL_CHUNK))
        assert sum(a.size * a.dtype.itemsize
                   for a in staged) == XL_STAGE_BYTES
        assert re.search(rf"bf16{stage}{{4,3,2,1,0:T\(8,128\)\(2,1\)}}",
                         text), "the stage's layout on the chip changed"
        # the program's temporaries: the stage as laid out, and what
        # the chunk held before it (1.02 GB at the parent, 0.98 of it
        # XLA's relayout of the stacked ``w2``: compile, PR 36)
        assert (compiled.memory_analysis().temp_size_in_bytes
                < XL_STAGE_BYTES_LAID_OUT + (1.1 * 2 ** 30))


def _pallas_grids(jaxpr, found=None):
    """kernel name -> the grids of its pallas_calls, through every
    nested jaxpr (the scans, the write's own jit)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(
                tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_grids(sub, found)
    return found


# -- a second family through the same programs (PR 31) -----------------------


def _flush_is_behind_the_steps(step, state, n_slots, keys):
    """The family's chunk as the GPT-2 one: the attend once a scan body,
    inside the scan over steps and a scan over layers, grid the slots;
    the write ONCE, ``chunk`` tokens a slot over (slot, two pages), in
    the flush's scan over layers alone."""
    jaxpr = jax.make_jaxpr(
        lambda *a: step.func.__wrapped__(*a, **step.keywords))(
            *step.args, state, _s((n_slots,), jnp.int32), keys).jaxpr
    assert _pallas_grids(jaxpr)["paged_kv_write"] == [(n_slots, 2)]
    assert _pallas_grids(jaxpr)["paged_flash_decode_attend"] == [(n_slots,)]
    depths = _loop_depths(jaxpr)
    assert depths["paged_kv_write"] == [1]
    assert depths["paged_flash_decode_attend"] == [2]


LFM2_B, LFM2_PAGES = 64, 1024 + 64                 # + parking pages


def _lfm2_cut():
    """The benchmark's cut of LFM2-24B-A2B at published widths (one
    dense conv layer + two whole periods attn, conv, conv, conv; 64
    experts top 4, GQA 32 / 8 heads of 64), bf16 weights. Shapes only."""
    from mpi_acx_tpu.models import lfm2
    cfg = lfm2.Lfm2Config(
        layer_types=("conv",) + ("full_attention", "conv", "conv",
                                 "conv") * 2, num_dense_layers=1)
    params = jax.eval_shape(lambda: lfm2.cast_params(
        lfm2.init_params(jax.random.key(0), cfg)))
    return lfm2, cfg, params


def test_lfm2_decode_chunk_compiles_and_moves_no_pool(v5e):
    """``paged_decode_chunk`` as a serve call binds it for the LFM2
    cell's geometry (64 slots, page 128): the shared write and the
    live-page walk at ``n_rep`` = 4 on a pool of 8 K/V heads, the expert
    layer's three grouped matmuls as Mosaic calls with the result shapes
    the benchmark's roofline reader matches, no instruction that moves
    a pool or a layer of it, and temporaries far below a chip."""
    lfm2, cfg, params = _lfm2_cut()
    spec = kvpage.paged_spec(lfm2, cfg)
    pool = jax.eval_shape(lambda: kvpage.init_page_pool(
        cfg, LFM2_PAGES - LFM2_B, PAGE, LFM2_B, spec=spec))
    assert pool["k"].shape == (2, LFM2_PAGES, 8, 64, PAGE)
    state = dict(k=pool["k"], v=pool["v"],
                 table=_s((LFM2_B, MAX_LEN // PAGE), jnp.int32),
                 pos=_s((LFM2_B,), jnp.int32), left=_s((LFM2_B,), jnp.int32),
                 held=_s((7, LFM2_B, 2, 2048), jnp.bfloat16),
                 owns=_s((LFM2_B,), jnp.bool_), moe=_s((5,), jnp.int32))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), LFM2_B))
    step = kvpage.make_paged_step_fn(params, cfg, lfm2, XL_CHUNK, PAGE)
    compiled = step.func.lower(
        *_place([*step.args, state, _s((LFM2_B,), jnp.int32), keys], v5e),
        **step.keywords).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([a-z_]+)[.0-9]* = (\(?[a-z0-9]+\[[0-9,]*\])",
                       "\n".join(l for l in text.splitlines()
                                 if "tpu_custom_call" in l))
    assert {n for n, _ in calls} == {"paged_flash_decode_attend",
                                     "paged_kv_write", "gmm"}
    assert ("paged_flash_decode_attend", "bf16[64,8,4,64]") in calls
    assert {r for n, r in calls if n == "gmm"} == {"f32[256,1536]",
                                                   "f32[256,2048]"}
    assert not _pool_movers(text, pool["k"].shape), \
        "\n".join(_pool_movers(text, pool["k"].shape))
    assert not _unfilled_stage(text, [pool["k"], pool["v"]], LFM2_B)
    _flush_is_behind_the_steps(step, state, LFM2_B, keys)
    # nor one that moves a layer's experts: the stacks [2, 64, ...] go to
    # the grouped matmul whole (moe.sorted_expert_ffn, ``layer``); a
    # layer sliced out in front of each call was two thirds of a step
    stack = re.compile(r" = bf16\[(?:2,)?(?:64|128),(?:2048,1536|1536,2048)\]"
                       r".*?\s(?!(?:parameter|get-tuple-element|bitcast)\()"
                       r"[a-z][a-z0-9-]*\(")
    moved = [l.strip()[:160] for l in text.splitlines() if stack.search(l)]
    assert not moved, "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@functools.lru_cache(maxsize=None)
def _compiled_prefill(name, bucket, history, v5e):
    """``serving.paged_prefill`` at ``bucket`` tokens (``history`` 0) or
    ``paged_suffix_prefill`` behind ``history`` cached ones, as a serve
    call binds it for the family's cell (``_lfm2_cut``, ``_jamba``,
    ``_gigachat_cut``, ``_nemotron_cut``), compiled for the described
    chip. One compile a process, whichever test asks first."""
    family, cfg, params = {"lfm2": _lfm2_cut, "jamba": _jamba,
                           "gigachat": _gigachat_cut,
                           "nemotron": _nemotron_cut}[name]()
    fn, args, kw = _prefill_call(family, cfg, params, bucket, history, PAGE)
    return fn.lower(*_place(args, v5e), on_tpu=True, **kw).compile()


def _prefill_call(family, cfg, params, bucket, history, page_tokens):
    """(program, argument shapes, static arguments) of a cold prefill or
    a suffix prefill: what ``serve_paged_greedy`` hands them (the page
    size only where a state layer cuts its tails at page ends, the
    history as ``gather_history`` and ``restore_tail`` return it)."""
    from mpi_acx_tpu.models import serving
    spec = kvpage.paged_spec(family, cfg)
    kw = dict(cfg=cfg, family=family, kv_int8=False,
              page_tokens=page_tokens if spec.n_state_layers else None)
    tokens, last = _s((1, bucket), jnp.int32), _s((), jnp.int32)
    if not history:
        return serving.paged_prefill, [params, tokens, last], kw
    hk = _s((spec.n_page_layers, spec.n_kv_heads, spec.head_dim, history),
            cfg.dtype)
    tail = jax.tree.map(lambda l: _s((spec.n_state_layers,) + l.shape,
                                     l.dtype), spec.state)
    return serving.paged_suffix_prefill, [
        params, tokens, hk, hk if spec.v_dim is None else None, tail,
        last], kw


@pytest.mark.parametrize("bucket", [64, 1024])
def test_lfm2_prefill_compiles_for_v5e(bucket, v5e):
    """``serving.paged_prefill`` for the family at the smallest and the
    largest bucket the cell reaches: the grouped matmuls over 4 x
    bucket sorted rows, and flash attention (K/V heads repeated) at
    1024."""
    compiled = _compiled_prefill("lfm2", bucket, 0, v5e)
    text = compiled.as_text()
    assert f"f32[{4 * bucket},1536]" in text and "%gmm" in text
    assert ("%flash_attention" in text) == (bucket == 1024)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# -- a state that is a matrix a channel (PR 33) -------------------------------

JAMBA_B, JAMBA_PAGES, JAMBA_LEN = 128, 1280 + 128, 1280    # + parking pages
_F32 = jnp.float32


def _jamba():
    """AI21-Jamba2-3B at every published width, bf16 weights. Shapes
    only."""
    from mpi_acx_tpu.models import jamba
    cfg = jamba.jamba2_3b()
    params = jax.eval_shape(lambda: jamba.cast_params(
        jamba.init_params(jax.random.key(0), cfg)))
    return jamba, cfg, params


def _ssm_case(name):
    """(function, argument shapes) at the configuration's widths: 5120
    channels of 16 numbers, 26 layers, 128 slots."""
    from mpi_acx_tpu.ops import ssm
    N, C, bf16 = 16, 5120, jnp.bfloat16
    if name == "update":
        return ssm.ssm_update, [
            _s((26, JAMBA_B, N, C), _F32), _s((), jnp.int32),
            _s((JAMBA_B, C), _F32), _s((JAMBA_B, C), bf16),
            _s((JAMBA_B, C), bf16), _s((JAMBA_B, N), _F32),
            _s((JAMBA_B, N), _F32), _s((N, C), _F32), _s((C,), _F32)]
    S = int(name.split("_")[1])
    return (lambda *a: ssm.ssm_scan(*a, snapshot=512, block=PAGE)), [
        _s((S, C), bf16), _s((S, C), _F32), _s((S, C), bf16),
        _s((S, N), _F32), _s((S, N), _F32), _s((N, C), _F32), _s((C,), _F32),
        _s((N, C), _F32)]


@pytest.mark.parametrize("name", ["update", "scan_64", "scan_512",
                                  "scan_1024"])
def test_ssm_kernels_compile_for_v5e(name, v5e):
    """``ops/ssm.py``'s two Pallas calls at the published widths: the
    update with the 1.09 GB stacked state aliased to its result and the
    layer a prefetched scalar; the scan over a bucket shorter than a
    page, of one snapshot, and of two."""
    fn, args = _ssm_case(name)
    donate = (0,) if name == "update" else ()
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        *_place(args, v5e)).compile()
    text = compiled.as_text()
    kernel = "%ssm_update" if name == "update" else "%ssm_scan"
    assert kernel in text and "tpu_custom_call" in text
    if name == "update":
        # in place: the state is neither copied in front of the call
        # nor allocated a second time behind it
        assert not _state_movers(text)
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    else:
        snaps = int(name.split("_")[1]) // 512
        # (a row more than the snapshots kept; the bucket of 64 hands
        # out none: a call of two results)
        assert (f"f32[{snaps + 1},16,5120]" in text) == bool(snaps)


def _state_movers(text):
    """Instructions of a compiled program that mention an array the
    size of the slots' stacked scan state, or of one layer of it, and
    are anything but plumbing or a Mosaic call; and any ``copy`` of the
    stacked conv windows (XLA's fusions read a layer of those in place
    and write it back in place: a dynamic-slice inside a fusion and a
    dynamic-update-slice as a fusion's root are not moves)."""
    scan_state = re.compile(rf"f32\[(?:26,)?{JAMBA_B},16,5120\]")
    windows = re.compile(rf" = bf16\[26,{JAMBA_B},15360\].*\scopy\(")
    plumbing = {"parameter", "get-tuple-element", "tuple", "while",
                "bitcast", "custom-call"}
    found = []
    for line in text.splitlines():
        op = re.search(r"\s([a-z][a-z0-9-]*)\(", line)
        if " = " not in line or not op:
            continue
        result = line.split(" = ", 1)[1].split(" ", 1)[0]
        if ((scan_state.search(result) and op.group(1) not in plumbing)
                or windows.search(line)):
            found.append(line.strip()[:160])
    return found


def test_state_movers_guard_sees_a_moved_state(v5e):
    """The guard itself: the plain update slices the layer out of the
    stack and puts it back, which it must report."""
    from mpi_acx_tpu.ops import ssm
    _, args = _ssm_case("update")
    text = jax.jit(ssm.ssm_update_ref, donate_argnums=(0,)).lower(
        *_place(args, v5e)).compile().as_text()
    assert _state_movers(text)


def test_jamba_decode_chunk_compiles_and_moves_no_state(v5e):
    """``paged_decode_chunk`` as a serve call binds it for the Jamba
    cell's geometry (128 slots, 1,408 pages of 128 tokens, 14 layers a
    scan body): the shared write and the live-page walk at ONE K/V head
    of 128 under 20 query heads, ``ssm_update`` with the result shape
    the benchmark's reader expects, no instruction that moves a pool,
    the stacked scan state (1.09 GB) or a layer of it, and temporaries
    far below a chip."""
    jamba, cfg, params = _jamba()
    spec = kvpage.paged_spec(jamba, cfg)
    pool = jax.eval_shape(lambda: kvpage.init_page_pool(
        cfg, JAMBA_PAGES - JAMBA_B, PAGE, JAMBA_B, spec=spec))
    assert pool["k"].shape == (2, JAMBA_PAGES, 1, 128, PAGE)
    assert set(pool) == {"k", "v"}
    held = jax.tree.map(lambda l: _s((26, JAMBA_B) + l.shape, l.dtype),
                        spec.state)
    assert held["ssm"].shape == (26, JAMBA_B, 16, 5120)
    assert held["conv"].shape == (26, JAMBA_B, 3 * 5120)
    state = dict(k=pool["k"], v=pool["v"],
                 table=_s((JAMBA_B, JAMBA_LEN // PAGE), jnp.int32),
                 pos=_s((JAMBA_B,), jnp.int32),
                 left=_s((JAMBA_B,), jnp.int32), held=held)
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                   JAMBA_B))
    step = kvpage.make_paged_step_fn(params, cfg, jamba, XL_CHUNK, PAGE)
    compiled = step.func.lower(
        *_place([*step.args, state, _s((JAMBA_B,), jnp.int32), keys], v5e),
        **step.keywords).compile()
    text = compiled.as_text()
    calls = set(re.findall(r"%([a-z_]+)[.0-9]* = (\(?[a-z0-9]+\[[0-9,]*\])",
                           "\n".join(l for l in text.splitlines()
                                     if "tpu_custom_call" in l)))
    assert calls == {("paged_flash_decode_attend", "bf16[128,1,20,128]"),
                     ("paged_kv_write", "(bf16[2,1408,1,128,128]"),
                     ("ssm_update", "(f32[128,5120]")}
    # 13 Mamba layers a scan body, each its own call on the whole stack
    assert len(re.findall(r"%ssm_update[.0-9]* = ", text)) == 13
    assert not _pool_movers(text, pool["k"].shape)
    assert not _unfilled_stage(text, [pool["k"], pool["v"]], JAMBA_B)
    _flush_is_behind_the_steps(step, state, JAMBA_B, keys)
    assert not _state_movers(text), "\n".join(_state_movers(text))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("bucket", [64, 1024])
def test_jamba_prefill_compiles_for_v5e(bucket, v5e):
    """``serving.paged_prefill`` for the family at the smallest and the
    largest bucket the cell reaches: ``ssm_scan`` (two snapshots at
    1024), and flash attention (the one K/V head repeated) at 1024."""
    compiled = _compiled_prefill("jamba", bucket, 0, v5e)
    text = compiled.as_text()
    assert "%ssm_scan" in text and f"f32[{bucket},5120]" in text
    assert ("%flash_attention" in text) == (bucket == 1024)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("family", ["gpt2", "lfm2"])
def test_the_chunk_stages_every_family_alike(family, monkeypatch):
    """One path, adapted to nothing but the shapes it is handed: the
    chunk program of a tiny GPT-2 and of a tiny LFM2 (off the chip, the
    dense pair) carries through its scan over steps ONE stage of ``[page
    layers, slots, chunk, K/V heads, 2 x head]``, hands no stage back,
    and a step by itself carries none."""
    from mpi_acx_tpu.models import lfm2
    monkeypatch.setattr(backend, "on_tpu", lambda: False)
    if family == "lfm2":
        fam, cfg = lfm2, lfm2.tiny_lfm2()
        params = lfm2.cast_params(lfm2.init_params(jax.random.key(0), cfg))
    else:
        fam, cfg = None, tfm.tiny_config()
        params = tfm.init_params(jax.random.key(0), cfg)
    state = kvpage.PagedKV(cfg, fam, 4, 128, 16, 32).device_state()
    tok, keys = jnp.zeros((4,), jnp.int32), jax.random.split(
        jax.random.key(0), 4)
    chunk = jax.make_jaxpr(
        lambda p, s, t, k: kvpage.paged_decode_chunk.__wrapped__(
            p, s, t, k, cfg=cfg, chunk=3, page_tokens=16, on_tpu=False,
            family=fam))(params, state, tok, keys)
    L, _, H, Dh, _ = state["k"].shape
    staged = (L, 4, 3, H, 2 * Dh)
    steps = [e for e in chunk.jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == 3]
    assert len(steps) == 1
    carried = [v.aval.shape for v in steps[0].outvars]
    assert carried.count(staged) == 1
    assert staged not in [v.aval.shape for v in chunk.jaxpr.outvars]
    one = jax.make_jaxpr(lambda p, s, t: kvpage.paged_decode_step(
        p, cfg, s, t, 16, fam))(params, state, tok)
    assert "stage" not in jax.eval_shape(
        lambda p, s, t: kvpage.paged_decode_step(p, cfg, s, t, 16, fam)[1],
        params, state, tok)
    assert staged not in [v.aval.shape for e in one.jaxpr.eqns
                          for v in e.outvars]


# -- a latent page pool read by 64 heads (PR 40) ------------------------------

GIGA_B, GIGA_PAGES, GIGA_LEN = 64, 2560 + 64, 8576         # + parking pages


def _gigachat_cut():
    """The benchmark's cut of GigaChat3.1-702B-A36B at published widths
    (one dense layer + five expert layers, 16 of 256 experts held, 1/8
    of the vocabulary), bf16 weights. Shapes only."""
    from mpi_acx_tpu.models import gigachat
    cfg = gigachat.GigaChatConfig(vocab=16032, n_layers=6, first_k_dense=1,
                                  experts_held=16)
    params = jax.eval_shape(lambda: gigachat.cast_params(
        gigachat.init_params(jax.random.key(0), cfg)))
    return gigachat, cfg, params


def test_gigachat_decode_chunk_compiles_and_moves_no_pool(v5e):
    """``paged_decode_chunk`` as a serve call binds it for the cell's
    geometry (64 slots of 67 pages, 2,624 latent pages of ``[1, 576,
    128]``): the walk's fold of 64 query rows of 576 against a page and
    ``P @ V`` against the same block's first 512 sublanes is taken by
    Mosaic, with the result shape the benchmark's reader matches; ONE
    pool goes through the write; the held experts' grouped matmuls are
    Mosaic calls with the shapes their reader matches; no instruction
    moves the pool or a layer of it, nor the expert stacks; the stage is
    ``[6, 64, 32, 576]`` in HBM; temporaries far below a chip."""
    gigachat, cfg, params = _gigachat_cut()
    spec = kvpage.paged_spec(gigachat, cfg)
    pool = jax.eval_shape(lambda: kvpage.init_page_pool(
        cfg, GIGA_PAGES - GIGA_B, PAGE, GIGA_B, spec=spec))
    assert set(pool) == {"k"}
    assert pool["k"].shape == (6, GIGA_PAGES, 1, 576, PAGE)
    state = dict(k=pool["k"], table=_s((GIGA_B, GIGA_LEN // PAGE), jnp.int32),
                 pos=_s((GIGA_B,), jnp.int32), left=_s((GIGA_B,), jnp.int32),
                 owns=_s((GIGA_B,), jnp.bool_), moe=_s((7,), jnp.int32))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), GIGA_B))
    step = kvpage.make_paged_step_fn(params, cfg, gigachat, XL_CHUNK, PAGE)
    compiled = step.func.lower(
        *_place([*step.args, state, _s((GIGA_B,), jnp.int32), keys], v5e),
        **step.keywords).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([a-z_]+)[.0-9]* = (\(?[a-z0-9]+\[[0-9,]*\])",
                       "\n".join(l for l in text.splitlines()
                                 if "tpu_custom_call" in l))
    assert {n for n, _ in calls} == {"paged_flash_decode_attend",
                                     "paged_kv_write", "gmm"}
    assert ("paged_flash_decode_attend", "bf16[64,1,64,512]") in calls
    assert ("paged_kv_write", "bf16[6,2624,1,576,128]") in calls
    assert {r for n, r in calls if n == "gmm"} == {"f32[512,2048]",
                                                   "f32[512,7168]"}
    sized = re.compile(r" = [a-z0-9]+\[(?:6,)?2624,1,576,128\].*?"
                       r"\s(?!(?:parameter|get-tuple-element|tuple|while|"
                       r"bitcast|custom-call)\()[a-z][a-z0-9-]*\(")
    moved = [l.strip()[:160] for l in text.splitlines() if sized.search(l)]
    assert not moved, "\n".join(moved)
    stack = re.compile(r" = bf16\[(?:5,)?(?:16|80),(?:7168,2048|2048,7168)\]"
                       r".*?\s(?!(?:parameter|get-tuple-element|bitcast)\()"
                       r"[a-z][a-z0-9-]*\(")
    moved = [l.strip()[:160] for l in text.splitlines() if stack.search(l)]
    assert not moved, "\n".join(moved)
    # the attend once a SEGMENT's scan body (the dense layer's, the
    # expert layers'), the write once, in the flush's scan alone
    jaxpr = jax.make_jaxpr(
        lambda *a: step.func.__wrapped__(*a, **step.keywords))(
            *step.args, state, _s((GIGA_B,), jnp.int32), keys).jaxpr
    assert _pallas_grids(jaxpr)["paged_kv_write"] == [(GIGA_B, 2)]
    assert _pallas_grids(jaxpr)["paged_flash_decode_attend"] == [
        (GIGA_B,)] * 2
    depths = _loop_depths(jaxpr)
    assert depths["paged_kv_write"] == [1]
    assert depths["paged_flash_decode_attend"] == [2, 2]
    assert re.search(r"bf16\[6,64,32,576\]{3,2,1,0:T\(8,128\)\(2,1\)}", text)
    assert not _unfilled_stage(text, [pool["k"]], GIGA_B, spec.v_dim)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("bucket,history", [(8192, 0), (64, 7936),
                                            (256, 7936)])
def test_gigachat_prefill_compiles_for_v5e(bucket, history, v5e):
    """``serving.paged_prefill`` at the cell's ONE cold bucket and
    ``paged_suffix_prefill`` at its smallest and largest suffix bucket
    behind 62 hit pages: the rows-attention kernel at head width 192
    (K/V streamed a tile a grid step), the expert layer a block of 2,048
    tokens at a time, and temporaries that fit beside 10.35 GB of
    weights and 2.3 GB of pages."""
    compiled = _compiled_prefill("gigachat", bucket, history, v5e)
    text = compiled.as_text()
    assert f"%flash_rows_attention" in text
    assert f" = bf16[64,{bucket},192]" in text
    assert f"f32[{8 * min(bucket, 2048)},2048]" in text and "%gmm" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6 * 2 ** 30


# -- layers that are ONE mixer; a state of 3-D heads; latent experts (PR 44) --

NEMO_B, NEMO_PAGES, NEMO_LEN = 96, 1536 + 96, 5120          # + parking pages
_NEMO_STATE = (128, 64, 128)


def _nemotron_cut():
    """The benchmark's cut of Nemotron-3-Super at published widths: one
    chip of four of stage 0 (MEMEMEM*EME, 128 of the router's 512
    experts, a quarter of the vocabulary), bf16 weights. Shapes only."""
    from mpi_acx_tpu.models import nemotron_h
    cfg = nemotron_h.NemotronHConfig(vocab=32768, pattern="MEMEMEM*EME",
                                     experts_held=128)
    params = jax.eval_shape(lambda: nemotron_h.cast_params(
        nemotron_h.init_params(jax.random.key(0), cfg)))
    return nemotron_h, cfg, params


def _ssd_case(name):
    """(function, argument shapes) at the published widths: 128 heads of
    64 values x 128 state numbers in 8 groups, 5 layers, 96 slots."""
    from mpi_acx_tpu.ops import ssd
    (H, P, N), G, bf16 = _NEMO_STATE, 8, jnp.bfloat16
    if name.startswith("update"):
        # (told which slots are live, or told nothing: one kernel)
        return ssd.ssd_update, [
            _s((5, NEMO_B, H, P, N), _F32), _s((), jnp.int32),
            _s((NEMO_B, H), _F32), _s((NEMO_B, H, P), bf16),
            _s((NEMO_B, G, N), bf16), _s((NEMO_B, G, N), bf16),
            _s((H,), _F32)] + [_s((NEMO_B,), jnp.bool_)] * (name != "update")
    S = int(name.split("_")[1])
    return (lambda *a: ssd.ssd_scan(*a, snapshot=512, chunk=PAGE)), [
        _s((S, H, P), bf16), _s((S, H), _F32), _s((S, G, N), bf16),
        _s((S, G, N), bf16), _s((H,), _F32), _s((H, P, N), _F32)]


def _ssd_state_movers(text):
    """Instructions of a compiled program that mention the slots'
    stacked Mamba-2 state (2.06 GB) or one layer of it and are anything
    but plumbing or a Mosaic call."""
    sized = re.compile(rf"f32\[(?:5,)?{NEMO_B},128,64,128\]")
    plumbing = {"parameter", "get-tuple-element", "tuple", "while",
                "bitcast", "custom-call"}
    found = []
    for line in text.splitlines():
        op = re.search(r"\s([a-z][a-z0-9-]*)\(", line)
        if (" = " in line and op and op.group(1) not in plumbing
                and sized.search(line.split(" = ", 1)[1].split("(")[0])):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("name", ["update", "update_live", "scan_32",
                                  "scan_512", "scan_5120"])
def test_ssd_kernels_compile_for_v5e(name, v5e):
    """``ops/ssd.py``'s two Pallas calls at the published widths: the
    update with the 2.06 GB stacked state aliased to its result and the
    layer a prefetched scalar (a lane rotated to a group's heads, one
    lane a head broadcast down a ``[64, 128]`` state, the read-out one
    product a group), told nothing of the slots and told which are live
    (the visiting order a second prefetched vector, a result block the
    steps behind the live slots come back to); the chunked scan over a
    bucket shorter than a chunk (padded), of one snapshot, and of the
    cold bucket's ten."""
    fn, args = _ssd_case(name)
    update = name.startswith("update")
    compiled = jax.jit(fn, donate_argnums=(0,) if update else ()).lower(
        *_place(args, v5e)).compile()
    text = compiled.as_text()
    kernel = "%ssd_update" if update else "%ssd_scan"
    assert kernel in text and "tpu_custom_call" in text
    if update:
        # in place: the state is neither copied in front of the call
        # nor allocated a second time behind it
        assert not _ssd_state_movers(text), "\n".join(_ssd_state_movers(text))
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    else:
        S = int(name.split("_")[1])
        # whole chunks; a row more than the snapshots kept
        assert f"f32[{-(-S // PAGE) * PAGE},8192]" in text
        assert (f"f32[{S // 512 + 1},128,64,128]" in text) == bool(S // 512)


def test_nemotron_decode_chunk_compiles_and_moves_no_state(v5e):
    """``paged_decode_chunk`` as a serve call binds it for the Nemotron
    cell's geometry (96 slots, 1,632 pages of 128 tokens, layers that are
    a mixer OR an expert layer alone: three ``ssd_update`` and three
    pairs of grouped matmuls in the program, one of each inside the
    ``(M E) x 3`` scan): the shared write and the live-page walk at 2
    K/V heads of 128 under 32 query heads, the latent experts' TWO
    grouped matmuls with the result shapes the benchmark's reader
    matches (2,112 pairs padded to 17 row tiles), no instruction that
    moves a pool, the stacked state (2.06 GB), a layer of it, or an
    expert stack, and temporaries far below a chip. The state carries
    ``left`` as a serve call's does: the update is told which slots
    live at each step, and is still one call a Mamba-2 layer. (With ONE layer of
    pages XLA keeps the stage's zero fill as a select on the chunk's
    first step, inside the loop: ``chip_smoke.py`` holds the served
    tokens to the dense pair's.)"""
    nemotron_h, cfg, params = _nemotron_cut()
    spec = kvpage.paged_spec(nemotron_h, cfg)
    pool = jax.eval_shape(lambda: kvpage.init_page_pool(
        cfg, NEMO_PAGES - NEMO_B, PAGE, NEMO_B, spec=spec))
    assert pool["k"].shape == (1, NEMO_PAGES, 2, 128, PAGE)
    held = jax.tree.map(lambda l: _s((5, NEMO_B) + l.shape, l.dtype),
                        spec.state)
    assert held["ssm"].shape == (5, NEMO_B) + _NEMO_STATE
    assert held["conv"].shape == (5, NEMO_B, 3 * 10240)
    state = dict(k=pool["k"], v=pool["v"],
                 table=_s((NEMO_B, NEMO_LEN // PAGE), jnp.int32),
                 pos=_s((NEMO_B,), jnp.int32),
                 left=_s((NEMO_B,), jnp.int32), held=held,
                 state_dead=_s((), jnp.int32),
                 owns=_s((NEMO_B,), jnp.bool_), moe=_s((7,), jnp.int32))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                   NEMO_B))
    step = kvpage.make_paged_step_fn(params, cfg, nemotron_h, XL_CHUNK, PAGE)
    compiled = step.func.lower(
        *_place([*step.args, state, _s((NEMO_B,), jnp.int32), keys], v5e),
        **step.keywords).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([a-z_]+)[.0-9]* = (\(?[a-z0-9]+\[[0-9,]*\])",
                       "\n".join(l for l in text.splitlines()
                                 if "tpu_custom_call" in l))
    assert sorted(calls) == sorted(
        [("ssd_update", "(f32[96,64,128]")] * 3
        + [("gmm", "f32[2176,2688]"), ("gmm", "f32[2176,1024]")] * 3
        + [("paged_flash_decode_attend", "bf16[96,2,16,128]"),
           ("paged_kv_write", "(bf16[1,1632,2,128,128]")])
    assert not _pool_movers(text, pool["k"].shape)
    _flush_is_behind_the_steps(step, state, NEMO_B, keys)
    assert not _ssd_state_movers(text), "\n".join(_ssd_state_movers(text))
    stack = re.compile(r" = bf16\[(?:[13],)?128,(?:1024,2688|2688,1024)\]"
                       r".*?\s(?!(?:parameter|get-tuple-element|bitcast)\()"
                       r"[a-z][a-z0-9-]*\(")
    moved = [l.strip()[:160] for l in text.splitlines() if stack.search(l)]
    assert not moved, "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("bucket,history", [(5120, 0), (256, 4096)])
def test_nemotron_prefill_compiles_for_v5e(bucket, history, v5e):
    """``serving.paged_prefill`` at the cell's ONE cold bucket (8,192
    capped at ``max_len``) and ``paged_suffix_prefill`` behind 32 hit
    pages and a restored snapshot: ``ssd_scan`` over whole chunks, the
    latent experts a block of 1,024 tokens at a time (22 sorted rows a
    token), flash attention on the cold bucket, and temporaries that fit
    beside 9.3 GB of weights, 2.04 GB of state, 1.36 GB of snapshots and
    0.2 GB of pages."""
    compiled = _compiled_prefill("nemotron", bucket, history, v5e)
    text = compiled.as_text()
    assert "%ssd_scan" in text and f"f32[{bucket},8192]" in text
    rows = 22 * min(bucket, 1024)
    assert f"f32[{rows},2688]" in text and f"f32[{rows},1024]" in text
    assert ("%flash_attention" in text) == (not history)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.4e9


# sha256 (16 hex) of each family's decode chunk as a jaxpr, off the chip
# (the dense pair), tiny preset, 2 slots, pages of 16, chunk 4: read at
# PR 44's parent commit and again on its tree (``nemotron``: at PR 47's
# parent). To read them again: ``_chunk_digest(name)`` below, in a
# checkout of the commit to pin.
_CHUNK_DIGESTS = {"gpt2": "b81a421f78dcef1f", "lfm2": "ddd3e63f20a11fc1",
                  "jamba": "959ebaf1b15f03f0", "gigachat": "0b576a3c3712b233",
                  "nemotron": "4a9f96af35cdefa8"}


def _tiny(name):
    from mpi_acx_tpu.models import gigachat, jamba, lfm2, nemotron_h
    return {
        "gpt2": lambda: (None, tfm.tiny_config()),
        "lfm2": lambda: (lfm2, lfm2.tiny_lfm2()),
        "jamba": lambda: (jamba, jamba.tiny_jamba()),
        "gigachat": lambda: (gigachat, gigachat.tiny_gigachat(
            experts_first=4, experts_held=4)),
        "nemotron": lambda: (nemotron_h, nemotron_h.tiny_nemotron(
            experts_first=4, experts_held=4))}[name]()


def _jaxpr_digest(fn, *args):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", str(
        jax.make_jaxpr(fn)(*args))).encode()).hexdigest()[:16]


def _chunk_digest(name):
    family, cfg = _tiny(name)
    fam = family or tfm
    params = fam.cast_params(fam.init_params(jax.random.key(0), cfg))
    pkv = kvpage.PagedKV(cfg, family, 2, 64, 16, 8)
    state = jax.eval_shape(
        lambda: pkv.device_state(jnp.zeros((2,), jnp.int32)))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2))
    return _jaxpr_digest(
        lambda p, s, t, k: kvpage.paged_decode_chunk.__wrapped__(
            p, s, t, k, cfg=cfg, chunk=4, page_tokens=16, on_tpu=False,
            family=family), params, state, _s((2,), jnp.int32), keys)


@pytest.mark.parametrize("name", sorted(_CHUNK_DIGESTS))
def test_the_other_families_chunk_programs_are_the_parents(name,
                                                           monkeypatch):
    """What PR 44 taught the shared plane (a ``LayerKind`` with no FFN or
    no operator, ``sorted_expert_ffn(w3=None)``, a stage of one layer
    rewritten whole) is branches in Python on what a family's spec and
    shapes say: the decode chunk GPT-2, LFM2, Jamba and GigaChat trace
    is, equation for equation, the one they traced at the parent (and
    since PR 47, whose shared split of a segment's leaves is the one
    place it touched the step, Nemotron's). A PR that MEANS to change a
    family's chunk reads the digest anew."""
    monkeypatch.setattr(backend, "on_tpu", lambda: False)
    assert _chunk_digest(name) == _CHUNK_DIGESTS[name]


# The four families' prefill and suffix-prefill programs
# (``serving.paged_prefill`` / ``paged_suffix_prefill``), read at PR 47's
# parent commit, where each family had its own whole-sequence pass; PR 47
# gave them ``kvpage.sequence_pass``. ``jaxpr``: sha256 (16 hex) of the
# program as a jaxpr, off the chip, tiny preset, one bucket of 32 tokens,
# behind 64 cached ones for a suffix, pages of 16: LFM2's pair, whose
# trace the shared pass keeps equation for equation. ``(bucket,
# history)``: of the program compiled for the described v5e at the cell's
# widths (``_compiled_prefill``), as ``_program_text`` reads it: the
# others', whose traces gained an equation that the compiler drops
# (Jamba's and Nemotron's attention takes no positions and the shared
# prefill makes them: an ``iota`` nobody reads) or moved a reshape of
# unit axes (GigaChat's rows pass the shared ``_by_layer``).
_PREFILL_DIGESTS = {
    ("lfm2", "prefill"): ("jaxpr", "0172d1da6ccceb7a"),
    ("lfm2", "suffix"): ("jaxpr", "07fcc5b266886a54"),
    ("jamba", "prefill"): ((1024, 0), "056b819ec172144b"),
    ("jamba", "suffix"): ((64, 1024), "7406bdf0e954829a"),
    ("gigachat", "prefill"): ((8192, 0), "8bc02fb9d8dc93de"),
    ("gigachat", "suffix"): ((256, 7936), "094a76496047bbe5"),
    ("nemotron", "prefill"): ((5120, 0), "836fa8fa594bb4db"),
    ("nemotron", "suffix"): ((256, 4096), "eaa8c29be2976f83")}


def _program_text(compiled):
    """A compiled program's optimized HLO with what is not the program
    taken out: the tables of source files and stack frames, each
    instruction's ``metadata``, addresses, the Mosaic kernels' serialized
    bodies (they hold their callers' file names and line numbers:
    ROADMAP.md, Queue 3) and the numbering of instruction names, which
    differs between two compiles of one commit."""
    text = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(.+\n)*", "", compiled.as_text())
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r'"body":\s*"[^"]*"', '"body":""', text)
    return re.sub(r"\b([A-Za-z_][\w\-]*?)(?:\.clone|\.\d+)+\b", r"\1",
                  text)


@pytest.mark.parametrize("name,program", sorted(_PREFILL_DIGESTS))
def test_the_families_prefill_programs_are_the_parents(name, program, v5e,
                                                       monkeypatch):
    """The whole-sequence pass written once (``kvpage.sequence_pass``,
    ``kvpage.prefill``) runs each family's operators in the order its own
    copy ran them: the eight programs are the parent's. A PR that MEANS
    to change a family's prefill reads its digest anew (``jaxpr``: in a
    checkout of the commit to pin; compiled: twice, two compiles of one
    commit must agree)."""
    at, want = _PREFILL_DIGESTS[name, program]
    if at != "jaxpr":
        got = hashlib.sha256(_program_text(_compiled_prefill(
            name, *at, v5e)).encode()).hexdigest()[:16]
    else:
        monkeypatch.setattr(backend, "on_tpu", lambda: False)
        family, cfg = _tiny(name)
        params = jax.eval_shape(lambda: family.cast_params(
            family.init_params(jax.random.key(0), cfg)))
        fn, args, kw = _prefill_call(family, cfg, params, 32,
                                     64 * (program == "suffix"), 16)
        got = _jaxpr_digest(
            lambda *a: fn.__wrapped__(*a, on_tpu=False, **kw), *args)
    assert got == want


# The training cell's WHOLE step (``benchmarks/entries/train_step_optax``:
# GPT-2 medium, AdamW, remat, chunked cross-entropy, a dp1 x pp1 x tp1
# mesh) at ONE micro-batch of 8 rows: a ``tp`` axis of one runs no ring.

def _train_step_text(v5e, n_micro=1):
    import json

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmarks import harness, weights
    from mpi_acx_tpu.train import make_train_step_optax
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "gpt2_medium_train.json")) as f:
        c = json.load(f)
    tr, o = c["train"], c["train"]["optimizer"]
    (device,) = v5e.device_set
    mesh = Mesh(np.array([device]).reshape(1, 1, 1), ("dp", "pp", "tp"))
    opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"])
    step, n_stages = make_train_step_optax(
        harness.gpt2_program_config(c, c["compute_dtype"]), mesh, n_micro,
        opt, remat=tr["remat"], xent_chunk=tr["xent_chunk"])
    params = jax.eval_shape(lambda: tfm.stage_slice(
        weights.make_gpt2(c, 1, jnp.dtype(c["weights_dtype"])), n_stages))
    placed = _place((params, jax.eval_shape(opt.init, params),
                     _s((n_micro, 8, c["n_positions"]), jnp.int32)),
                    NamedSharding(mesh, PartitionSpec()))
    return c, step.lower(*placed, placed[2]).compile().as_text()


def test_the_train_step_at_tp_one_runs_no_ring(v5e):
    """The compiled step sends nothing (the one ``collective-permute``
    left has NO source-target pair: the pipeline's, at a ``pp`` axis of
    one, once a micro-batch), branches on nothing, and its attention
    kernels sit in the two loops over the layers: the forward kernel
    once in the forward loop and NOT in the backward loop (the remat
    layer keeps its ``o`` and ``lse``), where the one ``%attn_bwd``
    is: 1 x layers and 1 x layers calls a micro-batch, under the names
    the benchmark's readers match."""
    c, text = _train_step_text(v5e)
    sends = re.findall(r"collective-permute-start\(.*?source_target_pairs="
                       r"\{(.*?)\}[,\s]", text)
    assert all(pairs == "" for pairs in sends) and len(sends) <= 1, sends
    assert not re.search(r"\sconditional\(", text)

    where, comp = {}, None           # kernel instruction -> its computation
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
        elif "tpu_custom_call" in line and " custom-call(" in line:
            where[line.split(" = ")[0].strip().removeprefix("ROOT ")] = comp
    fwd = [k for k in where if k.startswith("%flash_attention")]
    bwd = [k for k in where if k.startswith("%attn_bwd")]
    assert len(fwd) == 1 and len(bwd) == 1 and len(where) == 2, where
    assert not any("lse" in k for k in fwd), fwd     # the direct entry

    def trips(body):
        """The constant a ``while`` over ``body`` counts to."""
        (cond,) = re.findall(r" while\(.*condition=(%[\w.\-]+), body="
                             + re.escape(body) + r"[,\s]", text)
        block = text.split("\n" + cond + " (")[1].split("\n}\n")[0]
        (n,) = re.findall(r"s32\[\][^=]* constant\((\d+)\)", block)
        return int(n)

    loops = [where[fwd[0]], where[bwd[0]]]
    assert loops[0] != loops[1], where
    assert [trips(b) for b in loops] == [c["n_layer"]] * 2
    # The kept ``o`` is stacked over the layers as [B, S, H*D] rows: as
    # the kernel's [B, H, S, D] its 64-wide heads are padded to 128
    # lanes, twice the bytes (3.2 GB of the cell's four micro-batches).
    L, H, S = c["n_layer"], c["n_head"], c["n_positions"]
    D = c["n_embd"] // H
    assert f"bf16[{L},8,{H},{S},{D}]" not in text
    assert f"bf16[{L},8,{S},{H * D}]" in text


# -- a step that yields a block, not a token (PR 48) --------------------------

SDAR_B, SDAR_LEN, SDAR_CHUNK = 96, 1408, 16
SDAR_PAGES = SDAR_B * SDAR_LEN // PAGE + SDAR_B             # + parking pages


def _sdar_cut():
    """The benchmark's cut of SDAR-30B-A3B-Chat at published widths: one
    pipeline stage of eight (6 of 48 layers, every one of the 128
    experts, the whole vocabulary), bf16 weights. Shapes only."""
    from mpi_acx_tpu.models import sdar
    cfg = sdar.SdarConfig(n_layers=6)
    params = jax.eval_shape(lambda: sdar.cast_params(
        sdar.init_params(jax.random.key(0), cfg)))
    return sdar, cfg, params


def _sdar_chunk(v5e):
    sdar, cfg, params = _sdar_cut()
    spec = kvpage.paged_spec(sdar, cfg)
    pool = jax.eval_shape(lambda: kvpage.init_page_pool(
        cfg, SDAR_PAGES - SDAR_B, PAGE, SDAR_B, spec=spec))
    state = dict(k=pool["k"], v=pool["v"],
                 table=_s((SDAR_B, SDAR_LEN // PAGE), jnp.int32),
                 pos=_s((SDAR_B,), jnp.int32), left=_s((SDAR_B,), jnp.int32),
                 owns=_s((SDAR_B,), jnp.bool_), moe=_s((5,), jnp.int32))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), SDAR_B))
    step = kvpage.make_paged_step_fn(params, cfg, sdar, SDAR_CHUNK, PAGE)
    args = [*step.args, state, _s((SDAR_B, 4), jnp.int32), keys]
    return step, args, pool


def test_sdar_block_chunk_compiles_and_moves_no_pool(v5e):
    """``paged_decode_chunk``'s block arm as a serve call binds it for
    the SDAR cell's geometry (96 slots, 1,152 pages of 128 tokens, chunk
    16 = 4 blocks of 4 positions, 4 denoising forwards and a storing one
    a block): ONE copy of the layers in the program (the five forwards
    of a block are turns of one scan, the head under a conditional), the
    attend the token step's call with a block's 4 x 8 query rows a K/V
    head folded into one position's 32 (``bf16[96,4,32,128]``), the
    expert layer's three grouped matmuls at 96 x 4 x 8 = 3,072 rows with
    the result shapes the benchmark's reader matches, the flush ONE
    write a layer behind the blocks, a filled stage, no instruction that
    moves a pool or an expert stack, and temporaries far below a chip
    (the logits of a forward are 0.23 GB)."""
    step, args, pool = _sdar_chunk(v5e)
    assert pool["k"].shape == (6, SDAR_PAGES, 4, 128, PAGE)
    compiled = step.func.lower(*_place(args, v5e), **step.keywords).compile()
    text = compiled.as_text()
    calls = re.findall(r"%([a-z_]+)[.0-9]* = (\(?[a-z0-9]+\[[0-9,]*\])",
                       "\n".join(l for l in text.splitlines()
                                 if "tpu_custom_call" in l))
    assert sorted(calls) == sorted(
        [("gmm", "f32[3072,768]")] * 2 + [("gmm", "f32[3072,2048]"),
         ("paged_flash_decode_attend", "bf16[96,4,32,128]"),
         ("paged_kv_write", "(bf16[6,1152,4,128,128]")])
    assert not _pool_movers(text, pool["k"].shape), \
        "\n".join(_pool_movers(text, pool["k"].shape))
    made = jax.eval_shape(lambda: flash_decode.new_kv_stage(
        [pool["k"], pool["v"]], SDAR_B, SDAR_CHUNK))
    shapes = ["[" + ",".join(map(str, a.shape)) + "]" for a in made]
    unfilled = [l.strip()[:160] for l in text.splitlines()
                if "AllocateBuffer" in l
                and any(s in l.split(" custom-call(")[0] for s in shapes)]
    assert not unfilled, "\n".join(unfilled)
    stack = re.compile(r" = bf16\[(?:6,)?(?:128|768),(?:2048,768|768,2048)\]"
                       r".*?\s(?!(?:parameter|get-tuple-element|bitcast)\()"
                       r"[a-z][a-z0-9-]*\(")
    moved = [l.strip()[:160] for l in text.splitlines() if stack.search(l)]
    assert not moved, "\n".join(moved)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    # the flush is behind the blocks: the write outside every loop, the
    # attend under blocks x forwards x layers
    jaxpr = jax.make_jaxpr(functools.partial(
        step.func.__wrapped__, **step.keywords))(*args).jaxpr
    depths = _loop_depths(jaxpr)
    assert depths["paged_kv_write"] == [1]
    assert depths["paged_flash_decode_attend"] == [3]


@pytest.mark.parametrize("bucket", [64, 512, 1024])
def test_sdar_prefill_compiles_for_v5e(bucket, v5e):
    """``serving.paged_prefill`` for the family at the smallest bucket
    the cell reaches, the largest that is attended densely and the one
    that takes the flash kernel (the causal kernel with its logsumexp,
    merged with the columns after a row inside its block): the grouped
    matmuls over 8 x bucket sorted rows, NO head (no ``[1, 151936]``
    logits: a block family's prefill hands out no token), and
    temporaries that fit beside 8.7 GB of weights and 1.8 GB of
    pages."""
    sdar, cfg, params = _sdar_cut()
    fn, args, kw = _prefill_call(sdar, cfg, params, bucket, 0, PAGE)
    compiled = fn.lower(*_place(args, v5e), on_tpu=True, **kw).compile()
    text = compiled.as_text()
    assert f"f32[{8 * bucket},768]" in text and "%gmm" in text
    assert ("%flash_attention_lse" in text) == (bucket == 1024)
    assert "f32[1,1,151936]" not in text and ",151936]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
