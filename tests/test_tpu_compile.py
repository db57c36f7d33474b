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
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
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
