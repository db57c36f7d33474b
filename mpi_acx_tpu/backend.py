"""What this process runs on, and where its compiled programs are kept.

Facts every entry point and every kernel needs, each defined once:

* :func:`on_tpu` — THE answer to "is the default backend a TPU". The
  Pallas kernels compile when it is true and run in interpret mode when
  it is not; the flash/dense auto policies read it too. Callers go
  through the module (``backend.on_tpu()``), so tests that compile the
  kernels for a described chip patch this one name.
* :func:`enable_compile_cache` — JAX's persistent compilation cache, placed
  from outside by ``JAX_COMPILATION_CACHE_DIR`` or else at one fixed
  directory inside the checkout. The directory is part of the cache key:
  it never comes from a temporary name, a pid or the time, so every
  process of one run (chip_smoke.py's children, the examples) finds what an earlier one compiled.
* :func:`jit_bound` — how a closure hands the weights to a compiled
  program: as arguments, never as constants baked into it.
"""

from __future__ import annotations

import os

import jax

from mpi_acx_tpu import profiling

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def jit_bound(fn, *bound, **jit_kwargs):
    """``jax.jit(fn)`` whose leading arguments are ``bound`` at every
    call: ``jit_bound(f, params)(x)`` runs ``f(params, x)``. The serve
    loops' closures use it so that the weights travel as ARGUMENTS. A
    jitted function that merely closes over them gets them baked into
    the executable as constants — on the chip that was a 400 MB
    executable per program, a copy of the weights in HBM for each, a
    minute or two of compile each, and entries too large for the
    persistent cache to keep. ``donate_argnums`` etc. count ``bound``."""
    jitted = jax.jit(fn, **jit_kwargs)
    return lambda *args: jitted(*bound, *args)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX has already read it and no directory is set here. The process's
    log of the programs it traces, lowers and loads starts here too
    (``profiling.program_log``): an entry point calls this before its
    first program."""
    profiling.install_program_listener()
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # The serve loops jit many sub-second programs (scatter, gather,
    # per-bucket prefill); cache those too, not only the slow ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entries(path: str) -> int:
    """Number of compiled programs under ``path`` (0 if absent)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
