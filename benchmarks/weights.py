"""GPT-2 weights from ``--seed``, made by the benchmark, on the device,
in one jitted call and in the type they are used in.

The tree has the layout the program's GPT-2 family reads
(``mpi_acx_tpu.models.transformer``): per-layer tensors stacked on a
leading ``[n_layer]`` axis, ``wqkv`` as ``[d, 3d]`` (GPT-2's ``c_attn``),
tied embedding. The plain reference (``reference/gpt2.py``) reads the
same tree, so program and reference see the same numbers and neither
takes anything the other made.

Why this is not ``cast_params(init_params(...))`` of the program, as
ISSUE 23 had it: the reference may take nothing that the program has
made, weights least of all (an ``init_params`` that a later PR changes
would move program and reference together, unseen), and the program's
init makes the float32 tree first. The layout is GPT-2's own; a program
that stops reading it fails at the first call, loudly.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_SEED_MASK = 0x7FFFFFFF


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & _SEED_MASK),
                              (seed >> 31) & _SEED_MASK)


def gpt2_shapes(c: dict) -> dict:
    """Leaf name -> (shape, init scale; None = ones, 0 = zeros)."""
    L, d, ff = c["n_layer"], c["n_embd"], c["n_inner"]
    s, so = 0.02, 0.02 / math.sqrt(2 * L)
    return {
        "embed": ((c["vocab_size"], d), s),
        "pos": ((c["n_positions"], d), s),
        "layers/ln1_g": ((L, d), None), "layers/ln1_b": ((L, d), 0),
        "layers/wqkv": ((L, d, 3 * d), s),
        "layers/wo": ((L, d, d), so),
        "layers/ln2_g": ((L, d), None), "layers/ln2_b": ((L, d), 0),
        "layers/w1": ((L, d, ff), s), "layers/b1": ((L, ff), 0),
        "layers/w2": ((L, ff, d), so), "layers/b2": ((L, d), 0),
        "lnf_g": ((d,), None), "lnf_b": ((d,), 0),
    }


def n_params(c: dict) -> int:
    return sum(math.prod(shape) for shape, _ in gpt2_shapes(c).values())


def make_gpt2(c: dict, seed: int, dtype):
    """The parameter tree of configuration ``c`` in ``dtype``."""
    shapes = gpt2_shapes(c)

    def build(key):
        keys = jax.random.split(key, len(shapes))
        tree = {"layers": {}}
        for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items())):
            if scale is None:
                leaf = jnp.ones(shape, dtype)
            elif scale == 0:
                leaf = jnp.zeros(shape, dtype)
            else:
                leaf = (jax.random.normal(k, shape, jnp.float32)
                        * scale).astype(dtype)
            if name.startswith("layers/"):
                tree["layers"][name[7:]] = leaf
            else:
                tree[name] = leaf
        return tree

    return jax.jit(build)(seed_key(seed))
