"""SDAR-MoE weights from ``--seed``, made by the benchmark, on the
device, in the type they are used in, never whole in float32: a large
leaf is drawn a ``[rows, columns]`` matrix at a time (``lax.map``), so
the 8.7 GB tree needs no second copy of any of it.

The tree has the layout the program's ``sdar`` family reads: every
layer alike, so ONE stretch, ``tree["layers"]`` a dict of leaves
``[num_hidden_layers, ...]``; ``embed`` and ``head`` (untied:
``tie_word_embeddings`` false), ``final_norm``. The plain reference
(``reference/sdar.py``) is handed the same tree: program and reference
see the same numbers and neither takes anything the other made.

Leaves a layer: ``op_norm``, ``ffn_norm`` [d]; ``wq`` [d, Hq*Dh],
``wk``, ``wv`` [d, Hkv*Dh], ``wo`` [Hq*Dh, d], ``q_norm``, ``k_norm``
[Dh]; ``gate`` [d, E] (the softmax router: no bias); experts ``w1``
(gate), ``w3`` (up) [E, d, f], ``w2`` (down) [E, f, d].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key


def layer_shapes(c: dict) -> dict:
    """Leaf name -> (shape, init: None = ones, else a normal's scale:
    0.02, or the file's ``init_scale``, which a tiny test configuration
    raises so that its layers decide the logits)."""
    d, s = c["hidden_size"], c.get("init_scale", 0.02)
    dh, hq, hkv = (c["head_dim"], c["num_attention_heads"],
                   c["num_key_value_heads"])
    e, f = c["num_experts"], c["moe_intermediate_size"]
    return {"op_norm": ((d,), None), "ffn_norm": ((d,), None),
            "wq": ((d, hq * dh), s), "wk": ((d, hkv * dh), s),
            "wv": ((d, hkv * dh), s), "wo": ((hq * dh, d), s),
            "q_norm": ((dh,), None), "k_norm": ((dh,), None),
            "gate": ((d, e), s),
            "w1": ((e, d, f), s), "w3": ((e, d, f), s), "w2": ((e, f, d), s)}


def n_params(c: dict) -> int:
    """Embedding, head, final norm and ``num_hidden_layers`` layers."""
    return (2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
            + c["num_hidden_layers"] * sum(
                math.prod(shape) for shape, _ in layer_shapes(c).values()))


def _leaf(key, shape, init, dtype):
    if init is None:
        return jnp.ones(shape, dtype)

    def draw(k, sh):
        return (jax.random.normal(k, sh, jnp.float32) * init).astype(dtype)
    if math.prod(shape) < (1 << 24):
        return jax.jit(draw, static_argnums=1)(key, shape)
    # a matrix (or, of a matrix, a block of rows) at a time
    if len(shape) < 3:
        rows = next(r for r in (4096, 2048, 1024, 512, 256, 128)
                    if shape[0] % r == 0)
        lead, cell = (shape[0] // rows,), (rows,) + shape[1:]
    else:
        lead, cell = shape[:-2], shape[-2:]
    keys = jax.random.split(key, math.prod(lead))
    out = jax.jit(lambda ks: jax.lax.map(lambda k: draw(k, cell), ks))(keys)
    return out.reshape(shape)


def make_sdar(c: dict, seed: int, dtype):
    """The parameter tree of configuration ``c`` in ``dtype`` (the
    router's ``gate`` and the norms in float32: they are computed in
    it)."""
    key = seed_key(seed)
    n = 0

    def leaf(shape, init, dt=dtype):
        nonlocal n
        n += 1
        return _leaf(jax.random.fold_in(key, n), shape, init, dt)

    s = c.get("init_scale", 0.02)
    tree = {"embed": leaf((c["vocab_size"], c["hidden_size"]), s),
            "head": leaf((c["vocab_size"], c["hidden_size"]), s),
            "final_norm": leaf((c["hidden_size"],), None, jnp.float32)}
    L = c["num_hidden_layers"]
    tree["layers"] = {
        name: leaf((L,) + shape, init,
                   jnp.float32 if init is None or name == "gate" else dtype)
        for name, (shape, init) in sorted(layer_shapes(c).items())}
    return tree
