"""Seeded weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so the reference may
read them. The program tells only the layout (``LM.param_shapes()``); each
leaf is filled by a rule on its name:

* ``table`` (embedding / unembedding): normal, sd 0.02;
* any other matrix (``w``, ``k_up``, ``v_up``): normal, sd 1/sqrt(fan-in),
  fan-in being the second-to-last axis;
* a layernorm's ``scale`` (it has a ``bias`` beside it): 1 + normal(0.1);
  an rmsnorm's ``scale``, which the model applies as ``1 + scale``:
  normal(0.1); a ``bias``: normal, sd 0.02.

Random gains and biases keep the norms in the comparison: a norm applied
the wrong way moves every logit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _key(k):
    return getattr(k, "key", getattr(k, "idx", None))


def _rule(path, shape, with_bias: set) -> tuple[float, float]:
    """(mean, sd) of the leaf at ``path``."""
    name = _key(path[-1])
    if name == "table":
        return 0.0, 0.02
    if name == "scale":
        return (1.0 if tuple(map(_key, path[:-1])) in with_bias else 0.0), 0.1
    if name in ("bias", "b"):
        return 0.0, 0.02
    return 0.0, shape[-2] ** -0.5


def make_params(shapes, seed: int):
    """A params pytree shaped like ``shapes`` (ShapeDtypeStructs), filled
    from ``seed`` in one jitted program, in each leaf's own dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    with_bias = {tuple(map(_key, p[:-1])) for p, _ in flat if _key(p[-1]) == "bias"}
    rules = [(*_rule(p, leaf.shape, with_bias), leaf.shape, leaf.dtype)
             for p, leaf in flat]

    def init(key):
        out = []
        for i, (mean, sd, shape, dtype) in enumerate(rules):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((mean + sd * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
