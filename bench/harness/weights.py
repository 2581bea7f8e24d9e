"""Random weights from the seed, made by the benchmark in one jitted call
on the device, in bf16, in the parameter tree the serving program reads
(the family module's ``shapes``).

The reference reads the same tree, so neither side takes weights the
other made.  Matrices are normal with standard deviation 1/sqrt(fan-in);
the RMSNorm gains (applied as ``1 + scale``) are normal with deviation 0.1
so that a norm left out shows.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from harness import spec
from harness.traffic import rng_for

NORM_STD = 0.1


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _make(tree, key):
    leaves, tdef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    out = []
    for i, (shape, fan) in enumerate(leaves):
        std = NORM_STD if fan is None else fan ** -0.5
        out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.bfloat16) * std)
    return jax.tree.unflatten(tdef, out)


_MAKERS: Dict[str, object] = {}


def make_params(m: Dict, seed: int):
    """The whole parameter tree for ``--seed``, in one jitted call; its
    shapes are those of the configuration's family (``spec.family``)."""
    tree = spec.family(m).shapes(m)
    fn = _MAKERS.setdefault(repr(tree),
                            jax.jit(functools.partial(_make, tree)))
    return fn(jax.random.PRNGKey(int(rng_for(seed, 0).integers(0, 2 ** 31))))


def check_layout(m: Dict, params) -> None:
    """The benchmark's tree has the program's structure, shapes and dtypes
    (``model.init_abstract``); raises where they part."""
    from repro.configs import get_config
    from repro.models import model
    want = model.init_abstract(get_config(m["registry"]))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("benchmark weights and the program's parameter tree "
                         f"differ in structure:\n{jax.tree.structure(want)}\n"
                         f"{jax.tree.structure(got)}")
    for (path, w), g in zip(jax.tree_util.tree_flatten_with_path(want)[0],
                            jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path)}: program "
                             f"{w.shape} {w.dtype}, benchmark {g.shape} "
                             f"{g.dtype}")
