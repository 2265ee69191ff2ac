"""A configuration file, the model family it names, and the weights the
benchmark draws for it.

The configuration file is the yardstick: ``chipbench/configs/<name>.json``
holds a published configuration's keys as they are run, and its
``"family"`` names the module ``chipbench/families/<family>.py`` that
holds everything that depends on the architecture.  The module is loaded
by its path under the checkout's root, so a family comes in as a file of
its own.  It provides:

- ``build(conf)``: the program's model for the file (a
  ``repro.models.ModelBundle``), refusing a file the program cannot run
  as stated;
- ``draw(conf, names, shape, key)``: one weight, float32, from its
  parameter path (the list of key names) and shape;
- ``hidden(conf, prec, params, tokens)``: the plain reference's
  final-normed hidden states (S, d) of a row of ``tokens`` (S,), float32,
  causal, importing nothing of the program; ``prec`` is ``"f32"``, or a
  control precision (``"int8"``, ``"fp8"``) for every matrix product;
- ``logits(prec, params, h)``: the reference's logits of such rows;
- ``counts(conf, prompt, first, count)``: named counts (operations,
  bytes) behind output tokens ``first .. first + count - 1`` of a request
  with a ``prompt``-token prompt, all 0 for ``count`` 0.  The harness sums
  each over the tokens returned in a traced window and hands the sums to
  the metric readers under the same names (``chipbench/metrics``), so a
  family brings the counts its own readers need.

The weights are the benchmark's own, drawn on the device from the seed in
one jitted call, in the parameter layout the program's model takes (the
program never draws them).
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np


def load_family(root: str, family: str):
    """The module ``chipbench/families/<family>.py`` under ``root``."""
    path = os.path.join(root, "chipbench", "families", f"{family}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(root: str, name: str):
    """The configuration as it is run and its family's module.  The
    configuration is the file's keys, which follow the published
    configuration, with each of its ``departures`` (what the program runs
    in place of a published key) put in.  A file that names no family, or
    one with no module, is refused."""
    path = os.path.join(root, "chipbench", "configs", f"{name}.json")
    with open(path) as f:
        conf = json.load(f)
    family = conf.get("family")
    if not family:
        raise ValueError(f"{path} names no model family (its \"family\" "
                         f"key names chipbench/families/<family>.py)")
    if not os.path.exists(os.path.join(root, "chipbench", "families",
                                       f"{family}.py")):
        raise ValueError(f"{path} names the family {family!r}, which has no "
                         f"module chipbench/families/{family}.py")
    for key, dep in conf.get("departures", {}).items():
        conf[key] = dep["run"]
    return conf, load_family(root, family)


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words mixed from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def make_params(bundle, draw, seed: int, shardings=None):
    """Weights for ``bundle`` from ``seed``, on the device, in one jitted
    call (the seed is an argument, so every seed runs the same program).
    Leaf ``i`` of the parameter tree is ``draw(names, shape, key)`` with
    the ``i``-th key folded from the seed, cast to the leaf's dtype."""
    import jax
    import jax.numpy as jnp

    abstract, _ = bundle.abstract_params()
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def init(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), words[0]), words[1])
        out = []
        for i, (path, leaf) in enumerate(paths):
            names = [str(getattr(p, "key", "")) for p in path]
            v = draw(names, leaf.shape, jax.random.fold_in(key, i))
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(init, out_shardings=shardings)
    return fn(jnp.asarray(seed_words(seed)))
