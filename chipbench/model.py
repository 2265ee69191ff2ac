"""A configuration file, the model it describes, and the weights the
benchmark draws for it.

The configuration file is the yardstick: the program's model is built from
the file's sizes, and the reference (``reference.py``) follows the file.
The weights are the benchmark's own, drawn on the device from the seed in
one jitted call, in the parameter layout the program's model takes (the
program never draws them).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

# file key -> the program's ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
# what the program's dense decoder fixes and the file has to state alike
_FIXED = {"hidden_act": "silu", "partial_rotary_factor": 1.0,
          "rope_scaling": None, "rms_norm_eps": 1e-6}
NORMS = ("ln1", "ln2", "final_norm")


def load_config(root: str, name: str) -> dict:
    """The configuration as it is run: the file's keys, which follow the
    published configuration, with each of its ``departures`` (what the
    program runs in place of a published key) put in."""
    with open(os.path.join(root, "chipbench", "configs", f"{name}.json")) as f:
        conf = json.load(f)
    for key, dep in conf.get("departures", {}).items():
        conf[key] = dep["run"]
    return conf


def bundle_for(conf: dict):
    """The program's model for ``conf``: its registry entry for
    ``conf["arch"]`` with the file's sizes, and the serving runtime flags
    of ``repro.launch.serve.build_bundle``."""
    from repro.launch.serve import build_bundle
    from repro.models import build

    for key, want in _FIXED.items():
        if conf[key] != want:
            raise ValueError(f"{conf['arch']}: {key}={conf[key]!r}, but the "
                             f"program's dense decoder runs {want!r}")
    base = build_bundle(conf["arch"])
    if base.cfg.activation != "swiglu" or base.cfg.family != "dense":
        raise ValueError(f"{conf['arch']} is not a dense SwiGLU decoder")
    sizes = {field: conf[key] for key, field in _FIELDS.items()}
    cfg = dataclasses.replace(base.cfg, **sizes,
                              param_dtype=conf["dtype"],
                              compute_dtype=conf["dtype"])
    return build(cfg, base.flags)


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words mixed from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def make_params(bundle, seed: int, shardings=None):
    """Weights for ``bundle`` from ``seed``, on the device, in one jitted
    call (the seed is an argument, so every seed runs the same program).

    Matrices are truncated normal with std ``1/sqrt(fan_in)`` (the
    embedding ``1/sqrt(hidden_size)``); norm gains are ``1 + 0.1 N(0, 1)``,
    held as the offset from 1 that the program's RMSNorm adds to 1."""
    import jax
    import jax.numpy as jnp

    abstract, _ = bundle.abstract_params()
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    d = bundle.cfg.d_model

    def init(words):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(0), words[0]), words[1])
        out = []
        for i, (path, leaf) in enumerate(paths):
            k = jax.random.fold_in(key, i)
            names = [str(getattr(p, "key", "")) for p in path]
            if names[-1] in NORMS:
                v = 0.1 * jax.random.normal(k, leaf.shape, jnp.float32)
            else:
                std = (d ** -0.5 if names[0] == "embed"
                       else 1.0 / math.sqrt(leaf.shape[-2]))
                v = std * jax.random.truncated_normal(k, -2.0, 2.0,
                                                      leaf.shape, jnp.float32)
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    fn = jax.jit(init, out_shardings=shardings)
    return fn(jnp.asarray(seed_words(seed)))
