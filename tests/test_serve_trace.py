"""The serving path as a profile sees it: the host spans that
``ClusterFrontEnd.step`` and ``ServeEngine`` record with
``jax.profiler.TraceAnnotation``, and the names of the jitted programs
(``jit_decode_window`` and the rest), by which a trace reduction finds
them."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models import RuntimeFlags, build
from repro.serve import ClusterFrontEnd, Request, ServeEngine

FLAGS = RuntimeFlags(attn_impl="chunked", attn_bq=16, attn_bkv=16,
                     moe_impl="dense", loss_chunk=16)
SPANS = {"serve.step", "serve.route", "serve.admit", "serve.prefill_chunk",
         "serve.decode", "serve.reserve", "serve.dispatch",
         "serve.device_wait", "serve.unpack", "serve.harvest"}


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config(ARCHS["phi4-mini-3.8b"])
    bundle = build(cfg, FLAGS)
    return cfg, bundle, bundle.init(jax.random.PRNGKey(0))


def _engine(model, kind):
    _, bundle, params = model
    spec = (dict(draft_bundle=bundle, draft_params=params, spec_k=3)
            if kind == "spec" else {})
    return ServeEngine(bundle, params, batch_size=2, max_len=64,
                       cache_backend="dense" if kind == "dense" else "paged",
                       prefill_chunk=8, **spec)


def _drain(front, cfg):
    """Requests 0, 1 and 2, of 11 prompt tokens (two prefill chunks each)
    and 5 new tokens, through ``front``."""
    rng = np.random.default_rng(1)
    for rid in range(3):
        front.submit(Request(
            rid=rid, max_new_tokens=5,
            prompt=rng.integers(0, cfg.vocab_size, 11).astype(np.int32)))
    while front.step():
        pass


def _serve_spans(log_dir):
    """``[name, start_ns, end_ns, rid]`` of every ``serve.*`` host event in
    the profile written under ``log_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append([e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats).get("rid")])
    return spans


@pytest.mark.parametrize("kind", ["paged", "spec"])
def test_every_span_nests_inside_the_round(model, kind, tmp_path):
    """Each round records every span once per layer boundary, all inside
    ``serve.step``; the host waits on the chip only inside a decode window
    or a prefill chunk; a request's chunks carry its rid."""
    cfg = model[0]
    front = ClusterFrontEnd([_engine(model, kind)])
    _drain(front, cfg)                  # compiles outside the profile
    front.reset()
    with jax.profiler.trace(str(tmp_path)):
        _drain(front, cfg)
    spans = _serve_spans(str(tmp_path))
    assert {s[0] for s in spans} == SPANS

    def inside(span, names):
        return any(o[0] in names and o[1] <= span[1] and span[2] <= o[2]
                   for o in spans if o is not span)

    for s in spans:
        if s[0] != "serve.step":
            assert inside(s, {"serve.step"}), s
    for s in spans:
        if s[0] == "serve.device_wait":
            assert inside(s, {"serve.decode", "serve.prefill_chunk"}), s
        if s[0] in ("serve.reserve", "serve.dispatch", "serve.unpack"):
            assert inside(s, {"serve.decode"}), s
        if s[0] == "serve.prefill_chunk":
            assert inside(s, {"serve.admit"}), s
    chunks = [s[3] for s in spans if s[0] == "serve.prefill_chunk"]
    assert sorted(chunks) == [0, 0, 1, 1, 2, 2]


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_the_decode_window_module_is_named(model, kind):
    eng = _engine(model, kind)
    steps = jnp.zeros((eng.bsz,), jnp.int32)
    args = (eng.params, eng.cache, eng.tokens, eng.pos, steps, eng.keys)
    if kind == "paged":
        lowered = eng._paged_decode_many.lower(eng.window, *args, eng._table)
    else:
        lowered = eng._decode_many.lower(eng.window, *args)
    assert re.match(r"module @jit_decode_window\b", lowered.as_text())


def test_no_serving_program_compiles_unnamed(model, caplog):
    """Every program that paged, speculative and dense drains compile has
    a name of its own: none is ``jit__unknown`` (an unnamed partial) or
    ``jit__lambda_``."""
    cfg = model[0]
    with jax.log_compiles():
        for kind in ("paged", "spec", "dense"):
            _drain(ClusterFrontEnd([_engine(model, kind)]), cfg)
    names = set(re.findall(r"Compiling jit\((.+?)\) with", caplog.text))
    assert {"new_page_pools", "_prefill_impl", "decode_window",
            "spec_decode_window", "draft_prefill", "prefill"} <= names
    assert not [n for n in names if n.startswith("<")], names
