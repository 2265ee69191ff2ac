#!/usr/bin/env python3
"""Smoke run of the paged serving path on a TPU v5e, at published widths.

    python chip_smoke.py                # one chip: phi4-mini-3.8b
    python chip_smoke.py --four-chips   # four chips: internlm2-20b at TP=4

Each phase builds its model in bf16 through the serve launcher's own
builders (``repro.launch.serve``; random weights from ``--seed``, nothing
downloaded) and drains 16 greedy requests through ``ServeEngine``
admission, chunked prefill and fused decode windows (batch 8, max_len
2048, window 8; prompt lengths drawn from the seed in 64..1024 tokens, 32
new tokens each), exactly as ``python -m repro.launch.serve`` drains them.
It then checks that

- the engine's kernel plan runs the ``paged_attention`` kernel compiled
  (``interpret`` resolves to False) and the compiled decode dispatch holds
  it (``tpu_custom_call``);
- every token generated for the first 4 requests agrees with a plain
  forward of the same weights over prompt + generated tokens (no cache, no
  pages, no Pallas), to within ``MARGIN``;
- a second drain of the same requests after ``reset()`` is identical;
- under TP, every parameter and KV-pool leaf that its spec shards is
  sharded (the weights and pools are created in their shardings).

The default phase needs one chip and is the only one run without options;
``--four-chips`` runs only the TP=4 phase and its reference check.
Timings printed here are smoke figures, not benchmark numbers.  The last
line of stdout is one JSON object naming the device; any failed check,
a missing TPU or a device that is not a v5e exits nonzero without it.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# device_kind strings of the chip this smoke targets (JAX names a v5e
# "TPU v5 lite")
V5E_KINDS = ("TPU v5 lite", "TPU v5e")

BATCH, MAX_LEN, WINDOW = 8, 2048, 8
REQUESTS, PROMPT_LEN, MAX_NEW = 16, (64, 1025), 32
CHECKED = 4
# A generated token passes when the reference's logit for it is within
# MARGIN of the reference's largest logit.  Engine (chunked paged prefill,
# Pallas decode kernel) and reference (one unchunked forward) compute the
# same bf16 model with different accumulation orders, so their logits
# differ by rounding only.  With these random weights the top logits sit
# near 4, where one bf16 step is 2**-5; MARGIN allows 8 such steps, while a
# wrong token's logit sits about a whole unit or more below the top.
MARGIN = 0.25

PHASES = {
    "one-chip": dict(arch="phi4-mini-3.8b", tp=1),
    "four-chips": dict(arch="internlm2-20b", tp=4),
}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def check_device(count: int):
    """The first device must be a v5e TPU and ``count`` of them visible."""
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d.platform}")
    check(d.device_kind in V5E_KINDS,
          f"device kind {d.device_kind!r} is not a v5e {V5E_KINDS}")
    check(len(devs) >= count, f"needs {count} chips, JAX sees {len(devs)}")
    return d


class CompileCounters:
    """Compile seconds and persistent-cache hits, from JAX's monitoring."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def drain(pool, vocab: int, seed: int):
    from repro.launch.serve import make_requests

    reqs = make_requests(vocab, REQUESTS, seed=seed, prompt_len=PROMPT_LEN,
                         max_new=MAX_NEW)
    for r in reqs:
        pool.submit(r)
    t0 = time.perf_counter()
    stats = pool.drain()
    dt = time.perf_counter() - t0
    check(all(r.done and len(r.out_tokens) == MAX_NEW for r in reqs),
          "drain ended with unfinished requests")
    return reqs, stats, dt


def check_kernel(eng) -> None:
    """The plan compiles the kernel and the decode dispatch contains it."""
    import jax.numpy as jnp

    check(eng.plan.resolve_interpret() is False,
          "the engine's plan resolves interpret=True on the chip")
    steps = jnp.full((eng.bsz,), WINDOW, jnp.int32)
    text = eng._paged_decode_many.lower(
        WINDOW, eng.params, eng.cache, eng.tokens, eng.pos, steps, eng.keys,
        eng._table).compile().as_text()
    check("tpu_custom_call" in text,
          "the compiled decode dispatch has no tpu_custom_call")
    print(f"decode dispatch: tpu_custom_call present "
          f"(page={eng.page}, interpret=False)")


def check_placement(bundle, eng) -> None:
    """Every leaf its spec shards is split across the mesh, never whole."""
    import jax

    def whole(leaf):
        return leaf.sharding.shard_shape(leaf.shape) == tuple(leaf.shape)

    shardings = jax.tree.leaves(eng.dist.param_shardings(bundle))
    leaves = jax.tree.leaves(eng.params)
    split = [leaf for leaf, sh in zip(leaves, shardings)
             if any(ax is not None for ax in sh.spec)]
    check(all(not whole(leaf) for leaf in split),
          "a parameter leaf that its spec shards sits whole on one device")
    pools = [leaf for path, leaf
             in jax.tree_util.tree_leaves_with_path(eng.cache)
             if str(getattr(path[-1], "key", "")) in ("k_pages", "v_pages")]
    check(pools and all(not whole(leaf) for leaf in pools),
          "a KV pool sits whole on one device")
    print(f"placement: {len(split)}/{len(leaves)} parameter leaves and "
          f"{len(pools)} KV pools sharded over tp={eng.tp}; none whole on "
          f"one device")


def reference_check(bundle, eng, reqs) -> None:
    """Teacher-forced plain forward over prompt + generated tokens."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.models import transformer

    cfg, flags = bundle.cfg, bundle.flags
    done = sorted(reqs, key=lambda r: r.rid)[:CHECKED]
    toks = np.zeros((len(done), MAX_LEN), np.int32)
    idx = np.zeros((len(done), MAX_NEW), np.int32)
    for i, r in enumerate(done):
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1])])
        toks[i, :len(seq)] = seq
        # out_tokens[j] was chosen from the logits at position s - 1 + j
        idx[i] = len(r.prompt) - 1 + np.arange(MAX_NEW)

    def ref(params, tokens, pos):
        x, _, _ = transformer.forward(params, cfg, flags, tokens,
                                      mode="train")
        x = jnp.take_along_axis(x, pos[..., None], axis=1)
        return transformer.compute_logits(params, cfg, x).astype(jnp.float32)

    if eng.dist is None:
        fn = jax.jit(ref)
    else:
        # GSPMD over the engine's mesh: the tp policy's param shardings in,
        # replicated tokens and logits; no shard_map islands
        rep = NamedSharding(eng.dist.mesh, PartitionSpec())
        fn = jax.jit(ref, in_shardings=(eng.dist.param_shardings(bundle),
                                        rep, rep), out_shardings=rep)
    logits = np.asarray(fn(eng.params, jnp.asarray(toks), jnp.asarray(idx)))
    gen = np.asarray([r.out_tokens for r in done])
    rows = np.arange(MAX_NEW)
    gaps = np.stack([lg.max(-1) - lg[rows, g] for lg, g in zip(logits, gen)])
    exact = int((logits.argmax(-1) == gen).sum())
    print(f"reference check: {len(done)} requests x {MAX_NEW} tokens, "
          f"{exact}/{gen.size} exact argmax, largest gap "
          f"{float(gaps.max()):.6g} (margin {MARGIN})")
    check(bool(np.isfinite(logits).all()), "reference logits not finite")
    check(float(gaps.max()) <= MARGIN,
          f"a generated token is {float(gaps.max()):.6g} below the "
          f"reference's top logit (margin {MARGIN})")


def run_phase(name: str, seed: int, counters: CompileCounters) -> None:
    import jax

    from repro.launch.serve import build_bundle, build_pool

    spec = PHASES[name]
    bundle = build_bundle(spec["arch"])
    t0 = time.perf_counter()
    pool = build_pool(bundle, None, tp=spec["tp"], param_seed=seed,
                      batch_size=BATCH, max_len=MAX_LEN, window=WINDOW,
                      seed=seed, cache_backend="paged")
    eng = pool.engines[0]
    jax.block_until_ready((eng.params, eng.cache))
    print(f"{name}: {bundle.cfg.name} tp={spec['tp']} built in "
          f"{time.perf_counter() - t0:.2f}s")
    if eng.dist is not None:
        check_placement(bundle, eng)

    c0 = counters.seconds
    reqs, stats, cold = drain(pool, bundle.cfg.vocab_size, seed)
    print(f"cold drain: {cold:.2f}s, of which compile "
          f"{counters.seconds - c0:.2f}s; tokens_out={stats.tokens_out}, "
          f"prefill_chunks={stats.prefill_chunks}, "
          f"decode_dispatches={stats.decode_dispatches}")
    check_kernel(eng)
    reference_check(bundle, eng, reqs)

    for e in pool.engines:
        e.reset()
    again, stats, warm = drain(pool, bundle.cfg.vocab_size, seed)
    check([r.out_tokens for r in again] == [r.out_tokens for r in reqs],
          "a second drain of the same requests generated other tokens")
    print(f"warm drain: {warm:.2f}s, identical tokens; "
          f"{stats.tokens_out / warm:.1f} tok/s (smoke figure, not a "
          f"benchmark number)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and sampling keys")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the TP=4 internlm2-20b phase")
    args = ap.parse_args(argv)
    name = "four-chips" if args.four_chips else "one-chip"

    import jax

    dev = check_device(PHASES[name]["tp"])
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    counters = CompileCounters()
    run_phase(name, args.seed, counters)

    for d in jax.devices()[:PHASES[name]["tp"]]:
        stats = d.memory_stats() or {}
        print(f"device {d.id}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}")
    print(f"compile: {counters.seconds:.2f}s total, persistent cache "
          f"hits={counters.hits} misses={counters.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)
