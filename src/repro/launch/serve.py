"""Serving launcher: continuous-batching engine(s) over a checkpoint (or
fresh init at smoke scale), optionally spread across a TP x DP device mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
        --requests 8 --batch 4

    # one engine sharded over 2 devices (TP), two such replicas (DP):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --tp 2 --dp 2 --requests 16

TP shards a single engine's params and KV page pools across a mesh axis
(``dist.ServeMesh``); DP runs independent engine replicas — each on its own
device group — behind one shared admission queue (:class:`ReplicaPool`),
which dispatches every request to the least-loaded replica.  Replicas share
no device state, so the DP axis is pure scheduling: in the paper's framing
TP adds memory channels behind one request stream while DP adds whole
ports, and the admission queue is the host-side arbiter between them.
"""
import argparse
import sys
import time
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.configs import ARCHS, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import ModelBundle, RuntimeFlags, build
from repro.serve import (DisaggConfig, DisaggPool, Request, ServeEngine,
                         ServeStats, aggregate_stats)
from repro.train import CheckpointManager

# request i's scheduler class under each --priority mix (matches
# examples/serve_lm.py)
_PRIORITY_MIX = {"off": lambda i: 0, "low": lambda i: 0,
                 "high": lambda i: 1, "mixed": lambda i: i % 2}


def build_bundle(arch: str, *, smoke: bool = False,
                 kv_int8: bool = False) -> ModelBundle:
    """The served model: ``arch`` at its published widths (or its reduced
    same-family ``smoke`` config) with the serving runtime flags."""
    cfg = smoke_config(ARCHS[arch]) if smoke else ARCHS[arch]
    flags = RuntimeFlags(attn_impl="chunked", attn_bq=64, attn_bkv=64,
                         moe_impl="dense", loss_chunk=64,
                         kv_dtype="int8" if kv_int8 else "native")
    return build(cfg, flags)


def init_params(bundle: ModelBundle, seed: int = 0, dist=None):
    """Weights drawn from ``seed`` by ONE jitted program: the float32 draws
    fuse into their casts instead of materializing op by op, and under TP
    (``dist``, a :class:`repro.dist.ServeMesh`) every leaf lands directly in
    its shard — no leaf is ever whole on one device."""
    out = None if dist is None else dist.param_shardings(bundle)
    return jax.jit(bundle.init, out_shardings=out)(jax.random.PRNGKey(seed))


def make_requests(vocab_size: int, n: int, *, seed: int = 0,
                  prompt_len=(4, 24), max_new: int = 16,
                  priority: str = "off") -> List[Request]:
    """``n`` requests with prompt lengths drawn from ``[lo, hi)`` and
    tokens from the vocabulary, all from ``seed``."""
    rng = np.random.default_rng(seed)
    mix = _PRIORITY_MIX[priority]
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab_size,
                              size=int(rng.integers(*prompt_len)))
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=max_new, priority=mix(i)))
    return reqs


def device_groups(tp: int, dp: int,
                  devices: Optional[Sequence] = None) -> List[list]:
    """Split the visible devices into ``dp`` disjoint TP groups of ``tp``
    devices each (replica ``i`` owns ``devices[i*tp:(i+1)*tp]``)."""
    devs = list(jax.devices() if devices is None else devices)
    if tp < 1 or dp < 1:
        raise ValueError(f"tp={tp} and dp={dp} must be >= 1")
    if tp * dp > len(devs):
        raise ValueError(
            f"tp={tp} x dp={dp} needs {tp * dp} devices, have {len(devs)}")
    return [devs[i * tp:(i + 1) * tp] for i in range(dp)]


class ReplicaPool:
    """A shared admission queue over independent engine replicas (the DP
    axis).  ``submit`` routes each request to the least-loaded replica
    (queued + in-flight requests; ties go to the lowest replica index, so
    an idle pool round-robins).  Replicas never share device state — the
    pool is scheduling only, which is what makes DP scale linearly."""

    def __init__(self, engines: Sequence[ServeEngine]):
        if not engines:
            raise ValueError("ReplicaPool needs at least one engine")
        self.engines = list(engines)
        self.routed = [0] * len(self.engines)   # per-replica request counts

    @staticmethod
    def _load(eng: ServeEngine) -> int:
        return len(eng.queue) + sum(s is not None for s in eng.slots)

    def submit(self, req: Request) -> int:
        """Admit ``req`` to the least-loaded replica; returns its index."""
        i = min(range(len(self.engines)),
                key=lambda j: self._load(self.engines[j]))
        self.engines[i].add_request(req)
        self.routed[i] += 1
        return i

    def drain(self, max_rounds: int = 100_000) -> ServeStats:
        """Tick every replica that still has work until all are idle.
        The budget counts drain *rounds* — one step of every busy replica
        — so the effective per-replica budget no longer shrinks as ``dp``
        grows."""
        for _ in range(max_rounds):
            busy = [e for e in self.engines
                    if e.queue or any(s is not None for s in e.slots)]
            if not busy:
                return self.stats()
            for eng in busy:
                eng.step()
        busy = [e for e in self.engines
                if e.queue or any(s is not None for s in e.slots)]
        agg = self.stats()
        raise RuntimeError(
            f"replica pool failed to drain in {max_rounds} rounds: "
            f"{len(busy)}/{len(self.engines)} replicas busy, "
            f"{sum(len(e.queue) for e in self.engines)} queued; partial "
            f"aggregate: tokens_out={agg.tokens_out}, "
            f"prefills={agg.prefills}, decode_steps={agg.decode_steps}, "
            f"pool_stalls={agg.pool_stalls}")

    def stats(self) -> ServeStats:
        """Aggregate counters across replicas (sums every ServeStats
        field — peaks sum too: the pool's total live-page commitment)."""
        return aggregate_stats(self.engines)


def _placed_params(bundle, params, param_seed: int, meshes) -> list:
    """Each engine's params: ``params`` as given, or (``None``) drawn from
    ``param_seed`` in place — once per distinct device group, so engines
    sharing devices share one copy."""
    if params is not None:
        return [params] * len(meshes)
    drawn = {}
    out = []
    for m in meshes:
        key = None if m is None else tuple(d.id for d in m.mesh.devices.flat)
        if key not in drawn:
            drawn[key] = init_params(bundle, param_seed, m)
        out.append(drawn[key])
    return out


def build_pool(bundle, params=None, *, tp: int = 1, dp: int = 1,
               devices: Optional[Sequence] = None, param_seed: int = 0,
               **engine_kw) -> ReplicaPool:
    """``dp`` engine replicas, each TP-sharded over its own ``tp``-device
    group.  With ``tp * dp == 1`` the single engine runs undistributed
    (no mesh, any backend); any wider layout shards/pins KV page pools,
    so the paged backend is required.  ``params=None`` draws the weights
    from ``param_seed`` directly in each replica's shardings."""
    from repro.dist import ServeMesh

    if tp * dp == 1:
        meshes = [None]
    else:
        engine_kw.setdefault("cache_backend", "paged")
        meshes = [ServeMesh.tp(tp, devices=g)
                  for g in device_groups(tp, dp, devices)]
    return ReplicaPool([
        ServeEngine(bundle, p, **engine_kw, dist=m)
        for m, p in zip(meshes, _placed_params(bundle, params, param_seed,
                                               meshes))])


def build_disagg_pool(bundle, params=None, *, tp: int = 1,
                      prefill_replicas: int = 1, decode_replicas: int = 1,
                      devices: Optional[Sequence] = None,
                      disagg_config: Optional[DisaggConfig] = None,
                      param_seed: int = 0, **engine_kw) -> DisaggPool:
    """The ``disagg`` topology: a prefill pool that ships every finished
    prompt's pages to a decode pool as a checksummed transfer buffer
    (:class:`~repro.serve.cluster.DisaggPool`).  Requires the paged
    backend with the host swap tier on both sides.  Disaggregation is a
    scheduling topology, so pools may share devices: with ``tp == 1``
    every engine runs undistributed (single-device smoke runs both pools
    on one chip); with ``tp > 1`` each engine gets its own disjoint
    ``tp``-device group when enough devices exist (prefill groups first),
    and otherwise all engines TP-shard over the *same* ``tp`` devices —
    the hand-off is still a real gather/scatter across meshes.
    ``params=None`` draws the weights from ``param_seed`` in place."""
    from repro.dist import ServeMesh

    if prefill_replicas < 1 or decode_replicas < 1:
        raise ValueError("disagg topology needs >= 1 prefill and >= 1 "
                         "decode replica")
    engine_kw.setdefault("cache_backend", "paged")
    n = prefill_replicas + decode_replicas
    if tp == 1:
        meshes = [None] * n
    else:
        pool = list(devices) if devices is not None else list(jax.devices())
        if len(pool) >= tp * n:
            groups = device_groups(tp, n, devices)
        else:
            if len(pool) < tp:
                raise ValueError(f"tp={tp} needs {tp} devices, have "
                                 f"{len(pool)}")
            groups = [pool[:tp]] * n
        meshes = [ServeMesh.tp(tp, devices=g) for g in groups]
    engines = [ServeEngine(bundle, p, **engine_kw, dist=m)
               for m, p in zip(meshes, _placed_params(bundle, params,
                                                      param_seed, meshes))]
    return DisaggPool(engines[:prefill_replicas],
                      engines[prefill_replicas:], config=disagg_config)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--window", type=int, default=8,
                    help="fused decode ticks per dispatch")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width per engine replica")
    ap.add_argument("--dp", type=int, default=1,
                    help="independent engine replicas (device groups)")
    ap.add_argument("--seed", type=int, default=0,
                    help="traffic + sampling PRNG seed")
    ap.add_argument("--priority", default="off",
                    choices=sorted(_PRIORITY_MIX),
                    help="scheduler priority classes for the request mix "
                         "(matches examples/serve_lm.py)")
    ap.add_argument("--cache", default="auto",
                    choices=("auto", "dense", "paged"),
                    help="KV backend; auto lets the engine pick (paged is "
                         "forced whenever tp*dp > 1)")
    ap.add_argument("--topology", default="colocated",
                    choices=("colocated", "disagg"),
                    help="colocated: every replica prefills and decodes "
                         "(ReplicaPool).  disagg: a prefill pool ships "
                         "finished prompts' pages to a decode pool "
                         "(DisaggPool); --dp counts decode replicas")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="prefill-pool replicas under --topology disagg")
    ap.add_argument("--link-bw", type=float, default=32e9,
                    help="prefill->decode transfer link bandwidth (prices "
                         "the disagg-vs-colocated routing break-even)")
    ap.add_argument("--route", default="auto",
                    choices=("auto", "disagg", "colocated"),
                    help="pin the disagg router's per-request decision "
                         "(auto defers to the swap cost model)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    bundle = build_bundle(args.arch, smoke=args.smoke, kv_int8=args.kv_int8)
    params = None
    if args.ckpt:
        abs_params, _ = bundle.abstract_params()
        params = CheckpointManager(args.ckpt).restore(
            None, dict(params=abs_params))["params"]

    engine_kw = dict(batch_size=args.batch, max_len=args.max_len,
                     window=args.window, seed=args.seed)
    if args.cache != "auto":
        engine_kw["cache_backend"] = args.cache
    if args.topology == "disagg":
        pool = build_disagg_pool(
            bundle, params, tp=args.tp,
            prefill_replicas=args.prefill_replicas, decode_replicas=args.dp,
            disagg_config=DisaggConfig(
                link_bw=args.link_bw,
                force=None if args.route == "auto" else args.route),
            **engine_kw)
    else:
        pool = build_pool(bundle, params, tp=args.tp, dp=args.dp, **engine_kw)
    for req in make_requests(bundle.cfg.vocab_size, args.requests,
                             seed=args.seed, max_new=args.max_new,
                             priority=args.priority):
        pool.submit(req)
    t0 = time.perf_counter()
    stats = pool.drain() if args.topology == "colocated" else pool.run()
    dt = time.perf_counter() - t0
    print(f"{stats.tokens_out} tokens in {dt:.2f}s "
          f"({stats.tokens_out/dt:.1f} tok/s) across "
          f"{len(pool.engines)} replica(s) x tp={args.tp}, "
          f"prefills={stats.prefills}, decode_steps={stats.decode_steps}, "
          f"decode_dispatches={stats.decode_dispatches}")
    if args.topology == "disagg":
        d = pool.dstats
        print(f"disagg: {d.disagg_routed} shipped / {d.colocated_routed} "
              f"colocated, {d.transfers} transfers "
              f"({stats.transfer_bytes} bytes), "
              f"{stats.transfer_fallbacks} recompute fallbacks, "
              f"{d.rounds} rounds")
    else:
        print("per-replica requests: "
              + ", ".join(f"r{i}={n}" for i, n in enumerate(pool.routed)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
