"""One run of one cell: set-up, the measured window, the check against the
reference, and the metrics.

The system under test is the program's serving path as a deployment
drives it: engines from ``repro.launch.serve.build_pool`` behind one
``repro.serve.ClusterFrontEnd``, fed only through ``submit`` and
``step()``.  The client clock is this module's: a request is timed from
the moment it was due, and a token from the return of the first round
that hands it back.
"""
from __future__ import annotations

import functools
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model, trace
from chipbench.gen import common


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclass
class Rec:
    """One request as its client sees it."""
    index: int
    req: object                      # repro.serve.Request
    due: float                       # host clock, seconds
    first: Optional[float] = None
    last: Optional[float] = None
    finish: Optional[float] = None
    got: int = 0


# ----------------------------------------------------------------------
# files found by name
# ----------------------------------------------------------------------
def load_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_mix(root: str, name: str) -> dict:
    with open(os.path.join(root, "chipbench", "mixes", f"{name}.json")) as f:
        return json.load(f)


def generator(mix: dict):
    mod = importlib.import_module(f"chipbench.gen.{mix['generator']}")
    return mod.Generator


def reader(root: str, metric: str):
    """``metrics/<metric>.py``, else ``metrics/<stem before the dot>.py``."""
    base = os.path.join(root, "chipbench", "metrics")
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(base, f"{stem}.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {metric!r} under {base}")


def cell_metrics(bench: dict, workload: str, section: str) -> List[dict]:
    """The metrics of ``section`` that ``workload`` reports."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


# ----------------------------------------------------------------------
# compile counting
# ----------------------------------------------------------------------
class Compiles:
    """Traces, backend compiles and persistent-cache loads, from JAX's
    monitoring events; ``count`` is their total so far."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._EVENTS:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.count += 1


# ----------------------------------------------------------------------
# the system under test
# ----------------------------------------------------------------------
def pow2_bucket(c: int, chunk: int) -> int:
    """The prefill bucket the engine pads a ``c``-token chunk to."""
    b = 8
    while b < c:
        b *= 2
    return min(b, chunk)


def prefill_buckets(lengths, chunk: int) -> List[int]:
    """Every chunk bucket the prompts of ``lengths`` produce."""
    out = set()
    for s in lengths:
        s = int(s)
        while s > 0:
            c = min(chunk, s)
            out.add(pow2_bucket(c, chunk))
            s -= c
    return sorted(out)


class System:
    """The served model of ``conf``'s ``family``, built once: weights
    drawn from a seed, the engines and their front end."""

    def __init__(self, conf: dict, family, devices, seed: int):
        from repro.dist import ServeMesh
        from repro.launch.serve import build_pool
        from repro.serve import ClusterFrontEnd

        self.conf, self.family = conf, family
        self.devices = list(devices)
        self.bundle = family.build(conf)
        self.draw = functools.partial(family.draw, conf)
        tp = conf["tp"]
        self.shardings = (ServeMesh.tp(tp, devices=self.devices)
                          .param_shardings(self.bundle) if tp > 1 else None)
        t = time.perf_counter()
        self.params = model.make_params(self.bundle, self.draw, seed,
                                        self.shardings)
        jax.block_until_ready(self.params)
        self.weights_s = time.perf_counter() - t
        eng = conf["engine"]
        t = time.perf_counter()
        pool = build_pool(self.bundle, self.params, tp=tp,
                          devices=self.devices,
                          batch_size=eng["batch_size"],
                          max_len=eng["max_len"], window=eng["window"],
                          prefill_chunk=eng["prefill_chunk"],
                          seed=int(model.seed_words(seed)[0] >> 1),
                          cache_backend="paged")
        self.front = ClusterFrontEnd(pool.engines)
        jax.block_until_ready([e.cache for e in self.front.engines])
        self.build_s = time.perf_counter() - t

    @property
    def engines(self):
        return self.front.engines

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` in the same engines (compiled
        programs kept); the old weights are freed first."""
        for e in self.engines:
            e.params = None
        self.params = None
        gc.collect()
        self.params = model.make_params(self.bundle, self.draw, seed,
                                        self.shardings)
        for e in self.engines:
            e.params = self.params
        self.front.reset()

    def free_pools(self) -> None:
        """Drop the page pools and engine state (the weights stay)."""
        for e in self.engines:
            e.cache = None
            e.tokens = e.pos = e.keys = e._table = None
        gc.collect()

    def stats(self):
        return self.front.stats()


def warm_up(system: System, mix: dict, vocab: int) -> int:
    """Serve requests shaped to hit every program the cell's traffic can
    run: each prefill bucket of the mix's prompt pool, every slot, and each
    decode window length (1, 2, 4, ``window`` ticks).  Returns rounds."""
    from repro.serve import Request

    eng = system.conf["engine"]
    chunk, bsz, window = (eng["prefill_chunk"], eng["batch_size"],
                          eng["window"])
    buckets = prefill_buckets(
        common.quantiles(mix["prompt"], common.pool_size(mix)), chunk)
    rng = np.random.default_rng(0)
    rid = [10 ** 9]

    def req(n, new):
        rid[0] += 1
        return Request(rid=rid[0], max_new_tokens=new,
                       prompt=rng.integers(0, vocab, n).astype(np.int32))

    # every slot busy at once, then one bucket per request, then each
    # window length alone (n_run = next_pow2 of the largest budget)
    waves = [[req(buckets[i % len(buckets)], 2) for i in range(bsz)]]
    waves += [[req(b, 2)] for b in buckets]
    ticks = [1]
    while ticks[-1] < window:
        ticks.append(min(2 * ticks[-1], window))
    waves += [[req(buckets[0], t + 1)] for t in ticks]
    rounds = 0
    for wave in waves:
        for r in wave:
            system.front.submit(r)
        busy = True
        while busy:
            busy = system.front.step()
            rounds += 1
    system.front.reset()
    return rounds


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
class Tracer:
    """The profiler over a sub-window of the run (``trace 1`` only)."""

    def __init__(self, start: float, seconds: float, counts: dict):
        self.start, self.seconds = start, seconds
        self.state = "waiting"
        self.dir = None
        self.rounds = 0
        self.t0 = self.t1 = 0.0
        # the family's counts of the tokens returned in the traced window
        self.counts = dict(counts)
        self._span = None

    def before_round(self, now: float) -> None:
        if self.state == "waiting" and now >= self.start:
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(trace.WINDOW)
            self._span.__enter__()
            self.t0 = time.perf_counter()
            self.state = "on"

    def after_round(self, now: float, counts: dict) -> None:
        if self.state != "on":
            return
        self.rounds += 1
        for name, n in counts.items():
            self.counts[name] = self.counts.get(name, 0) + n
        if now >= self.t0 + self.seconds:
            self.close()

    def close(self) -> None:
        """Stop the profiler, if it is on."""
        if self.state == "on":
            self.t1 = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"


def _span(name: str, on: bool):
    import contextlib

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class Window:
    """Drives the front end through the measured window."""

    def __init__(self, system: System, mix: dict, gen, conf: dict,
                 tracer: Optional[Tracer]):
        self.system, self.mix, self.gen, self.conf = system, mix, gen, conf
        self.tracer = tracer
        self.recs: Dict[int, Rec] = {}
        self.live: List[Rec] = []
        self.rounds = 0
        self.late: List[float] = []
        # [seconds into the window, requests due and unfinished] per round
        self.backlog: List[list] = []

    def submit(self, index: int, due: float) -> Rec:
        from repro.serve import Request

        prompt, new = self.gen.request(index)
        rec = Rec(index, Request(rid=index, prompt=prompt,
                                 max_new_tokens=new), due)
        self.system.front.submit(rec.req)
        self.late.append(time.perf_counter() - due)
        self.recs[index] = rec
        self.live.append(rec)
        return rec

    def round(self) -> List[Rec]:
        """One ``step()``; returns the requests it finished."""
        tr = self.tracer
        now = time.perf_counter()
        if tr is not None:
            tr.before_round(now)
        on = tr is not None and tr.state == "on"
        with _span("chipbench.round", on):
            self.system.front.step()
        t = time.perf_counter()
        self.rounds += 1
        done, counts = [], {}
        with _span("chipbench.harvest", on):
            for rec in self.live:
                n = len(rec.req.out_tokens)
                if n > rec.got:
                    if on:
                        c = self.system.family.counts(
                            self.conf, len(rec.req.prompt), rec.got,
                            n - rec.got)
                        for name, k in c.items():
                            counts[name] = counts.get(name, 0) + k
                    if rec.first is None:
                        rec.first = t
                    rec.last = t
                    rec.got = n
                if rec.req.done:
                    rec.finish = t
                    done.append(rec)
            if done:
                self.live = [r for r in self.live if r.finish is None]
        if tr is not None:
            tr.after_round(t, counts)
        return done

    # -- open loop ------------------------------------------------------
    def open_loop(self, seconds: float):
        arrivals = self.gen.arrivals()
        t_open = time.perf_counter()
        end = t_open + seconds
        nxt = next(arrivals)
        t_close = t_open
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while t_open + nxt[0] <= now:
                self.submit(nxt[1], t_open + nxt[0])
                nxt = next(arrivals)
            if not self.live:
                with _span("chipbench.wait", self._tracing()):
                    time.sleep(max(0.0, min(t_open + nxt[0], end) - now))
                continue
            self.round()
            t_close = time.perf_counter()
            self.backlog.append([t_close - t_open, len(self.live)])
        t_close = max(t_close, time.perf_counter())
        due = [r for r in self.recs.values() if r.due < t_close]
        return t_open, t_close, due

    def drain(self, due: List[Rec], cap_s: float) -> float:
        """Serve on, with nothing new sent, until every request of ``due``
        has its first two tokens (the least that times its first token and
        its pace), or ``cap_s`` has passed."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < cap_s and self.live and any(
                r.got < 2 and r.finish is None for r in due):
            self.round()
        return time.perf_counter() - t0

    # -- closed loop ----------------------------------------------------
    def prime(self) -> None:
        """Every client's first request in, served until each has its first
        token: the loop is running with every slot full."""
        now = time.perf_counter()
        for c in range(self.gen.clients):
            self.submit(c, now)
        while any(r.first is None for r in self.live):
            self.round()

    def closed_loop(self, seconds: float):
        nxt = max(self.recs) + 1
        t_open = time.perf_counter()
        end = t_open + seconds
        t_close = t_open
        start_tokens = {i: r.got for i, r in self.recs.items()}
        while time.perf_counter() < end:
            for _ in self.round():
                self.submit(nxt, time.perf_counter())
                nxt += 1
            t_close = time.perf_counter()
        tokens = sum(r.got - start_tokens.get(i, 0)
                     for i, r in self.recs.items())
        return t_open, t_close, tokens

    def _tracing(self) -> bool:
        return self.tracer is not None and self.tracer.state == "on"


# ----------------------------------------------------------------------
# the check against the reference
# ----------------------------------------------------------------------
NUMBERS = ("max_logit_gap", "mean_logit_gap", "argmax_miss_pct")


def sample(recs: List[Rec], seed: int, want_tokens: int, most: int = 8):
    """Requests that were served tokens, finished or not, drawn from the
    seed: the longest (prompt and served tokens) first, then others in a
    seeded order until ``want_tokens`` served tokens."""
    served = [r for r in recs if r.got > 0]
    if not served:
        return []
    served.sort(key=lambda r: r.index)
    longest = max(served, key=lambda r: (len(r.req.prompt) + r.got, -r.index))
    rest = [r for r in served if r is not longest]
    order = np.random.default_rng([seed, 11]).permutation(len(rest))
    out, n = [longest], longest.got
    for i in order:
        if n >= want_tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += rest[i].got
    return out


def summarize(gaps: np.ndarray) -> dict:
    """The compared numbers of per-token gaps below the reference's best:
    the widest, the mean, and the share (%) of tokens that are not the
    reference's first choice."""
    gaps = np.asarray(gaps, np.float64)
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "argmax_miss_pct": 100.0 * float(np.mean(gaps > 0))}


BLOCK = 256


@functools.partial(jax.jit, static_argnums=(0, 1))
def gaps(logits, prec, params, h_ref, h_ctl, served):
    """Per row: how far below the reference's best logit lies the served
    token, and the token the ``prec`` logits put first (the control's
    pick; with ``prec="f32"`` it is the reference's own argmax).
    ``logits(prec, params, h)`` is the family's LM head."""
    ref = logits("f32", params, h_ref)
    best = ref.max(-1)
    at = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    pick = logits(prec, params, h_ctl).argmax(-1)
    at_pick = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return best - at, best - at_pick


def compare(system: System, recs: List[Rec], precs=("f32",)) -> dict:
    """Each sampled request through the family's reference, prompt and
    the tokens served to its client together, padded to ``max_len``.  For
    ``"f32"`` the gap of each served token below the reference's best
    logit; for a control precision, at the same positions, the gap of the
    token that it puts first.  Rows go through the LM head in blocks of
    ``BLOCK`` (one shape, compiled once).  Returns ``summarize`` of each
    precision's gaps; ``precs`` holds ``"f32"``."""
    conf, family = system.conf, system.family
    max_len = conf["engine"]["max_len"]
    found = {p: [] for p in precs}
    compared = 0
    for rec in recs:
        out = np.asarray(rec.req.out_tokens[:rec.got], np.int32)
        prompt = np.asarray(rec.req.prompt, np.int32)
        seq = np.zeros((max_len,), np.int32)
        ctx = np.concatenate([prompt, out[:-1]])
        seq[:len(ctx)] = ctx
        rows = len(prompt) - 1 + np.arange(len(out))
        h = {p: family.hidden(conf, p, system.params, jnp.asarray(seq))
             for p in precs}
        for b in range(0, len(out), BLOCK):
            n = min(BLOCK, len(out) - b)
            r = np.zeros((BLOCK,), np.int32)
            r[:n] = rows[b:b + n]
            o = np.zeros((BLOCK,), np.int32)
            o[:n] = out[b:b + n]
            r = jnp.asarray(r)
            for p in precs:
                g_served, g_pick = gaps(family.logits, p, system.params,
                                        h["f32"][r], h[p][r], jnp.asarray(o))
                g = g_served if p == "f32" else g_pick
                found[p].append(np.asarray(g)[:n])
        compared += len(out)
    numbers = {p: summarize(np.concatenate(g)) if g else None
               for p, g in found.items()}
    return dict(numbers=numbers, compared=compared)


def valid_outputs(recs: List[Rec], vocab: int) -> bool:
    for r in recs:
        toks = r.req.out_tokens
        if r.finish is not None and len(toks) != r.req.max_new_tokens:
            return False
        if any(not 0 <= t < vocab for t in toks):
            return False
    return True


# ----------------------------------------------------------------------
# a whole run
# ----------------------------------------------------------------------
def devices_for(chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Cell:
    """One cell, built and warmed up once: its files, its devices and the
    system under test.  ``serve`` runs a window of its traffic from a
    seed; ``check`` compares what a window served with the reference."""

    def __init__(self, root: str, workload: str, seed: int, *,
                 require_tpu: bool = True, peaks: Optional[dict] = None,
                 log=None):
        from chipbench.peaks import peaks_for

        self.log = log or (lambda m: print(m, file=sys.stderr, flush=True))
        self.root, self.workload = root, workload
        self.bench = load_bench(root)
        self.cell = next(w for w in self.bench["workloads"]
                         if w["name"] == workload)
        self.conf, self.family = model.load_config(root, self.cell["config"])
        self.mix = load_mix(root, self.cell["traffic"])
        common.check_lengths(self.mix, self.conf["engine"]["max_len"])
        self.devices = devices_for(self.cell["chips"], require_tpu)
        self.kind = self.devices[0].device_kind
        self.peaks = peaks or peaks_for(self.kind)
        self.compiles = Compiles()
        self.system = System(self.conf, self.family, self.devices, seed)
        t = time.perf_counter()
        rounds = warm_up(self.system, self.mix, self.conf["vocab_size"])
        self.log(f"set-up: weights {self.system.weights_s:.3f}s, engines "
                 f"{self.system.build_s:.3f}s, warm-up "
                 f"{time.perf_counter() - t:.3f}s ({rounds} rounds), compile "
                 f"{self.compiles.seconds:.3f}s over {self.compiles.count} "
                 f"traces/compiles/cache loads")

    def serve(self, seed: int, seconds: float, trace_on: bool = False,
              t_start: Optional[float] = None) -> dict:
        """A window of the cell's traffic from ``seed`` (a closed loop is
        primed first).  Returns the end-to-end values (``setup_s`` from
        ``t_start``), the requests and, when traced, the tracer."""
        mix, system = self.mix, self.system
        gen = generator(mix)(mix, seed, self.conf["vocab_size"])
        tracer = None
        if trace_on:
            # opens only once ``start`` is set, after a closed loop's priming
            tracer = Tracer(float("inf"),
                            min(float(mix["trace_seconds"]), seconds),
                            self.family.counts(self.conf, 0, 0, 0))
        win = Window(system, mix, gen, self.conf, tracer)
        if gen.kind == "closed_loop":
            win.prime()
        values = {}
        if t_start is not None:
            values["setup_s"] = time.perf_counter() - t_start
        c0 = self.compiles.count
        stats0 = system.stats()
        if tracer is not None:
            start = min(float(mix["trace_start_s"]), seconds - tracer.seconds)
            tracer.start = time.perf_counter() + max(0.0, start)
        if gen.kind == "open_loop":
            t_open, t_close, due = win.open_loop(seconds)
            compiled = self.compiles.count - c0
            drained_s = win.drain(due, float(mix["drain_cap_s"]))
            started = [r for r in due if r.first is not None]
            ttft = [1e3 * (r.first - r.due) for r in started]
            tpot = [1e3 * (r.last - r.first) / (r.got - 1) for r in started
                    if r.got > 1]
            attempted, failed = len(due), len(due) - len(started)
            values["backlog"] = win.backlog
            for q in (50, 90):
                if ttft:
                    values[f"ttft_p{q}_ms"] = percentile(ttft, q)
                if tpot:
                    values[f"tpot_p{q}_ms"] = percentile(tpot, q)
            done = sum(r.finish is not None for r in due)
            self.log(
                f"window: {t_close - t_open:.3f}s, {win.rounds} rounds in "
                f"all, {attempted} requests due, {len(started)} started and "
                f"{done} done after a {drained_s:.3f}s drain, {failed} "
                f"without a first token; ttft p50 "
                f"{percentile(ttft, 50) if ttft else float('nan'):.1f} ms, "
                f"tpot p50 "
                f"{percentile(tpot, 50) if tpot else float('nan'):.2f} ms; "
                f"generator late p90 {1e3 * percentile(win.late, 90):.2f} "
                f"ms, max {1e3 * max(win.late):.2f} ms")
        else:
            t_open, t_close, tokens = win.closed_loop(seconds)
            compiled = self.compiles.count - c0
            attempted, failed = len(win.recs), 0
            values["output_tok_s"] = tokens / (t_close - t_open)
            self.log(f"window: {t_close - t_open:.3f}s, {win.rounds} rounds "
                     f"in all, {tokens} tokens, {len(win.recs)} requests "
                     f"submitted, "
                     f"{sum(r.finish is not None for r in win.recs.values())}"
                     f" done")
        if tracer is not None:
            tracer.close()
        self.log(f"in the window: {compiled} traces/compiles/cache loads; "
                 f"engine counters over the window "
                 f"{counter_deltas(stats0, system.stats())} of "
                 f"{pool_pages(system)} pool pages")
        return dict(values=values, recs=list(win.recs.values()),
                    attempted=attempted, failed=failed, tracer=tracer)

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)

    def check(self, served: dict, seed: int, precs=("f32",)) -> dict:
        """The reference over a seeded sample of the served requests; the
        page pools are freed first (``system.front.reset()`` makes new
        ones)."""
        self.system.free_pools()
        want = self.conf["checks"]["min_compared_tokens"]
        picked = sample(served["recs"], seed, want)
        t = time.perf_counter()
        cmp = compare(self.system, picked, precs)
        self.log(f"reference: {len(picked)} requests "
                 f"({sum(r.finish is None for r in picked)} unfinished), "
                 f"{cmp['compared']} served tokens, "
                 f"{time.perf_counter() - t:.3f}s")
        cmp["outputs_ok"] = valid_outputs(served["recs"],
                                          self.conf["vocab_size"])
        return cmp


def judge(limits: dict, cmp: dict, failed: int, judged: str = "f32"):
    """``correct`` and the compared numbers beside their limits: each of
    ``NUMBERS`` that the configuration's ``checks`` gives a limit, read
    from precision ``judged`` (the program's served tokens for ``"f32"``,
    a control's picks otherwise)."""
    got = cmp["numbers"][judged] or {}
    checks = {name: {"value": got.get(name), "limit": limits[name]}
              for name in NUMBERS if name in limits}
    checks["compared_tokens"] = {"value": cmp["compared"],
                                 "limit": limits["min_compared_tokens"]}
    checks["never_started"] = {"value": failed, "limit": 0}
    correct = (all(c["value"] is not None and c["value"] <= c["limit"]
                   for n, c in checks.items() if n in NUMBERS)
               and cmp["compared"] >= limits["min_compared_tokens"]
               and failed == 0 and cmp["outputs_ok"])
    return bool(correct), checks


def run(root: str, workload: str, seed: int, seconds: float, trace_on: bool,
        *, t_start: float, require_tpu: bool = True,
        peaks: Optional[dict] = None, log=None, on_trace=None,
        control: Optional[str] = None) -> dict:
    """One run; returns the result line's object (``checks`` last).
    ``on_trace(raw, host)``, if given, sees the extracted trace and the
    host-side readings of the traced window before they are reduced.
    ``control`` (``"int8"`` or ``"fp8"``) judges, in the served tokens'
    place, the tokens that the reference in that precision puts first at
    the same positions: the check's control, never a benchmark run."""
    cell = Cell(root, workload, seed, require_tpu=require_tpu, peaks=peaks,
                log=log)
    served = cell.serve(seed, seconds, trace_on, t_start=t_start)
    device = dict(platform=cell.devices[0].platform, kind=cell.kind,
                  count=len(jax.devices()),
                  memory_peak_bytes=cell.memory_peak())
    judged = control or "f32"
    cmp = cell.check(served, seed, tuple(dict.fromkeys(("f32", judged))))
    correct, checks = judge(cell.conf["checks"], cmp, served["failed"],
                            judged)
    if not cmp["outputs_ok"]:
        cell.log("outputs: a finished request holds the wrong number of "
                 "tokens or a token outside the vocabulary")
    result = dict(correct=correct, attempted=served["attempted"],
                  failed=served["failed"])
    if trace_on:
        result["metrics"], extra = per_layer(cell, served["tracer"],
                                             on_trace)
        device.update(extra["device"])
        result["breakdown"] = extra["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": served["values"][m["name"]],
                        "unit": m["unit"]}
            for m in cell_metrics(cell.bench, workload, "end_to_end")
            if m["name"] in served["values"]}
    result["device"] = device
    for name, c in checks.items():
        cell.log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


def reading(raw: dict, host: dict) -> dict:
    """What a metric reader reads: the trace's record with the host-side
    readings of the traced window (see ``chipbench/metrics``)."""
    return dict(host, trace=trace.reduce(raw, devices=host["devices"]))


def counter_deltas(s0, s1) -> dict:
    keys = ("prefill_chunks", "decode_steps", "decode_dispatches",
            "tokens_out", "prompt_tokens", "prefix_hit_tokens", "pool_stalls",
            "preemptions", "prefill_retraces")
    out = {k: getattr(s1, k) - getattr(s0, k) for k in keys}
    out["pages_peak"] = s1.pages_peak
    return out


def pool_pages(system: System) -> int:
    """Pages of the first engine's pool, its reserved page among them."""
    return int(system.engines[0].num_pages)


def per_layer(cell: Cell, tracer: Tracer, on_trace=None):
    if tracer is None or tracer.state != "done":
        raise RuntimeError("the traced sub-window never opened")
    t = time.perf_counter()
    try:
        raw = trace.extract(trace.find_xplane(tracer.dir))
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    host = dict(tracer.counts, rounds=tracer.rounds,
                host_s=tracer.t1 - tracer.t0, chips=len(cell.devices),
                peaks=cell.peaks, devices=[d.id for d in cell.devices])
    if on_trace is not None:
        on_trace(raw, host)
    run_ = reading(raw, host)
    cell.log(f"trace read in {time.perf_counter() - t:.3f}s")
    metrics = {}
    for m in cell_metrics(cell.bench, cell.workload, "per_layer"):
        value = reader(cell.root, m["name"])(run_)
        if value is None:
            cell.log(f"METRIC MISSING: {m['name']} found nothing to read in "
                     f"the traced window; it is left out of the line")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    record = run_["trace"]
    cell.log(f"traced window: {record['window_s']:.6f}s on the trace, "
             f"{run_['host_s']:.6f}s on the host, {tracer.rounds} rounds, "
             f"busy {record['busy_s']:.6f}s per chip over "
             f"{len(record['devices'])} chips")
    extra = dict(device=dict(busy_s=record["busy_s"],
                             window_s=record["window_s"]),
                 breakdown=trace.breakdown(record))
    return metrics, extra
