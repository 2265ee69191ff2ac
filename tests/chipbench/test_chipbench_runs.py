"""Whole runs of the harness at a tiny size on the CPU, with the look for a
chip skipped: sound runs come out correct, and each fault a serving cell
can have, planted under the timed path, comes out not correct.  The
control (the reference put in the program's place, in fp8) comes out not
correct by the same limits.  A model family comes in as a file alone."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import pytest

import chipbench_tiny as T

sys.path.insert(0, T.REPO)

from chipbench import harness  # noqa: E402

SEED = 2 ** 33 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return T.make_root(tmp_path_factory.mktemp("chipbench"))


def _run(root, workload, trace_on=False, seconds=2.0):
    return harness.run(root, workload, SEED, seconds, trace_on,
                       t_start=0.0, require_tpu=False, peaks=T.CPU_PEAKS,
                       log=lambda m: None)


@pytest.mark.parametrize("workload,metric", [("tiny-open", "ttft_p90_ms"),
                                             ("tiny-closed", "output_tok_s")])
def test_a_sound_run_is_correct(root, workload, metric):
    res = _run(root, workload)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["compared_tokens"]["value"] >= \
        T.TINY["checks"]["min_compared_tokens"]


def test_a_traced_run_is_correct_and_reports_the_window(root):
    res = _run(root, "tiny-closed", trace_on=True)
    assert res["correct"] is True, res["checks"]
    # the CPU backend has no TPU plane: the device readers find nothing
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "round_ms.closed" in res["metrics"]


def _state_unchanged(monkeypatch):
    """A decode step that returns the KV pool it was given."""
    from repro.models import transformer

    orig = transformer.paged_decode_step

    def step(params, cfg, flags, cache, *a, **k):
        logits, _ = orig(params, cfg, flags, cache, *a, **k)
        return logits, cache

    monkeypatch.setattr(transformer, "paged_decode_step", step)


def _half_batch(monkeypatch):
    """Half of the batch left out: odd slots get the even slots' tokens."""
    from repro.serve import engine

    orig = engine._select_next

    def select(sampling, logits, keys, act):
        nxt, keys = orig(sampling, logits, keys, act)
        idx = (jnp.arange(nxt.shape[0]) // 2) * 2
        return nxt[idx], keys

    monkeypatch.setattr(engine, "_select_next", select)


def _exchange_left_out(monkeypatch):
    """The sum over TP shards left out: attention's output projection
    reads only the first half of the heads, as one of two shards holds
    them before its all-reduce."""
    from repro.models import transformer

    orig = transformer._apply_attn

    def attn(p, *a, **k):
        keep = p["wo"].shape[0] // 2
        return orig(dict(p, wo=p["wo"].at[keep:].set(0)), *a, **k)

    monkeypatch.setattr(transformer, "_apply_attn", attn)


def _token_altered(monkeypatch):
    """Each decode window's last token of every slot altered where the
    engine produces it."""
    from repro.serve import engine

    orig = engine.ServeEngine.decode_many

    def decode_many(self, n):
        before = [None if r is None else len(r.out_tokens)
                  for r in self.slots]
        reqs = list(self.slots)
        produced = orig(self, n)
        for r, b in zip(reqs, before):
            if r is not None and len(r.out_tokens) > b:
                vocab = self.bundle.cfg.vocab_size
                r.out_tokens[-1] = (r.out_tokens[-1] + 1) % vocab
        return produced

    monkeypatch.setattr(engine.ServeEngine, "decode_many", decode_many)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _exchange_left_out, _token_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root, "tiny-closed")
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_control_fails_the_limit(root):
    """The reference in fp8 in the program's place: at the same prompts
    and served tokens, the tokens it puts first lie further below the
    reference's best, on average, than the limit allows, and the run that
    judges them comes out not correct.  (At this size int8's rounding is
    too fine to show; the cell's int8 readings come from the chip.)"""
    res = harness.run(root, "tiny-closed", SEED, 2.0, False, t_start=0.0,
                      require_tpu=False, peaks=T.CPU_PEAKS,
                      log=lambda m: None, control="fp8")
    assert res["correct"] is False
    assert res["checks"]["compared_tokens"]["value"] >= \
        T.TINY["checks"]["min_compared_tokens"]
    mean = res["checks"]["mean_logit_gap"]
    assert mean["value"] > mean["limit"]


with open(os.path.join(T.REPO, "chipbench", "families", "dense_gqa.py")) as f:
    DENSE_SOURCE = f.read()
RESIDUAL = 'x = x + _mm(o, a["wo"], prec)'


@pytest.mark.parametrize("source,correct", [
    (DENSE_SOURCE, True),
    (DENSE_SOURCE.replace(RESIDUAL, 'x = _mm(o, a["wo"], prec)'), False),
], ids=["sound", "reference-drops-a-residual"])
def test_a_family_comes_in_as_a_file_alone(tmp_path, source, correct):
    """A checkout that adds ``families/tiny_gqa.py`` and a configuration
    naming it, and edits no other file, serves a cell through that
    family; the cell is judged by that file's reference, so a reference
    that leaves out attention's residual add fails the same run."""
    assert RESIDUAL in DENSE_SOURCE
    root = T.make_root(tmp_path, dict(family="tiny_gqa"),
                       families={"tiny_gqa": source})
    res = _run(root, "tiny-closed")
    assert res["correct"] is correct, res["checks"]
    gap = res["checks"]["max_logit_gap"]
    assert (gap["value"] <= gap["limit"]) is correct


def test_a_tp2_cell_runs_correct_on_two_virtual_devices(tmp_path):
    """The sharded path (weights drawn in their TP shardings, the engine
    over a two-device mesh, the reference under GSPMD) in a process of its
    own, where XLA is given two host devices before JAX starts."""
    root = T.make_root(tmp_path, dict(tp=2))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-tp2", "config": "tiny",
                               "traffic": "tiny-closed", "chips": 2,
                               "why": "tests"})
    for m in bench["end_to_end"]:
        if "tiny-closed" in m.get("workloads", []):
            m["workloads"].append("tiny-tp2")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{root!r}, {os.path.join(root, "src")!r},
                        {os.path.dirname(__file__)!r}]
        import jax
        assert len(jax.devices()) == 2
        import chipbench_tiny as T
        from chipbench import harness
        res = harness.run({root!r}, "tiny-tp2", 5, 2.0, False, t_start=0.0,
                          require_tpu=False, peaks=T.CPU_PEAKS,
                          log=lambda m: None)
        print(json.dumps(res))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["output_tok_s"]["value"] > 0
