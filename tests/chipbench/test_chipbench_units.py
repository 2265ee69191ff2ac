"""The benchmark's yardstick on the CPU: trace reduction, readers, the
dense family's FLOP count, weights and reference, the peak table, the
generators, and the refusals of a run that has no chip, no program or no
family.  Nothing here touches a TPU."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench_tiny import MIXES, REPO, TINY

sys.path.insert(0, REPO)

from chipbench import harness, model, peaks, trace  # noqa: E402
from chipbench.gen import closed_loop, common, open_loop  # noqa: E402

FIXTURES = os.path.join(REPO, "chipbench", "fixtures")
BENCH = harness.load_bench(REPO)
CELLS = [w["name"] for w in BENCH["workloads"]]
DENSE = model.load_family(REPO, "dense_gqa")


def _raw(device_ops, window=(0.0, 100.0), modules=()):
    """A raw trace: one TPU plane per entry of ``device_ops``."""
    planes = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        [trace.WINDOW, window[0], window[1] - window[0]],
        ["chipbench.round", 10.0, 20.0]]}]}]
    for i, ops in enumerate(device_ops):
        lines = [{"name": trace.OPS_LINE, "events": [list(e) for e in ops]}]
        if modules:
            lines.append({"name": trace.MODULES_LINE,
                          "events": [list(e) for e in modules]})
        planes.append({"name": f"/device:TPU:{i}", "lines": lines})
    return {"planes": planes}


def test_union_merges_overlaps():
    assert trace.union([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]]) == \
        [[0, 3], [5, 9], [10, 11]]


def test_overlap_of_interval_lists():
    assert trace.overlap([[0, 3], [5, 9]], [[2, 6], [8, 20]]) == 1 + 1 + 1
    assert trace.overlap([[0, 1]], []) == 0


def test_idle_share_counts_overlapping_ops_once():
    # busy: [10, 40] (three overlapping ops, one nested) + [60, 70], and an
    # op half outside the window [0, 100] counts only its inside part
    ops = [["fusion.1", 10, 20], ["fusion.2", 20, 20], ["copy.3", 15, 5],
           ["all-gather.4", 60, 10], ["fusion.5", 95, 20]]
    rec = trace.reduce(_raw([ops]))
    assert rec["window_s"] == pytest.approx(100e-9)
    assert rec["busy_s"] == pytest.approx(45e-9)
    read = harness.reader(REPO, "device_idle_share.closed")
    assert read(dict(trace=rec)) == pytest.approx(55.0)
    gaps = sorted(g[1] for g in rec["devices"][0]["gaps"])
    assert gaps == pytest.approx([10e-9, 20e-9, 25e-9])


def test_busy_is_averaged_over_devices_and_filtered_by_id():
    rec = trace.reduce(_raw([[["f", 0, 50]], [["f", 0, 10]]]))
    assert rec["busy_s"] == pytest.approx(30e-9)
    rec = trace.reduce(_raw([[["f", 0, 50]], [["f", 0, 10]]]), devices=[1])
    assert [d["id"] for d in rec["devices"]] == [1]
    assert rec["busy_s"] == pytest.approx(10e-9)


def test_readers_return_nothing_without_events():
    run = dict(trace=trace.reduce(_raw([])), rounds=0, host_s=1.0, flops=0,
               chips=1, peaks={"bf16_flops_per_s": 1.0})
    for m in BENCH["per_layer"]:
        assert harness.reader(REPO, m["name"])(run) is None, m["name"]


def test_a_trace_without_the_window_span_is_refused():
    raw = _raw([[["f", 0, 5]]])
    raw["planes"][0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce(raw)


def test_breakdown_lists_top_ops_and_labelled_gaps():
    ops = [["a", 0, 10], ["b", 30, 40], ["a", 80, 5]]
    bd = trace.breakdown(trace.reduce(_raw([ops])))
    assert bd["device_ops"][0] == ["b", pytest.approx(40e-9)]
    assert dict((n, s) for n, s in bd["device_ops"])["a"] == \
        pytest.approx(15e-9)
    # the gap [10, 30] has the host's round span [10, 30] over it
    assert ["chipbench.round", pytest.approx(20e-9)] in bd["idle_gaps"]
    assert len(bd["idle_gaps"]) <= 10


def test_round_ms_and_mfu():
    run = dict(rounds=40, host_s=8.0, flops=4e15, chips=2,
               peaks={"bf16_flops_per_s": 200e12})
    assert harness.reader(REPO, "round_ms.closed")(run) == \
        pytest.approx(200.0)
    assert harness.reader(REPO, "mfu")(run) == pytest.approx(125.0)


def test_reader_lookup_by_name_then_stem():
    with pytest.raises(FileNotFoundError):
        harness.reader(REPO, "no_such_metric.open")
    run = dict(rounds=4, host_s=1.0)
    assert harness.reader(REPO, "round_ms.open")(run) == pytest.approx(250.0)


SMALL = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "vocab_size": 10}


def test_flops_against_a_hand_count():
    # one layer, one token: q 8x8, k and v 8x4 each, o 8x8, three 8x16
    # mlp matrices = 64 + 64 + 64 + 384 = 576 multiply-adds
    assert DENSE.layer_matmul(SMALL) == 2 * 576
    # positions 3, 4: 4 + 5 keys, 4 heads of 2 dims, qk and pv
    assert DENSE.attention(SMALL, 3, 2) == 4 * 4 * 2 * 9
    assert DENSE.head(SMALL) == 2 * 8 * 10
    # a 3-token prompt and its first two output tokens: the prompt fed at
    # positions 0..2, output token 0 fed at position 3, two LM heads
    want = (2 * (3 * 1152 + 32 * 6)
            + 2 * (1152 + 32 * 4)
            + 2 * 160)
    assert DENSE.served(SMALL, 3, 0, 2) == want
    # served in two rounds, the sum is the same
    assert DENSE.served(SMALL, 3, 0, 1) + DENSE.served(SMALL, 3, 1, 1) == want
    assert DENSE.served(SMALL, 3, 5, 0) == 0


def test_decode_attention_and_counts_against_a_hand_count():
    # output tokens 0..2 of a 3-token prompt: token 0 comes from the
    # prompt; tokens 1 and 2 are fed at positions 3 and 4 and read 4 + 5
    # cached rows of 2 KV heads x 2 dims, K and V, 2 bytes each, a layer
    ops, nbytes = DENSE.decode_attention(SMALL, 3, 0, 3)
    assert ops == 2 * DENSE.attention(SMALL, 3, 2)
    assert nbytes == 2 * 9 * 4 * 2 * 2
    assert DENSE.decode_attention(SMALL, 3, 0, 1) == (0, 0)
    # what the readers get: these under their names, and 0 for no token
    assert DENSE.counts(SMALL, 3, 0, 3) == {
        "flops": DENSE.served(SMALL, 3, 0, 3), "attn_flops": ops,
        "attn_bytes": nbytes}
    assert set(DENSE.counts(SMALL, 0, 0, 0).values()) == {0}


# The dense family's draw and reference as they stood when they were moved
# behind the family module: the tiny configuration's weights from SEED
# (each leaf's sum and sum of magnitudes), and the reference's float32
# hidden states and logits of ROW (sum, sum of magnitudes, sum weighted by
# the row's position from 1).  A different draw order, rule or layer moves
# them by far more than the tolerance, which only absorbs rounding.
SEED = 2 ** 33 + 17
ROW = (np.arange(40) * 37 + 11) % 256
DRAWN = {
    "['blocks']['p0']['attn']['wk']": (0.13382303714752197, 369.70014703273773),
    "['blocks']['p0']['attn']['wo']": (6.574276447296143, 740.1997742652893),
    "['blocks']['p0']['attn']['wq']": (5.072579648345709, 743.4674925543368),
    "['blocks']['p0']['attn']['wv']": (-6.767151594161987, 373.54111981391907),
    "['blocks']['p0']['ln1']": (2.6155319213867188, 11.139411926269531),
    "['blocks']['p0']['ln2']": (-0.43225860595703125, 9.086372375488281),
    "['blocks']['p0']['mlp']['w_down']": (7.518952786922455, 1037.105009496212),
    "['blocks']['p0']['mlp']['w_gate']": (-14.050286263227463,
                                          1458.1338617503643),
    "['blocks']['p0']['mlp']['w_up']": (-12.08928182721138, 1470.6593637764454),
    "['embed']['tok']": (9.566559873521328, 1483.0394733920693),
    "['final_norm']": (0.2711033821105957, 4.817612171173096),
}
HIDDEN = (140.07219859113684, 2040.6658977320767, 1394.2392806546995)
LOGITS = (-191.26699419791112, 7323.531036352913, -7281.21058804763)


@pytest.fixture(scope="module")
def dense_params():
    import functools

    bundle = DENSE.build(TINY)
    return model.make_params(bundle, functools.partial(DENSE.draw, TINY),
                             SEED)


def test_the_dense_draw_has_not_moved(dense_params):
    import jax

    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(dense_params)[0]:
        a = np.asarray(leaf.astype(np.float32), np.float64)
        got[jax.tree_util.keystr(path)] = (a.sum(), np.abs(a).sum())
    assert set(got) == set(DRAWN)
    for name, want in DRAWN.items():
        assert got[name] == pytest.approx(want, rel=1e-6, abs=1e-6), name


def test_the_dense_reference_has_not_moved(dense_params):
    import jax.numpy as jnp

    h = DENSE.hidden(TINY, "f32", dense_params, jnp.asarray(ROW, jnp.int32))
    lg = DENSE.logits("f32", dense_params, h)
    pos = np.arange(1, len(ROW) + 1)[:, None]
    for got, want in ((h, HIDDEN), (lg, LOGITS)):
        a = np.asarray(got, np.float64)
        assert (a.sum(), np.abs(a).sum(), (a * pos).sum()) == \
            pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("family,match", [(None, "names no model family"),
                                          ("no_such_family", "no module")])
def test_a_config_without_a_family_module_is_refused(tmp_path, family,
                                                     match):
    conf = {k: v for k, v in TINY.items() if k != "family"}
    if family:
        conf["family"] = family
    os.makedirs(tmp_path / "chipbench" / "configs")
    with open(tmp_path / "chipbench" / "configs" / "tiny.json", "w") as f:
        json.dump(conf, f)
    with pytest.raises(ValueError, match=match) as err:
        model.load_config(str(tmp_path), "tiny")
    assert "tiny.json" in str(err.value)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice, match="TPU v4"):
        peaks.peaks_for("TPU v4")


def _mix(name):
    return MIXES[name] if name in MIXES else harness.load_mix(REPO, name)


@pytest.mark.parametrize("mix", ["tiny-open", "decode-closed", "tiny-closed"])
def test_generators_are_deterministic_in_the_seed(mix):
    m = _mix(mix)
    cls = harness.generator(m)
    big = 2 ** 33 + 12345
    a, b, c = cls(m, big, 1000), cls(m, big, 1000), cls(m, 7, 1000)
    n = common.pool_size(m)
    for i in range(70):
        pa, na = a.request(i)
        pb, nb = b.request(i)
        assert na == nb and np.array_equal(pa, pb)
    # another seed: the same multiset of lengths in another order
    assert sorted(len(a.request(i)[0]) for i in range(n)) == \
        sorted(len(c.request(i)[0]) for i in range(n))
    assert sorted(a.request(i)[1] for i in range(n)) == \
        sorted(c.request(i)[1] for i in range(n))
    assert [a.request(i)[1] for i in range(n)] != \
        [c.request(i)[1] for i in range(n)]
    if cls is open_loop.OpenLoop:
        times = lambda g: [t for (t, _), _ in zip(g.arrivals(), range(100))]  # noqa: E731
        assert times(a) == times(b)
        # the first block of gaps is one multiset in every seed
        assert times(a)[n] == pytest.approx(times(c)[n])
        assert times(a)[:n] != times(c)[:n]
    else:
        assert cls is closed_loop.ClosedLoop


def test_length_pools_hold_the_mix_bounds():
    for mix in ("tiny-open", "decode-closed", "tiny-closed"):
        m = _mix(mix)
        for key in ("prompt", "output"):
            q = common.quantiles(m[key], common.pool_size(m))
            assert q.min() >= m[key]["min"] and q.max() <= m[key]["max"]
    q = common.quantiles({"dist": "lognormal", "median": 512, "sigma": 0.7,
                          "min": 64, "max": 1536})
    assert q[len(q) // 2] == pytest.approx(512, rel=0.05)


def test_prefill_buckets():
    assert harness.prefill_buckets([64, 256], 256) == [64, 256]
    assert harness.prefill_buckets([300], 256) == [64, 256]
    assert harness.prefill_buckets([5, 513], 256) == [8, 256]
    # the decode mix's prompts (64-256) fill at most one chunk
    m = harness.load_mix(REPO, "decode-closed")
    q = common.quantiles(m["prompt"], common.pool_size(m))
    assert harness.prefill_buckets(q, 256) == [128, 256]


def _rec(index, prompt, got, done):
    req = types.SimpleNamespace(prompt=[0] * prompt, out_tokens=[1] * got)
    return harness.Rec(index, req, 0.0, finish=1.0 if done else None,
                       got=got)


def test_the_sample_takes_unfinished_requests_and_the_longest():
    recs = [_rec(0, 100, 40, True), _rec(1, 200, 300, False),
            _rec(2, 64, 0, False), _rec(3, 80, 250, False),
            _rec(4, 90, 30, True)]
    picked = harness.sample(recs, 2 ** 33 + 1, want_tokens=500)
    assert picked[0].index == 1
    assert 2 not in [r.index for r in picked]
    assert sum(r.got for r in picked) >= 500
    # the same seed draws the same sample; the cap on requests holds
    assert [r.index for r in harness.sample(recs, 2 ** 33 + 1, 500)] == \
        [r.index for r in picked]
    assert len(harness.sample(recs, 3, 10 ** 6, most=2)) == 2
    assert harness.sample([_rec(0, 10, 0, False)], 3, 10) == []


def test_summarize_and_judge():
    nums = harness.summarize([0.0, 0.0, 0.3, 0.1])
    assert nums == {"max_logit_gap": pytest.approx(0.3),
                    "mean_logit_gap": pytest.approx(0.1),
                    "argmax_miss_pct": pytest.approx(50.0)}
    limits = {"max_logit_gap": 0.5, "mean_logit_gap": 0.05,
              "min_compared_tokens": 4}
    cmp = dict(numbers={"f32": nums, "fp8": None}, compared=4,
               outputs_ok=True)
    ok, checks = harness.judge(limits, cmp, failed=0)
    assert not ok and checks["mean_logit_gap"]["value"] > 0.05
    assert "argmax_miss_pct" not in checks
    ok, _ = harness.judge(dict(limits, mean_logit_gap=0.2), cmp, failed=0)
    assert ok
    assert not harness.judge(dict(limits, mean_logit_gap=0.2), cmp,
                             failed=1)[0]
    assert not harness.judge(dict(limits, min_compared_tokens=5), cmp, 0)[0]
    # a control that gave no number has failed
    assert not harness.judge(limits, cmp, 0, judged="fp8")[0]


def test_the_cli_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", "3", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_devices_for_refuses_too_few_chips():
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.devices_for(1, require_tpu=True)
    with pytest.raises(harness.NoChip, match="4 chips"):
        harness.devices_for(4, require_tpu=False)


def test_the_benchmark_alone_exits_without_a_result(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths
    has no system to run."""
    root = tmp_path / "bare"
    root.mkdir()
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1"],
        cwd=root, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_json_names_files_that_exist():
    for c in BENCH["configs"]:
        conf = json.load(open(os.path.join(REPO, c["file"])))
        assert conf["reduced"] == c["reduced"]
        assert c["source"] == conf["source"]
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "families", f"{conf['family']}.py"))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "mixes", f"{w['traffic']}.json"))
        mine = {m["name"] for m in harness.cell_metrics(
            BENCH, w["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine & e2e) >= 2
        layer = harness.cell_metrics(BENCH, w["name"], "per_layer")
        assert layer
        for m in layer:
            harness.reader(REPO, m["name"])
            assert m["moves"] in mine


@pytest.mark.parametrize("cell", CELLS)
def test_each_cells_readers_find_their_metric_in_a_chip_trace(cell):
    """A trimmed trace recorded on the chip: every per-layer metric the
    cell declares has a value there (none is looked up by a name the
    program does not emit)."""
    fx = trace.load(os.path.join(FIXTURES, f"{cell}.json.gz"))
    run = harness.reading(fx["raw"], fx["host"])
    assert run["trace"]["devices"], "the fixture holds no device ops"
    assert 0 < run["trace"]["busy_s"] <= run["trace"]["window_s"]
    for m in harness.cell_metrics(BENCH, cell, "per_layer"):
        value = harness.reader(REPO, m["name"])(run)
        assert value is not None, m["name"]
        assert value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, m["name"]
