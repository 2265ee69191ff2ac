"""A checkout with a tiny configuration for the benchmark's CPU tests: the
benchmark's own files, the program beside them, and a BENCHMARK.json whose
cells serve a two-layer model of the phi4-mini-3.8b architecture."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "arch": "phi4-mini-3.8b", "family": "dense_gqa",
    "source": "tiny test model", "tp": 1,
    "dtype": "bfloat16", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "max_position_embeddings": 128, "rope_theta": 10000.0,
    "partial_rotary_factor": 1.0, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    "engine": {"batch_size": 4, "max_len": 128, "window": 4,
               "prefill_chunk": 16},
    "checks": {"max_logit_gap": 0.1, "mean_logit_gap": 0.001,
               "min_compared_tokens": 96},
}
LENGTHS = {"prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                      "min": 8, "max": 48},
           "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 8, "max": 32}}
MIXES = {
    "tiny-open": dict(generator="open_loop", rate_per_s=20.0, drain_cap_s=60,
                      trace_start_s=0.5, trace_seconds=1.0, **LENGTHS),
    "tiny-closed": dict(generator="closed_loop", clients=4, trace_start_s=0.2,
                        trace_seconds=1.0, drain_cap_s=60, **LENGTHS),
}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(tmp_path, conf=None, families=None) -> str:
    """A checkout under ``tmp_path``; ``conf`` replaces keys of TINY, and
    each of ``families`` (name -> source) is written to
    ``chipbench/families/<name>.py``."""
    root = os.path.join(str(tmp_path), "checkout")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(root, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(dict(TINY, **(conf or {})), f)
    for name, source in (families or {}).items():
        with open(os.path.join(root, "chipbench", "families", f"{name}.py"),
                  "w") as f:
            f.write(source)
    for name, mix in MIXES.items():
        with open(os.path.join(root, "chipbench", "mixes", f"{name}.json"),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tiny test model",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "tests"}]
    cells = {"tiny-open": "tiny-open", "tiny-closed": "tiny-closed"}
    bench["workloads"] = [{"name": n, "config": "tiny", "traffic": t,
                           "chips": 1, "why": "tests"}
                          for n, t in cells.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-closed"]
    # the open-loop tails, which no cell of the benchmark reports yet
    bench["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["tiny-open"]}
        for name in ("ttft_p90_ms", "tpot_p90_ms")]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
