"""Decode attention's share of its roofline, in %: the least time the
chip needs for the work (the larger of its operations over the bf16 peak
and of the K/V bytes it must read over HBM bandwidth: ``attn_flops`` and
``attn_bytes`` of the configuration family's ``counts`` in
``chipbench/families/<family>.py`` for the tokens decoded in the traced
window, split evenly over the chips) over the device time of the
``paged_attention`` kernel's ops on the first chip."""
import re

KERNEL = re.compile(r"^paged_attention(\.\d+)*$")


def read(run):
    devs = run["trace"]["devices"]
    if not devs or run["attn_bytes"] <= 0:
        return None
    t = sum(s for name, s in devs[0]["op_s"].items() if KERNEL.match(name))
    if t <= 0:
        return None
    pk = run["peaks"]
    need = max(run["attn_flops"] / pk["bf16_flops_per_s"],
               run["attn_bytes"] / pk["hbm_bytes_per_s"]) / run["chips"]
    return 100.0 * need / t
