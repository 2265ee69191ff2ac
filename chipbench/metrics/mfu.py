"""The whole model step's share of the chips' bf16 peak, in %: forward
operations of the tokens returned in the traced window (``flops`` of the
configuration family's ``counts``, ``chipbench/families/<family>.py``)
over window x chips x peak."""


def read(run):
    if run["flops"] <= 0 or run["host_s"] <= 0:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * run["flops"] / (run["host_s"] * peak)
