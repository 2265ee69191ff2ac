"""Host time of one ``ClusterFrontEnd.step()`` round in the traced
window: window / rounds, in ms (admission, prefill chunks, one fused
decode window and the front end's bookkeeping)."""


def read(run):
    if run["rounds"] <= 0:
        return None
    return 1e3 * run["host_s"] / run["rounds"]
