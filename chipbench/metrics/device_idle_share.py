"""Share of the traced window in which no op ran on the device, in %,
averaged over the chips used: 1 - (union of op intervals) / window."""


def read(run):
    tr = run["trace"]
    if not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
