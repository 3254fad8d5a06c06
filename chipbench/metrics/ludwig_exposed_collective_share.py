"""Share of device busy time in which a chip runs only a collective (the
halo exchange's collective-permutes, with no other operation beside
them), in percent, averaged over the chips.  Device trace."""


def read(run):
    tr = run["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    return tr["collective_exposed_s"] / tr["busy_s"] * 100.0
