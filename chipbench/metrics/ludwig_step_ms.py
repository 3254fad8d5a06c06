"""Wall time of the window over the LC-LB timesteps completed in it, in
milliseconds.  Host clock; the window ends with a block on the state."""


def read(run):
    rec = run["record"]
    if not rec.get("steps"):
        return None
    return rec["window_s"] / rec["steps"] * 1e3
