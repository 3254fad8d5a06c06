"""Wall time of the window over the Wilson-CG solves completed in it, in
seconds.  Host clock; every solve is synced."""


def read(run):
    rec = run["record"]
    if not rec.get("solves"):
        return None
    return rec["window_s"] / rec["solves"]
