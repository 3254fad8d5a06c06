"""Shares of the traced window that several readers compute alike."""


def idle_share(run):
    """1 - busy / window, in percent, from the device trace."""
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0


def hbm_roofline(run, bytes_per_site, units):
    """Required bytes over (device busy x peak HBM bandwidth), percent.
    ``units`` is the number of steps or solver iterations in the window;
    bytes are per chip (each chip holds its share of the sites)."""
    tr = run["trace"]
    if tr is None or tr["busy_s"] <= 0 or not units:
        return None
    sites = run["record"]["sites_per_chip"]
    need = bytes_per_site * sites * units
    return need / (tr["busy_s"] * run["peaks"]["hbm_bytes_per_s"]) * 100.0
