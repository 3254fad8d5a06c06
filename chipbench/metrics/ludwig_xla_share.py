"""Share of device busy time spent in XLA's own operations (the Ludwig
step's jnp stencils, relayouts and pads) rather than in the fused Pallas
launches, in percent.  Device trace."""


def read(run):
    tr = run["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    return tr["xla_s"] / tr["busy_s"] * 100.0
