"""Share of the HBM roofline of the whole Ludwig timestep, in percent: the
bytes any implementation must move per step (dist and q read and written
once, ``chipbench.work``) times the steps of the traced window, over the
device's busy time times its peak bandwidth.  Device trace."""

from chipbench import work
from chipbench.metrics._device import hbm_roofline


def read(run):
    return hbm_roofline(run, work.ludwig_bytes_per_site(),
                        run["record"].get("steps"))
