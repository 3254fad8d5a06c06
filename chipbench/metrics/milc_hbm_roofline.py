"""Share of the HBM roofline of the Wilson-CG iterations, in percent: the
bytes any implementation must move per iteration (``chipbench.work``)
times the iterations of the solves in the traced window, over the
device's busy time times its peak bandwidth.  Device trace."""

from chipbench import work
from chipbench.metrics._device import hbm_roofline


def read(run):
    return hbm_roofline(run, work.cg_bytes_per_site(),
                        sum(run["record"].get("iterations", ())))
