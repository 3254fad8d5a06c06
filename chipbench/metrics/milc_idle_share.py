"""Idle share of the device in the traced window of a milc cell:
1 - busy / window, in percent, busy being the union of the intervals in
which an operation ran on the device (mean over the chips).  Device
trace."""

from chipbench.metrics._device import idle_share


def read(run):
    return idle_share(run)
