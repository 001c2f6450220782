"""Mean drive time of a traced control cycle (observe, decide, apply)
minus the fused decide's device time per cycle, in ms: the host's share."""
from bench.metrics import common


def read(run):
    device = common.decide_device_ms(run)
    drive = common.drive_ms(run)
    if device is None or drive is None:
        return None
    return drive - device
