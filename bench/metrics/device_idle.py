"""``device_idle.<cells>``: the share of the traced window in which no
operation ran on the device (1 - union of op intervals / window), in %."""


def read(name, r):
    return 100.0 * r.summary.idle_share
