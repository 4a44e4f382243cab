"""Device: one minus (union of device-operation intervals over the traced
slice), on the least busy device, in percent: how far the host holds the
chip back."""


def read(run, arg=None):
    busy = run.busy()
    return 100.0 * busy["idle_share"] if busy else None
