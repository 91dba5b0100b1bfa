"""Share of the traced slice in which no operation ran on the device
(percent): one minus the union of the operation intervals over the
slice."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_frac
