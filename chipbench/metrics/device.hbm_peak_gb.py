"""Peak device memory in GB: ``peak_bytes_in_use`` (buffers) plus
``peak_bytes_reserved`` (program scratch, which the first counter does
not show)."""


def read(run):
    if not run.memory:
        return None
    return (run.memory["peak_bytes_in_use"]
            + run.memory["peak_bytes_reserved"]) / 1e9
