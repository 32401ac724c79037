"""The device's ``peak_bytes_in_use`` after the window over its
``bytes_limit``, in %."""


def read(run):
    peak = run.memory.get("peak_bytes_in_use")
    limit = run.memory.get("bytes_limit")
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
