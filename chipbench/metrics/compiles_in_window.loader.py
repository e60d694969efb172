"""Compile requests that missed the process's own cache inside the window
(each a compile, or a load from the persistent cache)."""


def read(record):
    if "spans" not in record:
        return None
    return float(record["compile_requests"])
