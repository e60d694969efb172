"""Compile requests that missed the process's own cache during set-up
(each a compile, or a load from the persistent cache): one per program
the window's traffic needs, since set-up warms every shape it uses."""


def read(record):
    if "spans" not in record:
        return None
    return float(record["setup_compile_requests"])
