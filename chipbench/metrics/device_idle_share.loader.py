"""Share of the traced window in which no operation ran on the device."""
from chipbench.reduce import idle_pct


def read(record):
    return idle_pct(record)
