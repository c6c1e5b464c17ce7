"""Share of the traced training window in which no operation ran on the
device: 1 - (union of device op intervals) / (traced window)."""
from lib.readers import idle_share


def read(ctx):
    return idle_share(ctx) if ctx["kind"] == "train" else None
