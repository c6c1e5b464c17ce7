"""Share of the traced window of the offline-decode cell in which no
operation ran on the device."""
from lib.readers import idle_share


def read(ctx):
    return idle_share(ctx) if ctx["kind"] == "serve" else None
