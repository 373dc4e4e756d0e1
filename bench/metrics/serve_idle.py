"""% of the traced window the fullest device is idle while the host is in
the server: the innermost program span then is a ``serve.*`` one
(``bench/lib/attribution.py``)."""

from bench.lib.attribution import idle_percent


def read(ctx, metric):
    return idle_percent(ctx, ("serve.",))
