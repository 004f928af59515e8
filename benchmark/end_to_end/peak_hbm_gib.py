"""Peak device memory on the fullest chip, after the window: buffers
(``peak_bytes_in_use``) plus the programs' scratch (``peak_bytes_reserved``),
which this runtime counts apart."""


def read(ctx):
    return ctx["peak_bytes"] / 2 ** 30
