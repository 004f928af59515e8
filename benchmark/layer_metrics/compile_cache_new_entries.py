"""Files this run added to the compile cache directory. Read only where the
directory already held entries when the run began: a cell's first run in a
checkout compiles everything and says nothing about what escapes the cache."""


def read(ctx):
    if ctx["cache_was_empty"]:
        return None
    return float(ctx["cache_new_entries"])
