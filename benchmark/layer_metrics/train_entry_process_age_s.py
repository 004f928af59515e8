"""Age of the process, by the OS, as ``train()`` was entered: the
``process_age_s`` attribute of the first ``startup.devices`` phase. In the
benchmark it holds the interpreter's start, the imports, reaching the chip,
authoring and the model check."""

from reduce import startup


def read(ctx):
    first = startup.entry(ctx["spans"])
    return first["args"].get("process_age_s") if first else None
