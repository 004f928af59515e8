"""Seconds of ``startup.devices`` and ``startup.dataset``: argument checks,
reaching the devices, placing the compile cache, the mesh, opening the data
set and building the task."""

from reduce import startup


def read(ctx):
    return startup.phases_s(ctx["spans"],
                            ("startup.devices", "startup.dataset"))
