"""Process start to the window's first edge: imports, reaching the chip,
authoring or finding the data set, the model check, train()'s own start-up
(initialisation, compilation or cache loads) and warm-up."""


def read(ctx):
    return ctx["setup_s"]
