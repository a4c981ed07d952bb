"""setup_s: seconds from the start of the process to the opening of the
measured window: making the data, building the index, loading or
compiling and warming every shape the cell uses.  Host clock."""


def read(ctx):
    return ctx.setup_s
