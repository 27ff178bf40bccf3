"""setup_s: seconds from the start of the process to the start of the
window: imports, building the system under test, compiling and warming up."""


def read(ctx):
    return ctx.setup_s
