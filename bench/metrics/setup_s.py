"""setup_s: seconds from the start of the process until the window opens:
drawing the weights, building the engine, loading or compiling every
program, prefilling shared documents and replaying the lead-in."""


def read(run):
    return run.setup_s
