"""Process start to the first scheduled arrival: data generation, host index
build, device upload, warmup of the cell's window classes, compile-cache
loads."""


def read(run):
    return run.setup_s
