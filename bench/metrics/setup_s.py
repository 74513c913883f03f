"""setup_s: seconds from the start of the command to the window's start:
JAX start-up, engine init, the store fill and warming every shape the
window uses (compilation, where the cache does not hold it)."""


def read(run):
    return run.setup_s
