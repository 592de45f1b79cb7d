"""Process start to the first timed request: building the configuration
from the seed, the program's tree load and placement, and warming (and, on
a cold cache, compiling) the cell's programs."""


def read(run):
    return run.setup_s
