"""Start of the process to the first measured step: imports, inputs made
from the seed, the data plane built, compiles or cache reads, warm-up."""


def read(run):
    return run.setup_s
