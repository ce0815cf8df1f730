"""Mean host span per batch of the decode the loader's thread runs: the
unpacking of the files and their copy to the device."""
from chipbench.spanstats import mean_ms


def read(run):
    return mean_ms(run, "decode")
