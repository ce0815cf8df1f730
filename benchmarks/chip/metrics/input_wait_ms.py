"""Mean host span per step in which the consumer waits in
``next(loader)`` for the ``PrefetchLoader`` to hand it a batch."""
from chipbench.spanstats import mean_ms


def read(run):
    return mean_ms(run, "input_wait")
