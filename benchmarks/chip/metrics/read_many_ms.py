"""Mean host span per batch of ``session.read_many`` (cluster + sessions),
over the batches whose read started inside the window."""
from chipbench.spanstats import mean_ms


def read(run):
    return mean_ms(run, "read_many")
