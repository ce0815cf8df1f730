"""Mean per batch of the time the ``PrefetchLoader``'s producer spent
putting a finished batch on its queue (``fanstore.loader.put_wait``):
blocked while the queue was full, microseconds where it never was."""
from chipbench import programspans as ps


def read(run):
    return ps.mean_ms(s.duration_ns
                      for s in ps.within(run, "fanstore.loader.put_wait"))
