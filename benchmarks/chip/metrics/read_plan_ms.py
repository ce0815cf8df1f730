"""Mean per batch of the plan inside the program's ``read_many``
(``fanstore.read_many``'s self time: metadata lookups, replica choice,
cache-tier lookups): the span less its local copies (``local_ns``) and
less its remote leg (its ``fanstore.read_many.remote`` child)."""
from chipbench import programspans as ps


def read(run):
    reads = ps.within(run, ps.READ)
    remote = ps.remote_ns(reads)
    return ps.mean_ms(r.duration_ns - r.counters.get("local_ns", 0)
                      - remote.get(r.id, 0) for r in reads)
