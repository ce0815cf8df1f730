"""Mean per batch of the local copies inside the program's
``read_many``: the ``local_ns`` counter of ``fanstore.read_many``, the
summed time of its ``fetch_local`` calls (store, local tier)."""
from chipbench import programspans as ps


def read(run):
    return ps.mean_ms(r.counters.get("local_ns", 0)
                      for r in ps.within(run, ps.READ))
