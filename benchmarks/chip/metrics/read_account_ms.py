"""Mean per batch of the modeled-cost bookkeeping inside the remote leg:
the ``account_ns`` each per-owner ``fanstore.fetch.remote`` span records
for ``_account_remote`` under the cluster's clock lock (the wait for the
lock included), summed over a read's owners; 0 for a batch with none."""
from chipbench import programspans as ps


def read(run):
    reads = ps.within(run, ps.READ)
    account = ps.account_ns(reads)
    return ps.mean_ms(account.get(r.id, 0) for r in reads)
