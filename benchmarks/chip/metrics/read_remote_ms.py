"""Mean per batch of the remote leg inside the program's ``read_many``:
its ``fanstore.read_many.remote`` span around ``_fetch_with_failover``,
one coalesced fetch per owner (transport); 0 for a batch with none."""
from chipbench import programspans as ps


def read(run):
    reads = ps.within(run, ps.READ)
    remote = ps.remote_ns(reads)
    return ps.mean_ms(remote.get(r.id, 0) for r in reads)
