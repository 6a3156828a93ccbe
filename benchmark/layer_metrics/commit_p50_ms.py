"""client: the median of the acknowledged writes of the traced run.  Not held
to a bound: one tick that starts late on one node leaves a standing backlog of
one slice in its peers' inboxes (the program drains one slice per source per
tick), and from then on the writes that node leads take two ticks more, to
the end of the run (PERF.md section 6).  ``ticks_per_commit`` is the same
number in tick periods: 6.5 undisturbed, 8.5 and 10.5 after such ticks."""


def read(r):
    if not r.commit_latencies_s:
        return None
    from benchmark.readings import percentile
    return 1e3 * percentile(r.commit_latencies_s, 50)
