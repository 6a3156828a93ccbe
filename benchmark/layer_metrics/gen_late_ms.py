"""client: how late the open-loop driver fired against its schedule (p95 of
fired - due), so that a starved generator is not read as a fast server."""


def read(r):
    if not r.gen_late_s:
        return None
    from benchmark.readings import percentile
    return 1e3 * percentile(r.gen_late_s, 95)
