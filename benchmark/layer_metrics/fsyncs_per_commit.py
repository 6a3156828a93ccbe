"""persist: fsync calls of all nodes over the window per acknowledged write."""


def read(r):
    return r.fsync_calls / r.acked_writes if r.acked_writes else None
