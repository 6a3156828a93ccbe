"""persist: mean WAL staging plus fsync barrier of one tick."""


def read(r):
    return r.stage_ms("wal", "fsync")
