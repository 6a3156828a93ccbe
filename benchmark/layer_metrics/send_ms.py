"""send: mean ``tick_stage_send_s`` (encode and hand to the TCP senders)."""


def read(r):
    return r.stage_ms("send")
