"""Device plane: the transport's GB/s over the raw copy's GB/s for the
same bytes between the same memories (``jax.device_put`` and a fetch to
the host, or ``device_put`` chip to chip), the two interleaved round by
round in the traced run.  Same bytes, so it is raw seconds over transport
seconds.  Over 105% would be an error of the count, not a result.  Moves
``xfer_GBps``."""


def read(obs):
    fw, raw = obs.get("fw_seconds"), obs.get("raw_seconds")
    if not fw or not raw:
        return None
    return raw / fw * 100.0
