"""Page pool (ServingEngine._device_pages): share of the traced window spent
in uploads of the host page store that changed the device mirror, each
timed by the harness's span and synchronized inside it; moves
``itl_p99_ms``."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * sum(w.trace.upload_seconds) / w.trace.window_s
