"""Host-device transfer: megabytes copied from the device to the host per
statement of the window, from ``ExecStats.d2h_bytes`` (``transfer.to_host``:
row takes, concats, key and mask reads).  None where the window has no
statement or the engine has no such counter."""


def read(w):
    v = w.stats.get("d2h_bytes")
    return v / 1e6 / w.statements if w.statements and v is not None else None
