"""Host-device transfer: device-to-host copies per statement of the window,
the points where the host blocked on a device value, from
``ExecStats.d2h_copies``.  None where the window has no statement or the
engine has no such counter."""


def read(w):
    v = w.stats.get("d2h_copies")
    return v / w.statements if w.statements and v is not None else None
