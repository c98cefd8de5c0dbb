"""Host-device transfer: megabytes of host arrays that entered a device
program per statement of the window, from ``ExecStats.h2d_bytes`` (kernel
entry points, jitted predicates and map chains, explicit copies).  None
where the window has no statement or the engine has no such counter."""


def read(w):
    v = w.stats.get("h2d_bytes")
    return v / 1e6 / w.statements if w.statements and v is not None else None
