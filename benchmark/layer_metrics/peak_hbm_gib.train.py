"""Peak bytes held on the fullest chip after the window (arrays in use
plus the runtime's reservation for executables' temporaries;
harness/device.py), in GiB."""


def read(ctx):
    return ctx["peak_bytes"] / 2 ** 30 if ctx["peak_bytes"] else None
