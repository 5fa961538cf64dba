"""compile (compile/): executables JAX built or fetched from its cache
inside the measured window. A count; anything but 0 fails `correct`."""


def read(ev):
    return ev.window["compiles"]
