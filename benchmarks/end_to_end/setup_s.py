"""Process start to the opening of the window: data, dataset or model,
the compile-paying call, warm-up."""


def read(ev):
    return ev.setup_s
