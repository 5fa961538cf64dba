"""entry (engine, basic.Booster.update): host milliseconds inside one
update() call, mean over the traced iterations. Host clock, the
benchmark's own span."""


def read(ev):
    calls = ev.spans.seconds("update")
    return 1e3 * sum(calls) / len(calls) if calls else None
