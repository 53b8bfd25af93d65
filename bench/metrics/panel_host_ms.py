"""Host time per panel, in ms: the length of each ``bench.panel`` span (the
guard and engine call the batcher makes, blocked on its result) less the
device-busy time inside it, averaged over the window's panels."""


def read(run):
    tr = run.trace
    spans = tr.spans_named("panel")
    if not spans or not tr.ops:
        return None
    dev = tr.devices()[0]
    host = [(s.end - s.start) * 1e-9 - tr.busy_s(s.start, s.end, dev)
            for s in spans]
    return sum(host) / len(host) * 1e3
