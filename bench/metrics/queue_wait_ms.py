"""Mean of the batcher's own ``queue_us`` stamp (submit to panel dispatch),
in ms, over the requests of the window that were served."""


def read(run):
    done = run.window.served()
    if not done:
        return None
    return sum(r.response.queue_us for r in done) / len(done) * 1e-3
