"""KV manager and allocator: preemptions in the window (the engine's
``preemptions`` counter, as a delta) over the requests that finished in
it."""


def read(run):
    rec = run.record
    done = sum(1 for r in rec.recs
               if r.token_times and r.seen >= r.max_new
               and 0.0 <= r.token_times[-1] <= rec.close_t)
    if done == 0:
        return None
    return ((rec.counters_close["preemptions"]
             - rec.counters_open["preemptions"]) / done)
