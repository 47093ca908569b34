"""Scheduler's prefix index: prompt tokens whose prefill the prefix cache
saved (the engine's ``prefix_tokens_reused`` counter, as a delta over the
window) over the prompt tokens of the requests first admitted in the
window, in percent."""


def read(run):
    rec = run.record
    admitted = sum(len(r.prompt) for r in rec.recs
                   if r.running_at is not None
                   and 0.0 <= r.running_at <= rec.close_t)
    if admitted == 0:
        return None
    reused = (rec.counters_close["prefix_tokens_reused"]
              - rec.counters_open["prefix_tokens_reused"])
    return 100.0 * reused / admitted
